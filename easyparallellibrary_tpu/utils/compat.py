"""The manual-sharding surface, spelled once.

Every module that enters a manual region calls :func:`shard_map` here
instead of ``jax.shard_map`` directly, so the framework's vocabulary
(``manual_axes``, ``check``) maps onto jax's (``axis_names``,
``check_vma``) in exactly one file.  The engines disable the check
because per-device branch divergence is intentional.
"""

from __future__ import annotations

from typing import Optional

import jax


def shard_map(f, mesh, in_specs, out_specs,
              manual_axes: Optional[frozenset] = None,
              check: bool = False):
  """Manual-map ``f`` over ``mesh``.

  ``manual_axes``: axes the body is manual over (None = all mesh axes).
  Partial-manual regions pass a subset; the remaining axes stay auto
  (GSPMD) inside the body.
  """
  kwargs = {}
  if manual_axes is not None:
    kwargs["axis_names"] = frozenset(manual_axes)
  return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=check, **kwargs)


def ambient_manual_axes() -> frozenset:
  """Mesh axes that are Manual in the ambient shard_map region (empty
  outside one)."""
  return frozenset(jax.sharding.get_abstract_mesh().manual_axes)
