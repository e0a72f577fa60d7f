"""One process per chip.

A TPU chip belongs to one process at a time, and a JAX process reaches
for every chip of its host.  A parent that has initialised JAX on the TPU
therefore owns the chips, and a child that needs them fails or hangs; so
do two children started side by side.  The supported single-host shape is
ONE process driving all local chips.  The places that spawn JAX workers
(utils/launcher.py, serving/transport.py) ask here first and refuse with
:class:`ChipOwnershipError` instead of letting the child find out.
"""

from __future__ import annotations

from typing import Mapping


class ChipOwnershipError(RuntimeError):
  """A worker process was about to reach for chips another process of
  this host owns."""


def reaches_for_tpu(env: Mapping[str, str]) -> bool:
  """Whether a JAX process started with ``env`` would take the host's
  TPU chips if it has any: true unless ``JAX_PLATFORMS`` names platforms
  and ``tpu`` is not among them.  Decided from the environment alone —
  looking for the chips with JAX would take them."""
  platforms = env.get("JAX_PLATFORMS", "")
  return not platforms or "tpu" in platforms.split(",")


def this_process_holds_tpu() -> bool:
  """Whether this process has already initialised JAX on a TPU."""
  import jax
  from jax._src import xla_bridge
  return (xla_bridge.backends_are_initialized()
          and jax.default_backend() == "tpu")
