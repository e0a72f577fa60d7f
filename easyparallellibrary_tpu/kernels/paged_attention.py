"""Paged-attention decode — Pallas TPU kernel + pure-jnp reference.

The serving engine's paged KV cache (serving/kv_cache.py) stores K/V in
fixed-size blocks indexed through per-slot block tables, so decode
attention must GATHER a token's context through that indirection instead
of slicing a contiguous per-slot region.  This module provides the two
implementations of that gather-attend, behind one dispatcher:

* **reference** — pure jnp (``jnp.take`` over the block dimension,
  dense masked softmax), numerically a MIRROR of
  ``models.slot_core.slot_cache_attend``: same einsum structure, same
  ``-1e9`` mask, same fp32 softmax, same dtype flow.  This is the CPU /
  correctness path — the engine's greedy bit-exactness contract vs
  ``generate(use_cache=True)`` is carried by this implementation, and
  the TPU kernel is tested against it (tests/test_serving_paged.py).
* **pallas** — a streaming TPU kernel in the flash-attention house
  style (kernels/flash_attention.py): grid ``(T, MB)`` with every head
  of a KV block in one program, the block table scalar-prefetched so
  each KV block's DMA is issued straight from the table entry, online
  softmax carried across the MB grid steps in VMEM scratch.  Under the per-token causal bound the block index map
  clamps to the last live block (Mosaic elides the repeated DMA) and
  ``pl.when`` skips the dead compute — so a token's attend costs its own
  context length, not the table width.

Dispatch rule (docs/serving.md): the kernel runs only when the active
backend is TPU; everywhere else the reference path runs.  Overrides ride
the flash kernels' autotune pattern: ``set_paged_attention_impl()``
programmatically, or ``EPL_PAGED_ATTENTION_IMPL`` in the environment
(``pallas`` | ``reference`` | ``interpret`` — the last runs the kernel
in Pallas interpreter mode, the parity tests' CPU vehicle).

Shapes (one flat token batch, serving/engine.py):

* ``q``                 ``[T, H, hd]``  this step's query rows
* ``k_pages/v_pages``   ``[NB, bs, H, hd]`` the paged cache pool
* ``tables_tok``        ``[T, MB]`` int32 — each token's slot block
  table row (``block_tables[slot_ids]``, gathered once per step)
* ``positions``         ``[T]`` int32 — each token's absolute position

Token ``t`` attends virtual rows ``j <= positions[t]``, row ``j``
resolved through ``tables_tok[t, j // bs]`` to pool row
``table_entry * bs + j % bs``.  Rows past a slot's allocation resolve to
the reserved null block; they sit at ``j > positions[t]`` by
construction and are masked (serving/kv_cache.py docstring).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# The kernel's name in a device trace (see ``flash_attention.FLASH_FWD``).
PAGED_ATTN = "paged_attn"

IMPLS = ("pallas", "reference", "interpret")

# Programmatic override (set_paged_attention_impl), consulted before the
# environment and the backend default — same precedence shape as the
# flash kernels' autotune table (explicit entry beats heuristic).
_IMPL_OVERRIDE = [None]


def set_paged_attention_impl(impl: Optional[str]) -> None:
  """Pin the paged-attention implementation (``None`` restores backend
  dispatch).  Benchmark/test hook — mirrors flash's ``set_block_want``."""
  if impl is not None and impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  _IMPL_OVERRIDE[0] = impl


def default_paged_impl() -> str:
  """The dispatch rule: override > ``EPL_PAGED_ATTENTION_IMPL`` >
  backend (``pallas`` on TPU, ``reference`` elsewhere)."""
  if _IMPL_OVERRIDE[0] is not None:
    return _IMPL_OVERRIDE[0]
  env = os.environ.get("EPL_PAGED_ATTENTION_IMPL", "")
  if env:
    if env not in IMPLS:
      raise ValueError(
          f"EPL_PAGED_ATTENTION_IMPL must be one of {IMPLS}; got {env!r}")
    return env
  return "pallas" if jax.default_backend() == "tpu" else "reference"


# -------------------------------------------------------------- reference --


def paged_attention_reference(q, k_pages, v_pages, tables_tok, positions):
  """Dense-gather reference: numerically the mirror of
  ``slot_cache_attend``'s attend half, so the paged engine's greedy
  output stays bit-identical to the contiguous engine's on this path
  (padded virtual rows are exactly ``-1e9``-masked; their softmax terms
  are exact zeros and change no sums — the same argument that lets the
  contiguous cache over-allocate by a chunk)."""
  T, H, hd = q.shape
  bs = k_pages.shape[1]
  MB = tables_tok.shape[1]
  L = MB * bs
  dtype = q.dtype
  scale = 1.0 / jnp.sqrt(hd).astype(dtype)
  kk = jnp.take(k_pages, tables_tok, axis=0).reshape(T, L, H, hd)
  vv = jnp.take(v_pages, tables_tok, axis=0).reshape(T, L, H, hd)
  logits = jnp.einsum("thd,tlhd->thl", q, kk) * scale
  valid = jnp.arange(L)[None, None, :] <= positions[:, None, None]
  logits = jnp.where(valid, logits, jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  return jnp.einsum("thl,tlhd->thd", probs.astype(dtype), vv)


# ----------------------------------------------------------------- pallas --


def _paged_kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, bs: int, num_blocks_grid: int,
                  scale: float):
  """One (token, table-slot) grid step: score this KV block against the
  token's query rows for ALL heads, fold into the online softmax carried
  in VMEM scratch, emit on the last table slot.

  Every value keeps heads on the sublane dim and head_dim on the lane
  dim (``[bs, H, hd]`` tiles, ``[.., H, 1]`` row statistics), so the
  body is elementwise VPU work plus lane/major-dim reductions — no
  per-head slicing and no layout change.  Decode attention is bandwidth-bound
  (one query row per head); the MXU has nothing to amortize here."""
  t = pl.program_id(0)
  i = pl.program_id(1)

  @pl.when(i == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  pos = pos_ref[t]
  # Blocks wholly past the token's position are dead: their DMA is
  # already elided by the clamped index map, skip the compute too.
  live = i * bs <= pos

  @pl.when(live)
  def _compute():
    q = q_ref[...].astype(jnp.float32)              # [1, H, hd]
    k = k_ref[0].astype(jnp.float32)                # [bs, H, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jnp.sum(q * k, axis=-1, keepdims=True) * scale      # [bs, H, 1]
    row = i * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(row <= pos, s, NEG_INF)
    m_prev = m_ref[...][:, :1]                      # [H, 1]
    l_prev = l_ref[...][:, :1]
    new_m = jnp.maximum(m_prev, jnp.max(s, axis=0))
    p = jnp.exp(s - new_m[None])                    # [bs, H, 1]
    corr = jnp.exp(m_prev - new_m)                  # [H, 1]
    new_l = l_prev * corr + jnp.sum(p, axis=0)
    m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(new_l, l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + jnp.sum(p * v, axis=0)

  @pl.when(i == num_blocks_grid - 1)
  def _finalize():
    l_safe = jnp.maximum(l_ref[...][:, :1], 1e-30)
    o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, tables_tok, positions,
                           interpret: Optional[bool] = None):
  """Streaming paged-attend kernel.  ``interpret=None`` follows the
  flash kernels' rule (interpreter mode off-TPU) so the kernel path can
  be exercised on CPU in tests."""
  T, H, hd = q.shape
  bs = k_pages.shape[1]
  MB = tables_tok.shape[1]
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  scale = 1.0 / math.sqrt(hd)
  # The index maps receive the scalar-prefetch refs after the grid
  # coordinates; dead blocks clamp to the token's last live table slot
  # so Mosaic elides the repeated DMA.
  def kv_idx(t, i, tab, pos):
    i = jnp.minimum(i, pos[t] // bs)
    return (tab[t, i], 0, 0, 0)

  # Mosaic wants the last two block dims tile-aligned or FULL: blocks
  # span every head (H, hd are the arrays' own trailing dims) and the
  # head loop lives inside the program.
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(T, MB),
      in_specs=[
          pl.BlockSpec((1, H, hd), lambda t, i, tab, pos: (t, 0, 0)),
          pl.BlockSpec((1, bs, H, hd), kv_idx),
          pl.BlockSpec((1, bs, H, hd), kv_idx),
      ],
      out_specs=pl.BlockSpec((1, H, hd),
                             lambda t, i, tab, pos: (t, 0, 0)),
      scratch_shapes=[
          pltpu.VMEM((H, 128), jnp.float32),      # running max
          pltpu.VMEM((H, 128), jnp.float32),      # running denom
          pltpu.VMEM((H, hd), jnp.float32),       # output accumulator
      ],
  )
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
  return pl.pallas_call(
      functools.partial(_paged_kernel, bs=bs, num_blocks_grid=MB,
                        scale=scale),
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((T, H, hd), q.dtype),
      interpret=interpret,
      name=PAGED_ATTN,
      **kwargs,
  )(tables_tok.astype(jnp.int32), positions.astype(jnp.int32),
    q, k_pages, v_pages)


# --------------------------------------------------------------- dispatch --


def paged_attention(q, k_pages, v_pages, tables_tok, positions,
                    impl: Optional[str] = None):
  """Paged gather-attend over a flat token batch (module docstring).
  ``impl=None`` applies the dispatch rule; the serving engine resolves
  the impl ONCE at construction so the jitted step never consults the
  environment."""
  impl = impl or default_paged_impl()
  if impl == "reference":
    return paged_attention_reference(q, k_pages, v_pages, tables_tok,
                                     positions)
  return paged_attention_pallas(q, k_pages, v_pages, tables_tok,
                                positions,
                                interpret=(impl == "interpret" or None))
