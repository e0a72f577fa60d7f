"""Grouped matmul for dropless expert layers — Pallas TPU kernel + reference.

``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``: the rows
of ``lhs`` ``[M, K]`` are sorted by group (models/moe.py sorts a step's
token-to-expert assignments by expert), ``group_sizes`` int32 ``[E]`` says
how many consecutive rows each group holds, and ``rhs`` ``[E, K, N]`` holds
one matrix a group.  Rows at or beyond ``sum(group_sizes)`` belong to no
group (positions beyond a slot's ``num_valid``, idle slots: they sort
behind the last group) and come out as ZEROS.  One algorithm, two
lowerings:

* **reference** — ``jax.lax.ragged_dot``: the same mathematics, whatever
  XLA makes of it on the backend at hand.
* **pallas** — one launch named ``moe_gmm``.  The rows are cut into tiles
  of :data:`TILE_M`; a VISIT is one (group, row tile) pair that share at
  least one row, and the visits are enumerated in group order from the
  group sizes alone (a cumulative sum and a search: no sort, no scatter),
  so a group without rows has no visit: it is skipped, its matrix never
  read.  Grid: column tiles of ``rhs`` outermost, visits innermost, the
  whole ``K`` in one block.  The ``rhs`` block of a visit is ``[K, tn]`` of
  its group's matrix; consecutive visits of one group (its rows straddle a
  tile boundary) name the same block, which the pipeline keeps: every
  group's matrix is streamed from HBM ONCE a call however few rows the
  group has.  That is the regime the kernel is for (small-active expert
  decode: ~23 rows an expert against 12.6 MB of weights); the rows'
  tiles are re-read once a column tile, a few per cent of the weights'
  bytes.  A visit multiplies the whole row tile and stores under a row
  mask into an output block that stays in VMEM over the visits of its row
  tile.  The number of visits is a value (scalar-prefetched): one compile;
  grid steps beyond it name the blocks already held and do nothing.

Arithmetic: the contraction accumulates in float32 from the operands'
dtype and is rounded once to the output's (``lhs``'s) dtype.  Equal to the
reference to rounding (another order of the same sums).

Dispatch rule (:func:`resolve_moe_gmm_impl`, shaped like
``resolve_kv_write_impl``): the kernel when the backend is a TPU, the
operands sit whole on one chip and the shapes fit
(:func:`moe_gmm_fits`); the reference everywhere else.  It reads the
backend and what it is handed — no configuration field, environment
variable or setter; ``interpret`` runs the kernel in Pallas interpreter
mode (the CPU parity tests, by name or by patching
:func:`_backend_impl`).  The engine resolves it once when it builds its
step and records it (``engine.lowerings["moe_gmm_impl"]``, trace metadata
``serving/moe_gmm_impl``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easyparallellibrary_tpu.env import Env

# The kernel's name in a device trace (see ``flash_attention.FLASH_FWD``).
# The benchmark reads it (PERF.md section 3).
MOE_GMM = "moe_gmm"

IMPLS = ("pallas", "reference", "interpret")

# Rows a tile: one pass of the MXU's 128 x 128 array.  Fewer rows cost the
# same weight loads, so a narrower tile saves nothing.
TILE_M = 128
LANES = 128
# Columns a ``rhs`` block may span, widest first; the block ``[K, tn]`` is
# the unit of the weights' DMA.
_TILES_N = (1024, 512, 256, 128)
_RHS_BLOCK_BYTES = 2 * 1024 * 1024
# The scoped-VMEM the call asks for: the rhs block, the row tile and the
# output block double-buffered, and the float32 product.
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 24 * 1024 * 1024


def _backend_impl() -> str:
  """The lowering this backend takes when the shapes allow it.  The CPU
  parity tests patch it to ``interpret``."""
  return "pallas" if jax.default_backend() == "tpu" else "reference"


def tile_n(k: int, n: int, dtype) -> int:
  """Columns per ``rhs`` block ``[k, tn]``: the widest of
  :data:`_TILES_N` that divides ``n`` and stays within the block and VMEM
  budgets; ``n`` itself when ``n`` is narrower than a lane tile; 0 if
  nothing fits."""
  size = jnp.dtype(dtype).itemsize
  wide = [t for t in _TILES_N if n % t == 0] or ([n] if n < LANES else [])
  for tn in wide:
    vmem = (2 * k * tn * size + 2 * TILE_M * k * size
            + 2 * TILE_M * tn * size + TILE_M * tn * 4)
    if k * tn * size <= _RHS_BLOCK_BYTES and vmem <= _VMEM_BUDGET:
      return tn
  return 0


def moe_gmm_fits(lhs_shape, rhs_shape, dtype) -> bool:
  """Whether the kernel can tile ``lhs [M, K] x rhs [E, K, N]`` of
  ``dtype``: 32-bit or 16-bit floats, ``K`` in whole lane tiles (it is
  the row tile's minor dimension) and a column tile within the budgets."""
  (_, k), (_, k2, n) = lhs_shape, rhs_shape
  dtype = jnp.dtype(dtype)
  if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
    return False
  if k != k2 or k % LANES:
    return False
  return tile_n(k, n, dtype) > 0


def resolve_moe_gmm_impl(lhs_shape, rhs_shape, dtype,
                         sharded: bool = False) -> str:
  """The dispatch rule: the backend's lowering (``pallas`` on a TPU,
  ``reference`` elsewhere), and ``reference`` whenever the operands live
  on a multi-device mesh (``sharded``: the SPMD partitioner cannot split
  a Mosaic call) or the shapes do not fit (:func:`moe_gmm_fits`)."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not moe_gmm_fits(lhs_shape, rhs_shape, dtype)):
    return "reference"
  return impl


def _zero_beyond(out, group_sizes):
  """Rows of no group are zeros, whatever the lowering left there."""
  rows = jnp.arange(out.shape[0], dtype=jnp.int32)[:, None]
  return jnp.where(rows < jnp.sum(group_sizes.astype(jnp.int32)), out,
                   jnp.zeros((), out.dtype))


# -------------------------------------------------------------- reference --


def moe_gmm_reference(lhs, rhs, group_sizes):
  """``jax.lax.ragged_dot``: row ``r`` of group ``g`` times ``rhs[g]``,
  float32 accumulation, rounded to ``lhs``'s dtype."""
  out = jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                           group_sizes.astype(jnp.int32),
                           preferred_element_type=jnp.float32)
  return _zero_beyond(out.astype(lhs.dtype), group_sizes)


# ----------------------------------------------------------------- pallas --


def visits(group_sizes, num_tiles: int, tile_m: int = TILE_M):
  """The (group, row tile) pairs that share a row, in group order, from
  the group sizes alone.  Returns ``(group_of, tile_of, first, count,
  starts, ends)``: ``group_of`` / ``tile_of`` int32 ``[E + num_tiles -
  1]`` (the most there can be), entries at or beyond ``count`` repeating
  the last visit's (so a grid step there names blocks already held);
  ``first`` marks the first visit of a row tile; ``starts`` / ``ends``
  the groups' row ranges."""
  sizes = group_sizes.astype(jnp.int32)
  E = sizes.shape[0]
  ends = jnp.cumsum(sizes)
  starts = ends - sizes
  first_tile = starts // tile_m
  tiles = jnp.where(sizes > 0, (ends - 1) // tile_m - first_tile + 1, 0)
  visit_ends = jnp.cumsum(tiles)
  count = visit_ends[-1]
  v = jnp.arange(E + num_tiles - 1, dtype=jnp.int32)
  v = jnp.minimum(v, jnp.maximum(count - 1, 0))
  # The group of visit v: how many groups end their visits at or before it.
  group_of = jnp.minimum(
      jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
      E - 1)
  tile_of = first_tile[group_of] + v - (visit_ends - tiles)[group_of]
  tile_of = jnp.clip(tile_of, 0, num_tiles - 1)
  first = jnp.concatenate(
      [jnp.ones((1,), jnp.int32),
       (tile_of[1:] != tile_of[:-1]).astype(jnp.int32)])
  return group_of, tile_of, first, count[None], starts, ends


def _moe_gmm_kernel(group_ref, tile_ref, first_ref, count_ref, start_ref,
                    end_ref, lhs_ref, rhs_ref, out_ref):
  """One (column tile, visit) grid step: the visit's row tile times its
  group's ``[K, tn]`` block, stored under the mask of the group's rows;
  rows of the tile no group has claimed yet are zeros."""
  v = pl.program_id(1)

  @pl.when(v < count_ref[0])
  def _visit():
    g = group_ref[v]
    lhs, rhs = lhs_ref[...], rhs_ref[...]
    # 16-bit operands multiply exactly on the MXU whatever precision the
    # caller's context names (and Mosaic refuses a float32 contraction of
    # them); float32 operands follow the context, as the reference does.
    precision = (None if lhs.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    acc = jax.lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())),
                              precision=precision,
                              preferred_element_type=jnp.float32)
    tm = lhs.shape[0]
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, 1), 0)
    mine = (row >= start_ref[g]) & (row < end_ref[g])
    held = jnp.where(first_ref[v] == 1, jnp.zeros_like(out_ref),
                     out_ref[...])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), held)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_pallas(lhs, rhs, group_sizes, interpret: bool = False):
  """The grouped matmul as one kernel; ``interpret`` runs it in Pallas
  interpreter mode (any backend).  Jitted, so that the layers of one step
  share one trace and one Mosaic lowering a shape, as ``kv_write_pallas``
  does; XLA inlines the calls."""
  M, K = lhs.shape
  E, _, N = rhs.shape
  dtype = lhs.dtype
  tn = tile_n(K, N, dtype)
  if not tn:
    raise ValueError(f"moe_gmm cannot tile rhs {rhs.shape} of {dtype}")
  num_tiles = pl.cdiv(M, TILE_M)
  if num_tiles * TILE_M != M:
    lhs = jnp.pad(lhs, ((0, num_tiles * TILE_M - M), (0, 0)))
  group_of, tile_of, first, count, starts, ends = visits(
      group_sizes, num_tiles)

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=6,
      grid=(N // tn, E + num_tiles - 1),
      in_specs=[
          pl.BlockSpec((TILE_M, K), lambda n, v, g, t, *_: (t[v], 0)),
          pl.BlockSpec((None, K, tn), lambda n, v, g, t, *_: (g[v], 0, n)),
      ],
      out_specs=pl.BlockSpec((TILE_M, tn), lambda n, v, g, t, *_: (t[v], n)),
  )
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
  out = pl.pallas_call(
      _moe_gmm_kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((num_tiles * TILE_M, N), dtype),
      interpret=interpret,
      name=MOE_GMM,
      **kwargs,
  )(group_of, tile_of, first, count, starts, ends, lhs, rhs.astype(dtype))
  # Row tiles no visit reached were never written, and the rows of a
  # visited tile beyond the last group hold zeros already.
  return _zero_beyond(out[:M], group_sizes)


# --------------------------------------------------------------- dispatch --


def moe_gmm(lhs, rhs, group_sizes, impl: Optional[str] = None):
  """Each row of ``lhs`` times its group's matrix (module docstring);
  returns ``out [M, N]`` in ``lhs``'s dtype.  ``impl=None`` applies the
  dispatch rule to the shapes at hand, and takes the operands as spread
  over chips whenever a multi-device mesh has been built; the serving
  engine resolves the impl from its own mesh and passes it."""
  if impl is None:
    impl = resolve_moe_gmm_impl(lhs.shape, rhs.shape, lhs.dtype,
                                sharded=Env.get().mesh_built())
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  if impl == "reference":
    return moe_gmm_reference(lhs, rhs, group_sizes)
  return moe_gmm_pallas(lhs, rhs, group_sizes, interpret=impl == "interpret")
