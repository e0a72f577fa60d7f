"""Index scores of learned sparse attention — Pallas TPU kernel + the XLA
reference, and the threshold that turns scores into a selection.

A full layer of models/dots3_note.py chooses the rows it attends through a
small INDEXER (DeepSeek-V3.2's): every query ``t`` scores every cached row
``s <= t`` of its slot,

    ``I(t, s) = sum_j w_j(t) * ReLU(qI_j(t) . kI(s))``,

over ``Hi`` index heads of width ``d`` against ONE index key a position
(the slot's index leaf ``[slots, Lc, d]``, kept in rows: ``d`` is a lane
tile), and attends the ``top_k`` largest.  One algorithm, two lowerings:

* **reference** — an einsum to ``[B, C, Hi, Lc]`` float32, the ReLU, the
  weights, a sum over the heads.  At a serving cell's sizes that tensor is
  gigabytes: correct everywhere, and what the kernel is tested against.
* **pallas** — launches named ``dsa_index`` (two a layer where the chunk
  is tiled: the slots that feed more than one position on tiles of 8, the
  decoding slots on their one position), grid over the step's live (slot,
  position tile) pairs (``slot_attention.live_tiles``) and blocks of the
  leaf.  The heads are folded inside VMEM: a tile's
  ``Hi x tp`` query rows times a key block give ``[Hi x tp, block]``
  scores that never leave it; what is written is ``[tp, block]``.  Blocks
  beyond the tile's last query are not fetched and are written as
  :data:`MASKED`.

Either way the result is ``[B, C, Lc]`` float32 with :data:`MASKED` at
every ``s > t`` and at every row at or beyond the slot's bound.  A dead
chunk position's row (``>= num_valid``) holds anything.

Arithmetic: products of the compute-dtype operands accumulate in float32;
the ReLU, the weights and the sum over heads are float32.  Positive
factors common to a query's scores (the published ``Hi^-1/2`` and
``d^-1/2``) change no choice and are left out.

:func:`kth_largest` finds each query's ``k``-th largest score EXACTLY by a
bitwise search over the scores' order-preserving integer keys (32 counting
passes XLA fuses; a sort of ``Lc`` scores a query is what a TPU does
slowly).  A row is selected iff its score is at least that threshold:
exact top-k wherever no two of a query's scores are equal.

Dispatch rule (:func:`resolve_dsa_index_impl`): as the other kernels'.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easyparallellibrary_tpu.env import Env
from easyparallellibrary_tpu.kernels.slot_attention import (
    LANES, live_tiles, split_decodes, sublane_tile)

# The kernel's name in a device trace.  The benchmark reads it (PERF.md
# section 3).
DSA_INDEX = "dsa_index"

IMPLS = ("pallas", "reference", "interpret")

# What a score no query may select reads (``s > t``, a row at or beyond
# the bound): finite, below every real score.
MASKED = -1e30

_BLOCKS = (512, 256, 128)
_VMEM_BUDGET = 24 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def _backend_impl() -> str:
  """The lowering this backend takes when the shapes allow it.  The CPU
  parity tests patch it to ``interpret``."""
  return "pallas" if jax.default_backend() == "tpu" else "reference"


def _tile(chunk: int) -> int:
  """Chunk positions of one query tile: a float32 sublane tile where the
  chunk is a whole number of them, else the whole chunk."""
  return 8 if chunk % 8 == 0 else chunk


def _block(Lc: int, d: int, dtype, rows: int, tp: int) -> int:
  size = jnp.dtype(dtype).itemsize
  for block in _BLOCKS:
    if block > Lc:
      continue
    vmem = (2 * block * d * size + 2 * rows * d * size
            + 2 * rows * LANES * 4 + 2 * tp * block * 4
            + 3 * rows * block * 4)
    if vmem <= _VMEM_BUDGET:
      return block
  return 0


def dsa_index_fits(leaf_shape, dtype, chunk: int, index_heads: int) -> bool:
  """Whether the kernel can tile an index leaf ``[B, Lc, d]`` of ``dtype``
  for ``chunk`` query positions of ``index_heads`` heads: a 16- or 32-bit
  float, ``d`` whole lane tiles, at least 128 rows, a chunk of at most
  128, a tile's rows in whole sublane tiles, a block within the budget."""
  if len(leaf_shape) != 3:
    return False
  _, Lc, d = leaf_shape
  dtype = jnp.dtype(dtype)
  if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
    return False
  if d % LANES or Lc < LANES or not 1 <= chunk <= LANES:
    return False
  tp = _tile(chunk)
  if (index_heads * tp) % sublane_tile(dtype) or tp % 8:
    return False
  return _block(Lc, d, dtype, index_heads * tp, tp) > 0


def resolve_dsa_index_impl(leaf_shape, dtype, chunk: int, index_heads: int,
                           sharded: bool = False) -> str:
  """The dispatch rule: the backend's lowering, and ``reference``
  whenever the leaf lives on a multi-device mesh or the shapes do not fit
  (:func:`dsa_index_fits`)."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not dsa_index_fits(leaf_shape, dtype, chunk, index_heads)):
    return "reference"
  return impl


# -------------------------------------------------------------- reference --


def dsa_index_reference(q, w, keys, cursors, num_valid=None):
  """``q`` ``[B, C, Hi, d]``, ``w`` float32 ``[B, C, Hi]``, ``keys`` ``[B,
  Lc, d]`` -> ``[B, C, Lc]`` float32 (module docstring)."""
  B, C = q.shape[:2]
  Lc = keys.shape[1]
  dots = jnp.einsum("bchd,bld->bchl", q, keys.astype(q.dtype),
                    preferred_element_type=jnp.float32)
  scores = jnp.sum(jax.nn.relu(dots) * w.astype(jnp.float32)[..., None], 2)
  nv = (jnp.full((B,), C, jnp.int32) if num_valid is None
        else num_valid.astype(jnp.int32))
  t = cursors.astype(jnp.int32)[:, None] + jnp.arange(C)[None]
  col = jnp.arange(Lc)[None, None]
  seen = (col <= t[..., None]) & (col < (cursors + nv)[:, None, None])
  return jnp.where(seen, scores, MASKED)


# ----------------------------------------------------------------- pallas --


def _dsa_index_kernel(slot_ref, tile_ref, count_ref, cur_ref, bound_ref,
                      q_ref, w_ref, k_ref, o_ref, *, block: int, tp: int,
                      heads: int):
  """One (live tile, key block) grid step: ``q_ref`` ``[1, 1, heads x tp,
  d]`` (rows (head, position)), ``w_ref`` their weights ``[1, 1, heads x
  tp, 1]``, ``k_ref`` ``[1, block, d]`` as the leaf holds it, ``o_ref``
  ``[1, tp, block]``."""
  del count_ref
  i = pl.program_id(0)
  kb = pl.program_id(1)
  b = slot_ref[i]
  cur = cur_ref[b]
  bound = bound_ref[b]
  t_lo = cur + tile_ref[i] * tp
  t_hi = jnp.minimum(bound, t_lo + tp) - 1
  live = kb * block <= t_hi

  @pl.when(live)
  def _score():
    q = q_ref[0, 0]
    precision = None if q.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(
        q, k_ref[0], (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)            # [heads x tp, block]
    s = jnp.maximum(s, 0.0) * w_ref[0, 0]
    # Rows are (head, position): the heads' sum is one of whole [tp,
    # block] tiles (a tile of one position: a sum down the sublanes).
    s = (jnp.sum(s, axis=0, keepdims=True) if tp == 1
         else jnp.sum(s.reshape(heads, tp, block), axis=0))  # [tp, block]
    col = kb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    t = t_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    o_ref[0] = jnp.where((col <= t) & (col < bound), s, MASKED)

  @pl.when(jnp.logical_not(live))
  def _masked():
    o_ref[0] = jnp.full(o_ref.shape[1:], MASKED, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def dsa_index_pallas(q, w, keys, cursors, num_valid=None,
                     interpret: bool = False, block: Optional[int] = None):
  """The index scores; decoding slots take a launch of their own on their
  one position (``slot_attention.split_decodes``), under the one name."""
  split = split_decodes(num_valid, q.shape[1])
  if split is None:
    return _dsa_index_launch(q, w, keys, cursors, num_valid, interpret,
                             block)
  many, one = split
  out = _dsa_index_launch(q, w, keys, cursors, many, interpret, block)
  lone = _dsa_index_launch(q[:, :1], w[:, :1], keys, cursors, one,
                           interpret, block)
  return jnp.concatenate(
      [jnp.where((one > 0)[:, None, None], lone, out[:, :1]), out[:, 1:]],
      axis=1)


def _dsa_index_launch(q, w, keys, cursors, num_valid, interpret: bool,
                      block: Optional[int]):
  """One launch over the live (slot, tile) pairs of ``num_valid``."""
  B, C, Hi, d = q.shape
  Lc = keys.shape[1]
  dtype = keys.dtype
  tp = _tile(C)
  rows = Hi * tp
  if block is None:
    block = _block(Lc, d, dtype, rows, tp)
  nb = pl.cdiv(Lc, block)
  cur = jnp.clip(cursors.astype(jnp.int32), 0, Lc - C)
  nv = (jnp.full((B,), C, jnp.int32) if num_valid is None
        else jnp.clip(num_valid.astype(jnp.int32), 0, C))
  bound = jnp.where(nv > 0, cur + nv, 0)
  slot, tile, count = live_tiles(nv, C, tp)
  # Rows of a tile are (head, position): the sum over the heads is then a
  # sum of whole [tp, block] tiles.
  by_tile = lambda x: x.reshape(B, C // tp, tp, Hi, -1).transpose(
      0, 1, 3, 2, 4).reshape(B, C // tp, rows, x.shape[-1])
  qt = by_tile(q.astype(dtype))
  wt = by_tile(w.astype(jnp.float32)[..., None])

  def key_idx(i, kb, slot, tile, count, cur, bound):
    # A block beyond the tile's last query stays on the one the pipeline
    # holds: no DMA of its own.
    b = slot[i]
    t_hi = jnp.minimum(bound[b], cur[b] + (tile[i] + 1) * tp) - 1
    return b, jnp.minimum(kb, jnp.maximum(t_hi, 0) // block), 0

  tile_spec = lambda width: pl.BlockSpec(
      (1, 1, rows, width),
      lambda i, kb, slot, tile, *_: (slot[i], tile[i], 0, 0))
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
  out = pl.pallas_call(
      functools.partial(_dsa_index_kernel, block=block, tp=tp, heads=Hi),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=5,
          grid=(count[0], nb),
          in_specs=[tile_spec(d), tile_spec(1),
                    pl.BlockSpec((1, block, d), key_idx)],
          out_specs=pl.BlockSpec(
              (1, tp, block),
              lambda i, kb, slot, tile, *_: (slot[i], tile[i], kb))),
      out_shape=jax.ShapeDtypeStruct((B, C, Lc), jnp.float32),
      interpret=interpret,
      name=DSA_INDEX,
      **kwargs,
  )(slot, tile, count, cur, bound, qt, wt, keys)
  return out


# --------------------------------------------------------------- dispatch --


def dsa_index(q, w, keys, cursors, num_valid=None, *,
              impl: Optional[str] = None):
  """Index scores of each slot's chunk against its index leaf (module
  docstring): ``q`` ``[B, C, Hi, d]``, ``w`` ``[B, C, Hi]``, ``keys`` ``[B,
  Lc, d]`` AFTER this step's write -> float32 ``[B, C, Lc]``.  ``impl=None``
  applies :func:`resolve_dsa_index_impl` to the operands at hand (the
  serving engine resolves it once and passes it)."""
  if impl is None:
    impl = resolve_dsa_index_impl(keys.shape, keys.dtype, q.shape[1],
                                  q.shape[2],
                                  sharded=Env.get().mesh_built())
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  if impl == "reference":
    return dsa_index_reference(q, w, keys, cursors, num_valid)
  return dsa_index_pallas(q, w, keys, cursors, num_valid,
                          interpret=impl == "interpret")


def kth_largest(scores, k):
  """The ``k[n]``-th largest of each row of ``scores`` float32 ``[N, L]``
  (``k`` int32 ``[N]``, ``1 <= k <= L``), exactly: float32 ``[N]``.

  A float's bits, with the sign bit flipped (and every other bit too for a
  negative), order as unsigned integers the way the floats order.  The
  threshold's key is built from its top bit down: a bit stays set iff at
  least ``k`` keys are at or above the candidate."""
  bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
  top = jnp.uint32(1 << 31)
  keys = jnp.where(bits >= top, ~bits, bits | top)
  k = k.astype(jnp.int32)[:, None]

  def fix_bit(i, found):
    cand = found | (top >> i.astype(jnp.uint32))
    enough = jnp.sum(keys >= cand, axis=1, keepdims=True,
                     dtype=jnp.int32) >= k
    return jnp.where(enough, cand, found)

  found = jax.lax.fori_loop(
      0, 32, fix_bit, jnp.zeros((scores.shape[0], 1), jnp.uint32))[:, 0]
  back = jnp.where(found >= top, found ^ top, ~found)
  return jax.lax.bitcast_convert_type(back, jnp.float32)
