"""Slot-cache attention over the live rows only — Pallas TPU kernel.

The attend half of ``models.gpt.slot_cache_attend``: every slot's ``C``
new query positions against that slot's own contiguous K/V cache, causal
at the slot's cursor.  One algorithm, two lowerings:

* **reference** — two einsums: ``q`` against ALL ``Lc`` rows of ALL
  slots, masked afterwards, a float32 softmax over the whole ``[B, H, C,
  Lc]`` score tensor.  A dead slot, and every row beyond a live slot's
  cursor, costs what a full one costs.  Correct everywhere, and what the
  kernel is tested against.
* **pallas** — one launch a layer named ``slot_attn``, grid over the
  LIVE slots (``num_valid > 0``; their number is a value, the grid's
  first dimension is dynamic and the program compiles once) and K/V
  blocks of :func:`block_positions` rows.  Each slot's cursor and bound
  (``cursor + num_valid``) are scalar-prefetched: the kernel reads of a
  slot's K and V only the blocks under its bound, and an idle slot costs
  neither a DMA nor a grid step — a step beyond the bound maps to the
  first block of the next live slot (fetched behind the arithmetic of
  the last live block, held until its turn) or to the block the pipeline
  already holds, so it issues no DMA of its own, and its arithmetic is
  skipped.  Scores, the running max, sum and
  accumulator of the online softmax live in VMEM; no ``[B, H, C, Lc]``
  tensor exists in HBM.  It reads the leaf AS IT LIES, in the form its
  rank asks for, the one ``kernels/kv_write.py`` writes through:

  - **rows** (rank 3, ``[slot, position, H_kv * hd]``): K and V blocks
    arrive ``[block, W]``, the scores contract over the lanes of both
    operands (the transposed-right-hand-side product) and the values
    product is plain; edge rows are masked on the sublane axis.  Where
    ``hd`` is a whole number of lane tiles a head is its own lanes; where
    it is a fraction of one (64: GPT-2), a lane tile holds ``128 / hd``
    heads and is treated as one unit — the unit's query rows are stacked
    once per head, each copy zeroed outside its own head's lanes, so one
    ``[n x rows, 128] x [128, block]`` and one ``[n x rows, block] x
    [block, 128]`` product serve the unit's heads, nothing is sliced
    below a lane tile, and the wanted lanes of each copy are selected on
    the way out.  ``q`` goes in and the output comes out as ``[B, C, H x
    hd]``: no transpose around the call (grouped heads alone ride the
    query rows, a small transpose of ``q`` and of the output).
  - **positions** (rank 4, kept position-minor by the TPU, ``[slot, H,
    hd, position]``): one batched product over the heads, K as the leaf
    holds it; the transposes around the call are bitcasts.

Arithmetic: scores accumulate in float32 from the compute-dtype ``q``
and K, the softmax runs in float32, probabilities are cast to the
compute dtype for the V contraction, which accumulates in float32 and is
normalised once at the end.  Equal to the reference to rounding, not bit
for bit (a blocked softmax sums in another order).

What comes out of rows the kernel does not compute: an idle slot's
rows, positions ``>= num_valid`` of a partial chunk and the rows that
pad a short chunk are ZEROS.  Nothing at or beyond a slot's bound
reaches the output: scores there are masked, and V is zeroed there as
well (the last live block's tail may hold a previous occupant's rows,
and the leaf's edge block lanes no row at all; ``0 * NaN = NaN``).

Grouped K/V heads (``H_kv < H``, models/jamba.py) ride the query-row
axis: the ``G = H / H_kv`` query heads of a K/V head are ``G * C`` rows
against that head's one K and V.

Dispatch rule (:func:`resolve_slot_attn_impl`, the twin of
``resolve_kv_write_impl``): the kernel when the backend is a TPU, the
leaf sits whole on one chip and the shapes fit (:func:`slot_attn_fits`);
the reference everywhere else.  It reads the backend and what it is
handed — no configuration field, environment variable or setter;
``interpret`` runs the kernel in Pallas interpreter mode (the CPU parity
tests, by name or by patching :func:`_backend_impl`).  The engine
resolves it once when it builds its step and records it
(``engine.slot_attn_impl``, trace metadata ``serving/slot_attn_impl``).

A layer whose cache is ONE leaf (models/glm_moe.py: the latent ``[B, Lc,
1, 576]`` of absorbed multi-head latent attention) passes ``cached_v =
None`` and ``v_width``: the values are the keys' leading ``v_width``
columns.  The kernel takes them from the K block it already holds in VMEM
(the leading sublanes of the position-minor block), so the leaf is read
from HBM once a block, not once as keys and once as values, and the
output is ``[B, C, H, v_width]``.  ``scale`` replaces ``1 / sqrt(hd)``
where the queries' width is not the head size the softmax is scaled by.

Shapes: ``q`` ``[B, C, H, hd]``; ``cached_k/cached_v`` ``[B, Lc, H_kv x
hd]`` (rows) or ``[B, Lc, H_kv, hd]`` (positions) AFTER this step's
window write; ``cursors``, ``num_valid`` int32 ``[B]``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easyparallellibrary_tpu.env import Env

NEG_INF = -1e30
# The kernel's name in a device trace (see ``flash_attention.FLASH_FWD``).
# The benchmark reads it (PERF.md section 3).
SLOT_ATTN = "slot_attn"

IMPLS = ("pallas", "reference", "interpret")

LANES = 128
# Positions a K/V block may span, widest first.  A block is the unit of
# both the skip and the DMA: wide enough to stream near the memory's
# rate (``kv_write``'s 128-position tiles reach 46% of it), narrow
# enough that a short request does not pay for a long allocation.
_BLOCKS = (2048, 1024, 512, 256, 128)
# One K (or V) block ``[H_kv, hd, block]`` may take this much.
_BLOCK_BYTES = 512 * 1024
# VMEM the kernel may ask for: K and V blocks double-buffered, q and the
# output block double-buffered, the float32 accumulator, max and sum,
# and three score-sized float32 temporaries.  v5e's scoped default is 16
# MiB.
_VMEM_BUDGET = 12 * 1024 * 1024


def _backend_impl() -> str:
  """The lowering this backend takes when the shapes allow it.  The CPU
  parity tests patch it to ``interpret``."""
  return "pallas" if jax.default_backend() == "tpu" else "reference"


def sublane_tile(dtype) -> int:
  """Rows of one sublane tile of ``dtype``: 8 of 32 bits, 16 of 16."""
  return 8 * 4 // jnp.dtype(dtype).itemsize


def _query_rows(chunk: int, group: int, dtype) -> int:
  """Query rows of one K/V head, padded to whole sublane tiles of
  ``dtype`` (a one-token decode is one row)."""
  tile = sublane_tile(dtype)
  return -(-chunk * group // tile) * tile


def _geometry(cache_shape, head_dim: Optional[int]):
  """``(Lc, H_kv, hd, rows)`` of a leaf of either order; a leaf kept in
  rows (rank 3) needs ``head_dim`` to tell its heads apart."""
  if len(cache_shape) == 3:
    _, Lc, W = cache_shape
    return Lc, W // head_dim, head_dim, True
  _, Lc, Hkv, hd = cache_shape
  return Lc, Hkv, hd, False


def _unit(hd: int):
  """Lanes of one unit of the rows form and the heads it holds: a head of
  whole lane tiles is its own unit, smaller heads share a lane tile."""
  width = max(hd, LANES)
  return width, width // hd


def block_positions(cache_shape, dtype, chunk: int, num_heads: int,
                    head_dim: Optional[int] = None) -> int:
  """Positions per K/V block for a leaf of either order: the widest
  of :data:`_BLOCKS` whose K block stays within :data:`_BLOCK_BYTES`,
  does not outgrow the leaf and leaves the kernel within its VMEM
  budget; 0 if none does."""
  Lc, Hkv, hd, rows_form = _geometry(cache_shape, head_dim)
  size = jnp.dtype(dtype).itemsize
  rows = _query_rows(chunk, num_heads // Hkv, dtype)
  W = Hkv * hd
  width, stack = _unit(hd)
  for block in _BLOCKS:
    if block > Lc or W * block * size > _BLOCK_BYTES:
      continue
    vmem = (4 * W * block * size               # K, V, double-buffered
            + 4 * rows * W * size)             # q, out, double-buffered
    if rows_form:
      stacked = (W // width) * stack * rows    # every unit's stacked rows
      vmem += (stacked * width * (size + 4)    # stacked q, acc
               + 2 * stacked * LANES * 4       # max, sum
               + 3 * stacked * block * 4)      # scores, probabilities
    else:
      vmem += (Hkv * rows * (hd + 2 * LANES) * 4    # acc, max, sum
               + 3 * Hkv * rows * block * 4)   # scores, probabilities
    if vmem <= _VMEM_BUDGET:
      return block
  return 0


def slot_attn_fits(cache_shape, dtype, chunk: int, num_heads: int,
                   head_dim: Optional[int] = None) -> bool:
  """Whether the kernel can tile a leaf of ``dtype`` for ``chunk`` query
  positions of ``num_heads`` heads, in the form its rank asks for: a
  32-bit or 16-bit float leaf of at least 128 positions, query heads in
  whole groups, a chunk no wider than 128 (so the causal edge touches
  two blocks at most), a block within the budgets, and

  * rows ``[B, Lc, H_kv x hd]`` (``head_dim`` given): an ``hd`` that is a
    whole number of lane tiles or divides one;
  * positions ``[B, Lc, H_kv, hd]``: an ``hd`` that fills whole sublane
    tiles."""
  Lc, Hkv, hd, rows_form = _geometry(cache_shape, head_dim)
  dtype = jnp.dtype(dtype)
  if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
    return False
  if Lc < LANES or not 1 <= chunk <= LANES:
    return False
  if Hkv < 1 or num_heads % Hkv:
    return False
  if rows_form:
    if cache_shape[2] != Hkv * hd or (hd % LANES and LANES % hd):
      return False
  elif hd % sublane_tile(dtype):
    return False
  return block_positions(cache_shape, dtype, chunk, num_heads, head_dim) > 0


def resolve_slot_attn_impl(cache_shape, dtype, chunk: int, num_heads: int,
                           sharded: bool = False,
                           head_dim: Optional[int] = None) -> str:
  """The dispatch rule: the backend's lowering (``pallas`` on a TPU,
  ``reference`` elsewhere), and ``reference`` whenever the leaf lives on
  a multi-device mesh (``sharded``: the SPMD partitioner cannot split a
  Mosaic call) or the shapes do not fit (:func:`slot_attn_fits`).  A
  leaf kept in rows (rank 3) names its heads' width in ``head_dim``."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not slot_attn_fits(cache_shape, dtype, chunk, num_heads,
                                    head_dim)):
    return "reference"
  return impl


# -------------------------------------------------------------- reference --


def slot_attention_reference(q, cached_k, cached_v, cursors,
                             v_width: Optional[int] = None,
                             scale: Optional[float] = None):
  """Every query against every row of its slot's cache, masked to the
  causal prefix ``j <= cursor + i``: nothing newer, nothing stale."""
  B, C, H, hd = q.shape
  Lc = cached_k.shape[1]
  if cached_k.ndim == 3:
    # Kept in rows: heads apart again, free on an ``hd``-minor array.
    cached_k = cached_k.reshape(B, Lc, -1, hd)
    cached_v = cached_v.reshape(B, Lc, -1, hd)
  Hkv = cached_k.shape[2]
  dtype = q.dtype
  if cached_v is None:
    cached_v = cached_k[..., :v_width]
  scale = (1.0 / jnp.sqrt(hd).astype(dtype) if scale is None
           else jnp.asarray(scale, dtype))
  # Grouped heads: query head h reads K/V head h // (H / H_kv); the
  # group is one more axis of the same two contractions.
  if Hkv != H:
    q = q.reshape(B, C, Hkv, H // Hkv, hd)
  qk, pv = (("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd") if Hkv == H else
            ("bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd"))
  logits = jnp.einsum(qk, q, cached_k) * scale
  pos = cursors[:, None, None, None] + jnp.arange(C)[None, None, :, None]
  valid = jnp.arange(Lc)[None, None, None, :] <= pos
  if Hkv != H:
    valid = valid[:, :, None]
  logits = jnp.where(valid, logits, jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  out = jnp.einsum(pv, probs.astype(dtype), cached_v)
  return out.reshape(B, C, H, cached_v.shape[-1])


# ----------------------------------------------------------------- pallas --


def live_order(alive):
  """``(order, live)`` for a grid over the live slots alone, in slot
  order: ``live`` (int32 ``[1]``, at least 1) is their number, a value
  and not a shape, so a grid whose first dimension it is compiles once;
  ``order[i]`` names the slot of grid row ``i``.  (The i-th live slot is
  the number of slots with at most i live ones up to and including
  themselves: no sort, no scatter.)  ``kernels/kv_write.py`` visits the
  slots a step feeds through the same pair."""
  B = alive.shape[0]
  upto = jnp.cumsum(alive, dtype=jnp.int32)
  order = jnp.minimum(
      jnp.sum(upto[None, :] <= jnp.arange(B)[:, None], axis=1,
              dtype=jnp.int32), B - 1)
  return order, jnp.maximum(upto[-1:], 1)


def _online_softmax_fold(s, m_ref, l_ref, at):
  """Fold one block's masked scores ``s [.., rows, block]`` into the
  running max and sum held at index ``at`` of their scratches (``...``
  in the positions form, a unit in the rows form); returns the block's
  unnormalised probabilities and the factor that rescales what the
  accumulator already holds."""
  m_all = m_ref[at]
  m_prev = m_all[..., :1]
  l_prev = l_ref[at][..., :1]
  m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
  # A row with nothing visible yet (beyond the slot's chunk) keeps
  # m = NEG_INF and sums ones: finite, and zeroed when emitted.
  p = jnp.exp(s - m_new)
  corr = jnp.exp(m_prev - m_new)
  m_ref[at] = jnp.broadcast_to(m_new, m_all.shape)
  l_ref[at] = jnp.broadcast_to(
      l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), m_all.shape)
  return p, corr


def _slot_attn_rows_kernel(order_ref, live_ref, cur_ref, bound_ref, pos_ref,
                           q_ref, k_ref, v_ref, o_ref, qs_ref, m_ref, l_ref,
                           acc_ref, *, block: int, num_blocks: int,
                           scale: float, hd: int):
  """One (slot, K/V block) grid step of the rows form: K and V blocks
  ``[block, W]`` as the leaf holds them, ``q`` and the output ``[rows,
  W]``.

  The lanes are walked a UNIT at a time (:func:`_unit`): a head of whole
  lane tiles, or one lane tile of ``n`` smaller heads.  On a slot's first
  step the unit's query rows are stacked ``n`` times into ``qs_ref``,
  copy ``s`` zeroed outside head ``s``'s lanes, so that the scores
  contraction over the unit's lanes (of both operands: K is not
  transposed) gives each copy its own head's scores, and the values
  product gives copy ``s`` its head's output in that head's own lanes:
  the copies are merged by a lane select when the slot is emitted.  The
  units' scores are stacked on the sublanes, ``[units x n x rows,
  block]``, so the softmax runs once over all of them and the units'
  products stand side by side with nothing between them for the MXUs to
  wait on; the three softmax scratches are stacked the same way.
  ``pos_ref`` holds each stacked row's position in the chunk."""
  del live_ref
  rows, W = q_ref.shape[1:]
  width, stack = _unit(hd)
  units = W // width
  R = stack * rows                     # stacked query rows of one unit
  b = order_ref[pl.program_id(0)]
  kb = pl.program_id(1)
  cur = cur_ref[b]
  bound = bound_ref[b]
  lanes = lambda u: slice(u * width, (u + 1) * width)
  if stack > 1:
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // hd

  @pl.when(kb == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for u in range(units):
      q = q_ref[0, :, lanes(u)]
      for s in range(stack):
        qs_ref[u * R + s * rows:u * R + (s + 1) * rows] = (
            q if stack == 1 else jnp.where(head == s, q, jnp.zeros_like(q)))

  def fold(edge: bool):
    # 16-bit operands multiply exactly on the MXU whatever precision the
    # caller's context names (and Mosaic refuses a float32 contraction
    # of them); float32 operands follow the context, as the einsums do.
    precision = (None if q_ref.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    s = jnp.concatenate([
        jax.lax.dot_general(
            qs_ref[u * R:(u + 1) * R], k_ref[0, :, lanes(u)],
            (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        for u in range(units)], axis=0) * scale      # [units x R, block]
    vs = [v_ref[0, :, lanes(u)] for u in range(units)]
    if edge:
      # The block holds rows at or beyond the cursor: query row i sees
      # key j iff j <= cursor + i, and nothing at or beyond the bound
      # (its own chunk's invalid tail, a previous occupant's rows, the
      # leaf's edge rows) may reach the sums, through K or through V.
      col = kb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
      s = jnp.where((col <= cur + pos_ref[...]) & (col < bound), s, NEG_INF)
      stale = kb * block + jax.lax.broadcasted_iota(
          jnp.int32, (block, width), 0) >= bound
      vs = [jnp.where(stale, jnp.zeros_like(v), v) for v in vs]
    p, corr = _online_softmax_fold(s, m_ref, l_ref, ...)
    p = p.astype(vs[0].dtype)
    acc_ref[...] = acc_ref[...] * corr + jnp.concatenate([
        jax.lax.dot_general(
            p[u * R:(u + 1) * R], vs[u], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        for u in range(units)], axis=0)              # [units x R, width]

  live = kb * block < bound
  behind = (kb + 1) * block <= cur     # every row of it under the cursor

  @pl.when(live & behind)
  def _interior():
    fold(edge=False)

  @pl.when(live & jnp.logical_not(behind))
  def _edge():
    fold(edge=True)

  @pl.when(kb == num_blocks - 1)
  def _emit():
    l_col = jnp.maximum(l_ref[...][:, :1], 1e-30)
    out = jnp.where(pos_ref[...] < bound - cur, acc_ref[...] / l_col, 0.0)
    for u in range(units):
      merged = out[u * R:u * R + rows]
      for s in range(1, stack):
        merged = jnp.where(
            head == s, out[u * R + s * rows:u * R + (s + 1) * rows], merged)
      o_ref[0, :, lanes(u)] = merged.astype(o_ref.dtype)


def _slot_attn_kernel(order_ref, live_ref, cur_ref, bound_ref, pos_ref,
                      q_ref, k_ref, *refs, block: int, num_blocks: int,
                      scale: float, v_width: Optional[int]):
  """One (slot, K/V block) grid step of the positions form: score the
  block against every query row of every head, fold it into the online
  softmax carried in VMEM scratch, emit on the slot's last step.

  Values keep ``[heads, rows, .]``: one batched matmul over the heads
  for the scores (``[rows, hd] x [hd, block]``, K as the leaf holds it)
  and one for the V contraction (over the block's positions, the lanes
  of both operands).  ``order_ref`` names the slot of this grid row
  (live slots only are visited), ``live_ref`` is the index maps' alone,
  ``pos_ref`` holds each query row's position in the chunk (rows beyond
  the chunk carry one no slot reaches).  ``refs``: the V block (absent
  for a one-leaf layer, whose values are the K block's leading
  ``v_width`` sublanes), the output block and the three scratches."""
  v_ref = refs[0] if v_width is None else None
  o_ref, m_ref, l_ref, acc_ref = refs[-4:]
  b = order_ref[pl.program_id(0)]
  kb = pl.program_id(1)
  cur = cur_ref[b]
  bound = bound_ref[b]

  @pl.when(kb == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  def fold(edge: bool):
    q, k = q_ref[0], k_ref[0]
    v = v_ref[0] if v_width is None else k[:, :v_width]
    # 16-bit operands multiply exactly on the MXU whatever precision the
    # caller's context names (and Mosaic refuses a float32 contraction
    # of them); float32 operands follow the context, as the einsums do.
    precision = None if q.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((0,), (0,))), precision=precision,
        preferred_element_type=jnp.float32) * scale    # [Hkv, rows, block]
    if edge:
      # The block holds rows at or beyond the cursor: query row i sees
      # key j iff j <= cursor + i, and nothing at or beyond the bound
      # (its own chunk's invalid tail, a previous occupant's rows, the
      # leaf's edge lanes) may reach the sums, through K or through V.
      col = kb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
      s = jnp.where((col <= cur + pos_ref[...][None])
                    & (col < bound), s, NEG_INF)
      vcol = kb * block + jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
      v = jnp.where(vcol < bound, v, jnp.zeros_like(v))
    p, corr = _online_softmax_fold(s, m_ref, l_ref, ...)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
        precision=precision,
        preferred_element_type=jnp.float32)            # [Hkv, rows, hd]

  live = kb * block < bound
  behind = (kb + 1) * block <= cur     # every row of it under the cursor

  @pl.when(live & behind)
  def _interior():
    fold(edge=False)

  @pl.when(live & jnp.logical_not(behind))
  def _edge():
    fold(edge=True)

  @pl.when(kb == num_blocks - 1)
  def _emit():
    l_col = jnp.maximum(l_ref[...][:, :, :1], 1e-30)
    real = pos_ref[...][None] < bound - cur          # [1, rows, 1]
    o_ref[0] = jnp.where(real, acc_ref[...] / l_col, 0.0).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block",
                                             "v_width", "scale"))
def slot_attention_pallas(q, cached_k, cached_v, cursors, num_valid=None,
                          interpret: bool = False,
                          block: Optional[int] = None,
                          v_width: Optional[int] = None,
                          scale: Optional[float] = None):
  """The live-rows attend; ``interpret`` runs the kernel in Pallas
  interpreter mode (any backend), ``block`` overrides
  :func:`block_positions` (tests and measurement).  Jitted, so that the
  layers of one step share one trace and one Mosaic lowering of the
  kernel, as ``kv_write_pallas`` does; XLA inlines the calls."""
  B, C, H, hd = q.shape
  Lc, Hkv, _, rows_form = _geometry(cached_k.shape, hd)
  G = H // Hkv
  dtype = cached_k.dtype
  # One leaf: no V operand, the values' width is ``v_width``.
  vd = hd if cached_v is not None else v_width
  if block is None:
    block = block_positions(cached_k.shape, dtype, C, H, hd)
  nb = pl.cdiv(Lc, block)
  rows = _query_rows(C, G, dtype)

  # The write clamps its window into the leaf (kv_write.py); the read
  # follows it, so the two stay one contract outside it too.
  cur = jnp.clip(cursors.astype(jnp.int32), 0, Lc - C)
  nv = (jnp.full((B,), C, jnp.int32) if num_valid is None
        else jnp.clip(num_valid.astype(jnp.int32), 0, C))
  alive = nv > 0
  bound = jnp.where(alive, cur + nv, 0)
  # The grid visits the live slots alone, in slot order (:func:`live_order`).
  # An idle slot costs no grid step and no DMA; its output block is never
  # written and is zeroed below.
  order, live = live_order(alive)
  # Each query row's position in its chunk: rows are (group, position),
  # padding rows carry a position no slot reaches.
  pos = jnp.arange(rows, dtype=jnp.int32)
  pos = jnp.where(pos < G * C, pos % C, C)[:, None]

  def kv_idx(i, kb, order, live, cur, bound):
    # A step beyond its slot's bound points at the first block of the
    # next live slot, which so streams in behind the arithmetic of this
    # slot's last block and is held until its turn; the last live slot's
    # stay on the block the pipeline holds.  Either way such steps issue
    # no DMA of their own.
    b = order[i]
    ahead = order[jnp.minimum(i + 1, B - 1)]
    reads = kb * block < bound[b]
    more = i + 1 < live[0]
    held = jnp.maximum(bound[b] - 1, 0) // block
    return (jnp.where(reads, b, jnp.where(more, ahead, b)),
            jnp.where(reads, kb, jnp.where(more, 0, held)))

  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
  scale = 1.0 / math.sqrt(hd) if scale is None else float(scale)
  # Query rows of one K/V head are (group, position): a transpose of
  # ``q`` and of the output for grouped heads alone.
  qr = q.astype(dtype).reshape(B, C, Hkv, G, hd)

  if rows_form:
    W = Hkv * hd
    width, stack = _unit(hd)
    stacked = (W // width) * stack * rows      # every unit's stacked rows
    qr = qr.transpose(0, 3, 1, 2, 4).reshape(B, G * C, W)
    if rows != G * C:
      qr = jnp.pad(qr, ((0, 0), (0, rows - G * C), (0, 0)))
    row_spec = pl.BlockSpec((1, rows, W),
                            lambda i, kb, order, *_: (order[i], 0, 0))
    def rows_idx(*a):
      b, kb = kv_idx(*a)
      return b, kb, 0

    kv_spec = pl.BlockSpec((1, block, W), rows_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(live[0], nb),
        in_specs=[pl.BlockSpec((stacked, 1), lambda i, kb, *_: (0, 0)),
                  row_spec, kv_spec, kv_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((stacked, width), dtype),          # stacked q
            pltpu.VMEM((stacked, LANES), jnp.float32),    # running max
            pltpu.VMEM((stacked, LANES), jnp.float32),    # running sum
            pltpu.VMEM((stacked, width), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_slot_attn_rows_kernel, block=block,
                          num_blocks=nb, scale=scale, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, W), dtype),
        interpret=interpret,
        name=SLOT_ATTN,
        **kwargs,
    )(order, live, cur, bound, jnp.tile(pos, (stacked // rows, 1)), qr,
      cached_k, cached_v)
    out = jnp.where(alive[:, None, None], out, 0)
    out = out[:, :G * C].reshape(B, G, C, Hkv, hd)
    return out.transpose(0, 2, 3, 1, 4).reshape(B, C, H, hd)

  # Position-minor views of the leaves: bitcasts on the TPU, and the
  # inverse of the ones kv_write returned through.
  to_minor = lambda x: jnp.transpose(x, (0, 2, 3, 1))
  qr = qr.transpose(0, 2, 3, 1, 4).reshape(B, Hkv, G * C, hd)
  if rows != G * C:
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - G * C), (0, 0)))

  row_spec = lambda width: pl.BlockSpec(
      (1, Hkv, rows, width), lambda i, kb, order, *_: (order[i], 0, 0, 0))
  def minor_idx(*a):
    b, kb = kv_idx(*a)
    return b, 0, 0, kb

  kv_spec = pl.BlockSpec((1, Hkv, hd, block), minor_idx)
  leaves = [cached_k] if cached_v is None else [cached_k, cached_v]
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=4,
      grid=(live[0], nb),
      in_specs=[pl.BlockSpec((rows, 1), lambda i, kb, *_: (0, 0)),
                row_spec(hd)] + [kv_spec] * len(leaves),
      out_specs=row_spec(vd),
      scratch_shapes=[
          pltpu.VMEM((Hkv, rows, LANES), jnp.float32),   # running max
          pltpu.VMEM((Hkv, rows, LANES), jnp.float32),   # running sum
          pltpu.VMEM((Hkv, rows, vd), jnp.float32),      # accumulator
      ],
  )
  out = pl.pallas_call(
      functools.partial(
          _slot_attn_kernel, block=block, num_blocks=nb, scale=scale,
          v_width=None if cached_v is not None else v_width),
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, vd), dtype),
      interpret=interpret,
      name=SLOT_ATTN,
      **kwargs,
  )(order, live, cur, bound, pos, qr, *map(to_minor, leaves))
  out = jnp.where(alive[:, None, None, None], out, 0)
  out = out[:, :, :G * C].reshape(B, Hkv, G, C, vd)
  return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, vd)


# --------------------------------------------------------------- dispatch --


def slot_attention(q, cached_k, cached_v, cursors, num_valid=None,
                   impl: Optional[str] = None,
                   v_width: Optional[int] = None,
                   scale: Optional[float] = None):
  """Attend each slot's chunk over its own cache (module docstring);
  returns ``out [B, C, H, hd]`` (``[B, C, H, v_width]`` for a one-leaf
  layer: ``cached_v=None``, the values the keys' leading ``v_width``
  columns).  ``impl=None`` applies the dispatch rule
  to the shapes at hand, and takes the leaf as spread over chips
  whenever a multi-device mesh has been built (the legacy ``generate()``
  decode); the serving engine resolves the impl from its own mesh and
  passes it."""
  if impl is None:
    cluster = Env.get().cluster
    mesh = cluster.built_mesh if cluster is not None else None
    impl = resolve_slot_attn_impl(
        cached_k.shape, cached_k.dtype, q.shape[1], q.shape[2],
        sharded=mesh is not None and mesh.size > 1, head_dim=q.shape[3])
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  if (cached_v is None) != (v_width is not None):
    raise ValueError("a one-leaf attend passes cached_v=None AND v_width; "
                     "a K/V pair passes neither")
  if impl == "reference":
    return slot_attention_reference(q, cached_k, cached_v, cursors,
                                    v_width=v_width, scale=scale)
  return slot_attention_pallas(q, cached_k, cached_v, cursors, num_valid,
                               interpret=impl == "interpret",
                               v_width=v_width, scale=scale)
