"""Slot-cache attention over the live rows only — Pallas TPU kernel.

The attend half of ``models.slot_core.slot_cache_attend``: every slot's ``C``
new query positions against that slot's own contiguous K/V cache, causal
at the slot's cursor.  One algorithm, two lowerings:

* **reference** — two einsums: ``q`` against ALL ``Lc`` rows of ALL
  slots, masked afterwards, a float32 softmax over the whole ``[B, H, C,
  Lc]`` score tensor.  A dead slot, and every row beyond a live slot's
  cursor, costs what a full one costs.  Correct everywhere, and what the
  kernel is tested against.
* **pallas** — one launch a layer named ``slot_attn`` on ONE flat grid
  over the step's live PIECES in slot order (:func:`live_pieces`; their
  number is a value, the grid's one dimension is dynamic and the program
  compiles once).  A slot whose bound (``cursor + num_valid``) is ``n``
  rows is ``n // block`` whole blocks of :func:`block_positions` rows and
  then ONE tail piece of the rest rounded up to a granule
  (:func:`walk_geometry`: a sublane tile of rows, a lane tile of
  positions); an idle slot has no piece, and no grid step exists for a
  block beyond a bound.  The leaves stay in HBM: the kernel copies each
  piece into one of :data:`_DEPTH` VMEM buffers itself, a whole block as
  one DMA and a tail as the DMAs of static sizes its length decomposes
  into (``block / 2, block / 4, .., granule``; :func:`walk_geometry` says
  how a tail that reaches the leaf's end closes), and starts the copies of
  the pieces ahead (the next live slot's first, after a slot's last)
  before it folds the one it holds.  A tail is folded as an edge block
  over the whole buffer: the rows beyond it are whatever the buffer held,
  masked as everything at or beyond the bound is.  Scores, the running
  max, sum and accumulator of the online softmax live in VMEM; no ``[B, H,
  C, Lc]`` tensor exists in HBM.  It reads the leaf AS IT LIES, in the form its
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
well (a bound's granule may hold a previous occupant's rows beyond it,
the buffer beyond a tail whatever piece it held before, and the padding
of a leaf's last lane tile no row at all; ``0 * NaN = NaN``).

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
resolves it once when it builds its step and records it (trace metadata
``serving/slot_attn_impl``, ``engine.lowerings["slot_attn_impl"]``).

A layer whose cache is ONE leaf (models/glm_moe.py: the latent ``[B, Lc,
1, 576]`` of absorbed multi-head latent attention) passes ``cached_v =
None`` and ``v_width``: the values are the keys' leading ``v_width``
columns.  The kernel takes them from the K block it already holds in VMEM
(the leading sublanes of the position-minor block), so the leaf is read
from HBM once a block, not once as keys and once as values, and the
output is ``[B, C, H, v_width]``.  ``scale`` replaces ``1 / sqrt(hd)``
where the queries' width is not the head size the softmax is scaled by.

That grid's query block is a slot's WHOLE chunk of every head: ``C x H``
rows a live slot, whatever the slot feeds.  At 20 heads x chunk 8
(GLM-4.7-Flash) that is 160 rows; at 64 heads x chunk 32 (GigaChat3.5) it
is 2,048, of which a decoding slot needs 64.  So a plain one-leaf attend
whose chunk is more than one tile of positions runs on the TILE grid
below (:func:`plain_tile_form`: from the leaf's shape, the heads, the
chunk and the flat batch's width; no key, no model's name): the same
rows under the same mask and the same arithmetic, computed for the live
(slot, tile of 8 positions) pairs and, in a launch of their own, for the
one position of each decoding slot, queries read from and results
written to the step's token-flat batch where it lies.  Both launches
keep the name ``slot_attn``: it is one attend, and the benchmark sums
the calls of that name.

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
# Positions a K/V block may span, widest first.
_BLOCKS = (2048, 1024, 512, 256, 128)
# ``slot_attn``'s block: what one copy moves and one fold covers.  The
# walk visits no block beyond a bound and copies of a tail only its
# granules, so a wide block costs no byte; it costs the arithmetic of a
# tail folded at the block's width, and spares grid steps and rescalings
# of the accumulator.  On the chip (PERF.md section 6, PR 44) 1024 rows
# were the fastest or within 2% of it on every cell's leaf that 1.5 MiB
# of K let take them, 2048 slower wherever bounds mostly end under it
# (the hybrid's, LFM2's), and GPT-2 medium's 2 KiB rows as fast at 512.
_BLOCK_ROWS = 1024
_BLOCK_BYTES = 1536 * 1024
# VMEM the kernel may ask for: K and V pieces in ``_DEPTH`` buffers, q and
# the output block double-buffered, the float32 accumulator, max and sum,
# and three score-sized float32 temporaries; and the limit it hands
# Mosaic (v5e's scoped default is 16 MiB of 128).
_VMEM_BUDGET = 24 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024
# Buffers a leaf's pieces rotate through: the walk keeps the copies of the
# next ``_DEPTH - 1`` pieces in flight behind the fold of the one it holds.
_DEPTH = 3


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
  of :data:`_BLOCKS` within :data:`_BLOCK_ROWS` whose K block stays
  within :data:`_BLOCK_BYTES`, does not outgrow the leaf and leaves the
  kernel within its VMEM budget; 0 if none does."""
  Lc, Hkv, hd, rows_form = _geometry(cache_shape, head_dim)
  size = jnp.dtype(dtype).itemsize
  rows = _query_rows(chunk, num_heads // Hkv, dtype)
  W = Hkv * hd
  width, stack = _unit(hd)
  for block in _BLOCKS:
    if (block > min(Lc, _BLOCK_ROWS)
        or W * block * size > _BLOCK_BYTES):
      continue
    vmem = (2 * _DEPTH * W * block * size      # K, V pieces
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
    whole number of lane tiles or divides one, ``Lc`` a multiple of 8;
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
    # Rows in whole 8-row tiles, as the array keeps them: what a tail
    # that reaches the leaf's end closes with (:func:`walk_geometry`).
    if cache_shape[2] != Hkv * hd or (hd % LANES and LANES % hd) or Lc % 8:
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


def walk_geometry(cache_shape, dtype):
  """``(granule, length)`` of the walk over a leaf.  ``granule``: the
  positions a piece is a whole number of, a sublane tile of a leaf kept in
  rows (16 of bfloat16, 8 of float32), a lane tile of one kept in
  positions; a slot's tail is its bound rounded up to it.  ``length``: the
  positions of a slot the walk may copy.  A leaf kept in rows holds its
  ``Lc`` rows and no more (a tail that reaches the leaf's end closes with
  the few rows of its last granule); one kept in positions is padded to
  whole lane tiles by the array's own tiling, which a copy may read (as
  Pallas' pipeline reads a partial edge block) and the masks discard."""
  Lc = cache_shape[1]
  if len(cache_shape) == 3:
    return sublane_tile(dtype), Lc
  return LANES, -(-Lc // LANES) * LANES


def live_pieces(bound, length: int, block: int, granule: int):
  """``(slot, start, rows, count)`` for ONE flat grid over the step's live
  pieces in slot order: slot ``b`` with bound ``n > 0`` is the whole
  blocks ``[k x block, (k + 1) x block)`` under ``n`` rounded up to
  ``granule`` (held to ``length``, the positions a slot has:
  :func:`walk_geometry`), then one tail of the rest (``rows`` under
  ``block``: a multiple of ``granule``, or one that ends at ``length``);
  an idle slot is nothing.  ``count`` (int32
  ``[1]``, at least 1: with every slot idle one piece of no rows) is a
  value; the lists hold ``B x cdiv(length, block)`` entries, those from
  ``count`` on of no rows.  The twin of :func:`live_order` one level down,
  and :func:`live_tiles`' on the other axis.  It reads the bounds and the
  leaf's geometry alone, so the layers of a step share one."""
  B = bound.shape[0]
  nb = -(-length // block)
  covered = jnp.minimum(-(-bound.astype(jnp.int32) // granule) * granule,
                        length)
  whole = covered // block
  tail = covered - whole * block
  per = whole + (tail > 0)
  ends = jnp.cumsum(per, dtype=jnp.int32)
  item = jnp.arange(B * nb, dtype=jnp.int32)
  slot = jnp.minimum(
      jnp.sum(ends[None, :] <= item[:, None], axis=1, dtype=jnp.int32), B - 1)
  k = item - jnp.take(ends - per, slot)
  w = jnp.take(whole, slot)
  rows = jnp.where(k < w, block, jnp.where(k == w, jnp.take(tail, slot), 0))
  rows = jnp.where(item < ends[-1], rows, 0)
  return (slot, jnp.clip(k, 0, nb - 1) * block, rows,
          jnp.maximum(ends[-1:], 1))


def _piece_copies(rows, block: int, granule: int, length: int):
  """``(size, wanted, offset)`` of the copies a piece of ``rows`` rows is
  made of, sizes static: the block, then its halves down to the granule,
  each wanted where ``rows`` has its bit, at the offset the larger ones
  before it fill; last, where ``length`` is no whole number of granules,
  the rows of its last one (a tail that ends at ``length``)."""
  size = block
  while size >= granule:
    yield size, (rows & size) != 0, rows - jax.lax.rem(rows, 2 * size)
    size //= 2
  if length % granule:
    yield (length % granule, (rows & (granule - 1)) != 0,
           rows - (rows & (granule - 1)))


def _walk(slot_ref, start_ref, rows_ref, count_ref, leaves, bufs, sem, *,
          block: int, granule: int, length: int, minor: bool):
  """This grid step's piece ``(slot, start, rows, buffer)``, in VMEM when
  it returns: starts the copies of the piece ``_DEPTH - 1`` steps ahead
  (on the first step also of those before it), then waits for this
  step's.  ``leaves`` the K (and V) leaves in HBM, ``bufs`` their ``[_DEPTH,
  ..block..]`` buffers, ``sem`` DMA semaphores ``[leaves, _DEPTH]``;
  ``minor``: the position is the leaves' last axis, else their second."""
  j = pl.program_id(0)

  def copies(p, act):
    b, start, n = slot_ref[p], start_ref[p], rows_ref[p]
    at = jax.lax.rem(p, _DEPTH)
    for size, wanted, off in _piece_copies(n, block, granule, length):
      @pl.when(wanted)
      def _():
        aligned = min(size, granule)
        src = pl.ds(pl.multiple_of(start + off, aligned), size)
        dst = pl.ds(pl.multiple_of(off, aligned), size)
        for i, (leaf, buf) in enumerate(zip(leaves, bufs)):
          act(pltpu.make_async_copy(
              leaf.at[b, :, :, src] if minor else leaf.at[b, src],
              buf.at[at, :, :, dst] if minor else buf.at[at, dst],
              sem.at[i, at]))

  start_copy = lambda dma: dma.start()
  for d in range(_DEPTH - 1):
    @pl.when((j == 0) & (d < count_ref[0]))
    def _():
      copies(d, start_copy)
  ahead = j + _DEPTH - 1

  @pl.when(ahead < count_ref[0])
  def _():
    copies(ahead, start_copy)
  copies(j, lambda dma: dma.wait())
  return slot_ref[j], start_ref[j], rows_ref[j], jax.lax.rem(j, _DEPTH)


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


def _slot_attn_rows_kernel(slot_ref, start_ref, rows_ref, count_ref, cur_ref,
                           bound_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                           k_buf, v_buf, sem, qs_ref, m_ref, l_ref, acc_ref,
                           *, block: int, granule: int, length: int,
                           scale: float, hd: int):
  """One piece of the rows form: K and V pieces ``[block, W]`` as the
  leaf holds them (a tail fills the buffer's leading rows), ``q`` and the
  output ``[rows, W]``.

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
  rows, W = q_ref.shape[1:]
  width, stack = _unit(hd)
  units = W // width
  R = stack * rows                     # stacked query rows of one unit
  b, first, held, at = _walk(
      slot_ref, start_ref, rows_ref, count_ref, (k_hbm, v_hbm),
      (k_buf, v_buf), sem, block=block, granule=granule, length=length,
      minor=False)
  cur = cur_ref[b]
  bound = bound_ref[b]
  lanes = lambda u: slice(u * width, (u + 1) * width)
  if stack > 1:
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // hd

  @pl.when(first == 0)
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
            qs_ref[u * R:(u + 1) * R], k_buf[at, :, lanes(u)],
            (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        for u in range(units)], axis=0) * scale      # [units x R, block]
    vs = [v_buf[at, :, lanes(u)] for u in range(units)]
    if edge:
      # The piece holds rows at or beyond the cursor: query row i sees
      # key j iff j <= cursor + i, and nothing at or beyond the bound
      # (its own chunk's invalid tail, a previous occupant's rows, what
      # the buffer held beyond a tail) may reach the sums, through K or
      # through V.
      col = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
      s = jnp.where((col <= cur + pos_ref[...]) & (col < bound), s, NEG_INF)
      stale = first + jax.lax.broadcasted_iota(
          jnp.int32, (block, width), 0) >= bound
      vs = [jnp.where(stale, jnp.zeros_like(v), v) for v in vs]
    p, corr = _online_softmax_fold(s, m_ref, l_ref, ...)
    p = p.astype(vs[0].dtype)
    acc_ref[...] = acc_ref[...] * corr + jnp.concatenate([
        jax.lax.dot_general(
            p[u * R:(u + 1) * R], vs[u], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        for u in range(units)], axis=0)              # [units x R, width]

  # A whole block with every row under the cursor needs no mask.
  behind = (held == block) & (first + block <= cur)

  @pl.when(behind)
  def _interior():
    fold(edge=False)

  @pl.when(jnp.logical_not(behind) & (held > 0))
  def _edge():
    fold(edge=True)

  @pl.when(first + held >= bound)
  def _emit():
    l_col = jnp.maximum(l_ref[...][:, :1], 1e-30)
    out = jnp.where(pos_ref[...] < bound - cur, acc_ref[...] / l_col, 0.0)
    for u in range(units):
      merged = out[u * R:u * R + rows]
      for s in range(1, stack):
        merged = jnp.where(
            head == s, out[u * R + s * rows:u * R + (s + 1) * rows], merged)
      o_ref[0, :, lanes(u)] = merged.astype(o_ref.dtype)


def _slot_attn_kernel(slot_ref, start_ref, rows_ref, count_ref, cur_ref,
                      bound_ref, pos_ref, q_ref, *refs, block: int,
                      granule: int, length: int, scale: float,
                      v_width: Optional[int]):
  """One piece of the positions form: score it against every query row of
  every head, fold it into the online softmax carried in VMEM scratch,
  emit on the slot's last piece.

  Values keep ``[heads, rows, .]``: one batched matmul over the heads
  for the scores (``[rows, hd] x [hd, block]``, K as the leaf holds it)
  and one for the V contraction (over the piece's positions, the lanes
  of both operands).  ``pos_ref`` holds each query row's position in the
  chunk (rows beyond the chunk carry one no slot reaches).  ``refs``: the
  K leaf and the V leaf in HBM (V absent for a one-leaf layer, whose
  values are the K piece's leading ``v_width`` sublanes), the output
  block, a buffer a leaf, the semaphores and the three scratches."""
  leaves = 1 if v_width is not None else 2
  hbm, o_ref, bufs = refs[:leaves], refs[leaves], refs[leaves + 1:-4]
  sem, m_ref, l_ref, acc_ref = refs[-4:]
  b, first, held, at = _walk(
      slot_ref, start_ref, rows_ref, count_ref, hbm, bufs, sem, block=block,
      granule=granule, length=length, minor=True)
  cur = cur_ref[b]
  bound = bound_ref[b]

  @pl.when(first == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  def fold(edge: bool):
    q, k = q_ref[0], bufs[0][at]
    v = bufs[1][at] if v_width is None else k[:, :v_width]
    # 16-bit operands multiply exactly on the MXU whatever precision the
    # caller's context names (and Mosaic refuses a float32 contraction
    # of them); float32 operands follow the context, as the einsums do.
    precision = None if q.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((0,), (0,))), precision=precision,
        preferred_element_type=jnp.float32) * scale    # [Hkv, rows, block]
    if edge:
      # The piece holds rows at or beyond the cursor: query row i sees
      # key j iff j <= cursor + i, and nothing at or beyond the bound
      # (its own chunk's invalid tail, a previous occupant's rows, what
      # the buffer held beyond a tail) may reach the sums, through K or
      # through V.
      col = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
      s = jnp.where((col <= cur + pos_ref[...][None])
                    & (col < bound), s, NEG_INF)
      vcol = first + jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
      v = jnp.where(vcol < bound, v, jnp.zeros_like(v))
    p, corr = _online_softmax_fold(s, m_ref, l_ref, ...)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
        precision=precision,
        preferred_element_type=jnp.float32)            # [Hkv, rows, hd]

  # A whole block with every row under the cursor needs no mask.
  behind = (held == block) & (first + block <= cur)

  @pl.when(behind)
  def _interior():
    fold(edge=False)

  @pl.when(jnp.logical_not(behind) & (held > 0))
  def _edge():
    fold(edge=True)

  @pl.when(first + held >= bound)
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
  kernel, as ``kv_write_pallas`` does; XLA inlines the calls and keeps
  one piece list for the layers of a step."""
  B, C, H, hd = q.shape
  Lc, Hkv, _, rows_form = _geometry(cached_k.shape, hd)
  G = H // Hkv
  dtype = cached_k.dtype
  # One leaf: no V operand, the values' width is ``v_width``.
  vd = hd if cached_v is not None else v_width
  if block is None:
    block = block_positions(cached_k.shape, dtype, C, H, hd)
  granule, length = walk_geometry(cached_k.shape, dtype)
  rows = _query_rows(C, G, dtype)

  # The write clamps its window into the leaf (kv_write.py); the read
  # follows it, so the two stay one contract outside it too.
  cur = jnp.clip(cursors.astype(jnp.int32), 0, Lc - C)
  nv = (jnp.full((B,), C, jnp.int32) if num_valid is None
        else jnp.clip(num_valid.astype(jnp.int32), 0, C))
  alive = nv > 0
  bound = jnp.where(alive, cur + nv, 0)
  # The grid visits the live pieces alone, in slot order
  # (:func:`live_pieces`).  An idle slot costs no grid step and no DMA;
  # its output block is never written and is zeroed below.
  with jax.named_scope("slot_attn_pieces"):
    pieces = live_pieces(bound, length, block, granule)
  # Each query row's position in its chunk: rows are (group, position),
  # padding rows carry a position no slot reaches.
  pos = jnp.arange(rows, dtype=jnp.int32)
  pos = jnp.where(pos < G * C, pos % C, C)[:, None]

  leaves = [cached_k] if cached_v is None else [cached_k, cached_v]
  if interpret and length > Lc:
    # The padding a leaf kept in positions has on the chip
    # (:func:`walk_geometry`) the interpreter lacks: it would shift a copy
    # that reaches into it back inside the array.  So it gets one, of NaN.
    leaves = [jnp.pad(x, [(0, 0), (0, length - Lc), (0, 0), (0, 0)],
                      constant_values=jnp.nan) for x in leaves]
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)
  scale = 1.0 / math.sqrt(hd) if scale is None else float(scale)
  # Query rows of one K/V head are (group, position): a transpose of
  # ``q`` and of the output for grouped heads alone.
  qr = q.astype(dtype).reshape(B, C, Hkv, G, hd)
  in_hbm = [pl.BlockSpec(memory_space=pl.ANY)] * len(leaves)
  sems = pltpu.SemaphoreType.DMA((len(leaves), _DEPTH))

  if rows_form:
    W = Hkv * hd
    width, stack = _unit(hd)
    stacked = (W // width) * stack * rows      # every unit's stacked rows
    qr = qr.transpose(0, 3, 1, 2, 4).reshape(B, G * C, W)
    if rows != G * C:
      qr = jnp.pad(qr, ((0, 0), (0, rows - G * C), (0, 0)))
    row_spec = pl.BlockSpec((1, rows, W),
                            lambda j, slot, *_: (slot[j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(pieces[3][0],),
        in_specs=[pl.BlockSpec((stacked, 1), lambda j, *_: (0, 0)),
                  row_spec] + in_hbm,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((_DEPTH, block, W), dtype),        # K pieces
            pltpu.VMEM((_DEPTH, block, W), dtype),        # V pieces
            sems,
            pltpu.VMEM((stacked, width), dtype),          # stacked q
            pltpu.VMEM((stacked, LANES), jnp.float32),    # running max
            pltpu.VMEM((stacked, LANES), jnp.float32),    # running sum
            pltpu.VMEM((stacked, width), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_slot_attn_rows_kernel, block=block,
                          granule=granule, length=length, scale=scale,
                          hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, W), dtype),
        interpret=interpret,
        name=SLOT_ATTN,
        **kwargs,
    )(*pieces, cur, bound, jnp.tile(pos, (stacked // rows, 1)), qr, *leaves)
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
      (1, Hkv, rows, width), lambda j, slot, *_: (slot[j], 0, 0, 0))
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=6,
      grid=(pieces[3][0],),
      in_specs=[pl.BlockSpec((rows, 1), lambda j, *_: (0, 0)),
                row_spec(hd)] + in_hbm,
      out_specs=row_spec(vd),
      scratch_shapes=[pltpu.VMEM((_DEPTH, Hkv, hd, block), dtype)
                      for _ in leaves] + [                # K (and V) pieces
          sems,
          pltpu.VMEM((Hkv, rows, LANES), jnp.float32),   # running max
          pltpu.VMEM((Hkv, rows, LANES), jnp.float32),   # running sum
          pltpu.VMEM((Hkv, rows, vd), jnp.float32),      # accumulator
      ],
  )
  out = pl.pallas_call(
      functools.partial(
          _slot_attn_kernel, block=block, granule=granule, length=length,
          scale=scale, v_width=None if cached_v is not None else v_width),
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, vd), dtype),
      interpret=interpret,
      name=SLOT_ATTN,
      **kwargs,
  )(*pieces, cur, bound, pos, qr, *map(to_minor, leaves))
  out = jnp.where(alive[:, None, None, None], out, 0)
  out = out[:, :, :G * C].reshape(B, Hkv, G, C, vd)
  return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, vd)


# ------------------------------------- selected rows, or rows behind a window --
#
# Three more forms of the attend, each under a kernel name of its own so
# that a device trace tells their time apart; two of a ONE-LEAF cache
# (models/dots3_note.py), one of a K/V PAIR (models/smallthinker.py); and
# the PLAIN one-leaf attend itself (every row ``s <= t`` under the bound,
# the selected form less its selection and its two score operands), under
# the first grid's name, where :func:`plain_tile_form` holds:
#
# * ``slot_attn_sel``: a query sees row ``s <= t`` iff ``s`` is in its
#   SELECTION.  The selection reaches the kernel as the index scores
#   ``[B, C, Lc]`` float32 (kernels/dsa_index.py) and one threshold a
#   query: ``s`` is selected iff ``score(t, s) >= threshold(t)``, which is
#   exact top-k wherever no two of a query's scores are equal.  Every block
#   under the tile's bound is fetched and scored and the unselected rows
#   are masked: a gathered or block-skipping form is not built.
# * ``slot_attn_win``: the leaf is a RING of ``R`` rows (``R`` a multiple
#   of 128, at least ``window + chunk - 1``): position ``p`` lives at row
#   ``p mod R``, so row ``j`` holds the newest position ``p < bound`` with
#   ``p mod R = j``, and a query at ``t`` sees it iff ``t - window < p <=
#   t``.  Blocks wholly outside every window of the tile are neither
#   fetched nor computed.
# * ``slot_attn_kvwin``: the same ring and the same lower bound over a K/V
#   PAIR kept in rows, ``[B, R, H_kv x hd]`` twice (``hd`` whole lane
#   tiles), under grouped heads: the ``G = H / H_kv`` query heads of a K/V
#   head are stacked on the sublanes, ``G x positions`` rows against that
#   head's lanes of the K block (the transposed-right-hand-side product of
#   the rows form above) and of the V block.  ``G`` need not be a power of
#   two (7: 28 heads on 4).  Queries are read from, and the output written
#   as, ``[B, C, H x hd]``, a head its own lane tiles: rows as the
#   projection leaves them, gathered into slot order, nothing transposed
#   (in place from the flat batch they would be an offset in rows into a
#   rank-2 array, which Mosaic cannot prove whole sublane tiles).  A tile
#   is the whole chunk while that keeps it within :data:`_TILE_ROWS` rows
#   (the ring is read once a prefilling slot); a decoding slot's one
#   position stacks its heads ``G`` to a sublane tile a K/V head.
#
# All run on a grid over the step's live (slot, position TILE) pairs
# (:func:`live_tiles`; their number is a value, the program compiles once)
# and the leaf's blocks: a tile is ``tile_positions`` chunk positions of
# every head, rows ordered (position, head) as the projection leaves them,
# so ``q`` and the output need no transpose.  The leaf is re-read a live
# tile; the online softmax, the masks and the arithmetic are
# :func:`_slot_attn_kernel`'s.  A slot that feeds ONE position (a decode)
# would pay a whole tile for it, so a chunk of tiles is served by two
# launches under the one name (:func:`split_decodes`): the slots that feed
# more than one position on tiles of positions, those that feed one on
# tiles of that one position.  Both write into ONE output buffer that
# starts as zeros and is aliased through them, each under every slot's true
# bound: no select, slice or concatenation of ``[slots, chunk]``-sized
# tensors follows a launch, and the tile a launch with nothing to do still
# visits holds what the other launch writes there.
#
# Which of a step's mixers read and write its token-flat batch ``[T, ..]``
# themselves (models/slot_core.py:SlotRows; ``starts[b]`` the row of slot
# ``b``'s first live position), and which are handed ``[slots, chunk, ..]``
# arrays gathered from it (``SlotRows.to_slots``) and gathered back
# (``to_flat``):
#
# * THE ONE-LEAF TILE FORMS, ``slot_attn_sel``, ``slot_attn_win`` and the
#   plain leaf's ``slot_attn`` where it takes this grid, work
#   on the flat batch where it lies (:func:`tile_attn_out` says when: the
#   kernel resolved, the batch narrower than ``slots x chunk``; a plain
#   leaf besides only where :func:`plain_tile_form` holds).  ``q`` is
#   ``[T, H, W]`` and a tile's rows are the block at row ``starts[b] + tile
#   x tp``, an offset in elements, held to ``T - tp`` (a tile that starts
#   nearer the batch's end is read from there and worked that many rows
#   further down, :func:`_tile_shift`: the flat batch is never padded).  The
#   output is ``[T, H, v_width]``, rows as ``q``'s, left in HBM: a tile's
#   result is cast into one VMEM tile and its LIVE positions alone are
#   copied out by the kernel, a count of 1 to ``tp`` as DMAs of 8, 4, 2 and
#   1 positions (:func:`_tile_out_copies`), waited for when the next tile
#   is about to emit.  So a slot's partial last tile writes nothing of the
#   next slot's rows, the two launches write disjoint rows (or the same
#   values) in either order, and the rows beyond the step's live positions
#   keep the zeros the buffer starts as.  Called with ``[B, C, H, W]``
#   (every position of every slot: ``starts[b] = b x C``) the same code
#   gives ``[B, C, H, v_width]``, dead positions zeros.  The layer that
#   calls them (models/blocks.py:LatentAttention) then holds no ``[slots,
#   chunk, ..]`` array of queries or of attended rows; its latent and index
#   rows still go ``to_slots`` for ``kv_write`` and ``dsa_index``, and the
#   index scores ``to_flat`` for the thresholds.
# * THE PAIR FORM, ``slot_attn_kvwin``, takes ``q`` gathered ``to_slots`` and
#   writes ``[B, C, H x hd]`` blocks a tile, gathered back ``to_flat``: its
#   rows are ``H x hd`` lanes wide, rank 2, and Mosaic cannot take an
#   offset in rows into them.
# * ``slot_attn`` ON THE FIRST GRID (above: every K/V pair, and a plain
#   leaf whose chunk is one tile of positions or whose heads the tile
#   kernel declines), ``kv_write``, ``dsa_index``,
#   ``ssm_scan`` and the convolution's window: ``[slots, chunk, ..]`` in,
#   ``[slots, chunk, ..]`` out, two gathers a mixer.  The one-leaf forms'
#   output is the form they would take (ROADMAP S2(c)): the array left in
#   HBM, the live rows copied by the kernel in static sizes.

SLOT_ATTN_SEL = "slot_attn_sel"
SLOT_ATTN_WIN = "slot_attn_win"
SLOT_ATTN_KVWIN = "slot_attn_kvwin"

# Query rows of one tile the kernels aim at (positions x heads).
_TILE_ROWS = 1024
# VMEM the two kernels may ask for, and the limit they hand Mosaic (v5e's
# scoped default of 16 MiB leaves no room for the score temporaries).
_TILE_VMEM_BUDGET = 24 * 1024 * 1024
_TILE_VMEM_LIMIT = 48 * 1024 * 1024


def tile_positions(chunk: int, num_heads: int) -> int:
  """Chunk positions of one query tile: 8 (a float32 sublane tile: the
  selection's scores are read a tile of positions at a time) while the
  tile stays within :data:`_TILE_ROWS` rows and divides the chunk, else
  the whole chunk."""
  if chunk % 8 == 0 and 8 * num_heads <= _TILE_ROWS:
    return 8
  return chunk


def pair_tile_positions(chunk: int, num_heads: int) -> int:
  """Chunk positions of one query tile of the pair form: the whole chunk
  while the tile stays within :data:`_TILE_ROWS` rows (a prefilling slot
  then reads its ring once), else 16 (a sublane tile of either dtype)
  where that divides the chunk."""
  if chunk * num_heads <= _TILE_ROWS or chunk % 16:
    return chunk
  return 16


def _pair_rows(tp: int, group: int, dtype) -> int:
  """Stacked query rows of one K/V head in the pair form: its ``group``
  heads' ``tp`` positions, or, for one position, its heads up to a whole
  sublane tile."""
  tile = sublane_tile(dtype)
  return group * tp if tp > 1 else -(-group // tile) * tile


def live_tiles(num_valid, chunk: int, tile: int):
  """``(slot, tile index, count)`` for a grid over the live (slot,
  position tile) pairs in slot order: slot ``b`` has ``ceil(num_valid[b]
  / tile)`` of them.  ``count`` (int32 ``[1]``, at least 1) is a value.
  The twin of :func:`live_order`, one level down."""
  B = num_valid.shape[0]
  per = (num_valid.astype(jnp.int32) + tile - 1) // tile
  ends = jnp.cumsum(per, dtype=jnp.int32)
  item = jnp.arange(B * (chunk // tile), dtype=jnp.int32)
  slot = jnp.minimum(
      jnp.sum(ends[None, :] <= item[:, None], axis=1, dtype=jnp.int32), B - 1)
  first = jnp.take(ends - per, slot)
  return (slot, jnp.clip(item - first, 0, chunk // tile - 1),
          jnp.maximum(ends[-1:], 1))


def decodes_apart(chunk: int) -> bool:
  """Whether a chunk is wide enough to be tiled (a multiple of 8 above 8),
  so that the slots that feed one position take a launch of their own."""
  return chunk % 8 == 0 and chunk > 8


def split_decodes(num_valid, chunk: int):
  """``(num_valid of the slots that feed more than one position, 0 or 1
  for those that feed exactly one)`` where :func:`decodes_apart` holds,
  ``None`` where one launch serves all."""
  if not decodes_apart(chunk) or num_valid is None:
    return None
  nv = num_valid.astype(jnp.int32)
  one = nv == 1
  return jnp.where(one, 0, nv), one.astype(jnp.int32)


def _tile_block(L: int, W: int, dtype, rows: int, vd: int,
                ring: bool, pair: bool = False) -> int:
  """Leaf positions per block for a query tile of ``rows`` rows: the
  widest of :data:`_BLOCKS` (a ring: the widest 128-multiple that divides
  it, the ring itself first) that keeps the kernel within
  :data:`_TILE_VMEM_BUDGET`; 0 if none does.  A ``pair`` holds a K and a V
  block, and its query rows are one head wide (``vd``), stacked once
  more in scratch."""
  size = jnp.dtype(dtype).itemsize
  if ring:
    blocks = [b for b in range(L, 0, -LANES) if L % b == 0 and b <= 1024]
  else:
    blocks = [b for b in _BLOCKS if b <= L]
  leaves, q_width = (2, 2 * vd) if pair else (1, W)
  for block in blocks:
    vmem = (2 * leaves * W * block * size        # the leaf's block(s)
            + 2 * rows * (q_width + vd) * size   # q, out
            + rows * (vd + 2 * LANES) * 4        # acc, max, sum
            + 3 * rows * block * 4)              # scores, probabilities
    if vmem <= _TILE_VMEM_BUDGET:
      return block
  return 0


def tile_attn_fits(cache_shape, dtype, chunk: int, num_heads: int,
                   v_width: int, ring: bool = False) -> bool:
  """Whether the selected (``ring`` false) or the windowed form can tile
  a one-leaf cache ``[B, L, 1, W]`` of ``dtype``: a 16- or 32-bit float,
  a width of whole sublane tiles, a chunk of whole tiles of positions,
  a leaf of at least 128 rows (a ring: whole 128-row tiles), heads in
  whole sublane tiles, and a block within the budget.  A rank-3 shape is
  one leaf of a K/V PAIR kept in rows, ``[B, L, H_kv x v_width]``
  (``v_width`` the head size): the pair form, built behind a window only,
  wants heads of whole lane tiles, query heads in whole groups, and a
  tile of positions in whole sublane tiles (or the one position of a
  chunk of 1)."""
  dtype = jnp.dtype(dtype)
  if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
    return False
  tile = sublane_tile(dtype)
  if len(cache_shape) == 3:
    _, L, W = cache_shape
    hd = v_width
    if not ring or hd % LANES or W % hd or num_heads % (W // hd):
      return False
    if L < LANES or L % LANES or not 1 <= chunk <= LANES:
      return False
    tp = pair_tile_positions(chunk, num_heads)
    if tp > 1 and tp % tile:
      return False
    rows = (W // hd) * _pair_rows(tp, num_heads // (W // hd), dtype)
    return _tile_block(L, W, dtype, rows, hd, True, pair=True) > 0
  if len(cache_shape) != 4 or cache_shape[2] != 1:
    return False
  _, L, _, W = cache_shape
  if W % tile or v_width % tile or num_heads % tile:
    return False
  if L < LANES or (ring and L % LANES) or not 1 <= chunk <= LANES:
    return False
  tp = tile_positions(chunk, num_heads)
  return _tile_block(L, W, dtype, tp * num_heads, v_width, ring) > 0


def tile_attn_out(impl: Optional[str], narrower: bool) -> str:
  """Where the one-leaf forms read their queries and write their result in
  a step whose attend was lowered to ``impl``, on a flat batch that is
  ``narrower`` than ``slots x chunk`` or is not: ``"flat"``, the step's
  token-flat batch itself, where the kernel runs and the batch's rows are
  not the chunk positions in order already; ``"slots"``, arrays in
  ``[slots, chunk]`` order gathered from and back into it (at full width by
  a reshape).  What the mixer asks (models/blocks.py:LatentAttention) and
  what the engine's record says (serving/kv_cache.py:tile_attn_out)."""
  return "flat" if impl in ("pallas", "interpret") and narrower else "slots"


def plain_tile_form(impl: Optional[str], narrower: bool, cache_shape, dtype,
                    chunk: int, num_heads: int, v_width: int) -> bool:
  """Whether a PLAIN one-leaf attend (no window, no selection: every row
  ``s <= t`` under the slot's bound) runs on the tile grid over the flat
  batch, as the selected and the windowed forms do, and not on the first
  grid over ``[slots, chunk]`` operands: where those forms work on the flat
  batch at all (:func:`tile_attn_out`), the tile kernel can tile the leaf
  (:func:`tile_attn_fits`) AND a slot's chunk is more than one tile of
  positions, since only then does a tile skip anything (a chunk of 32 x 64
  heads is 2,048 query rows a slot on the first grid, of which a decoding
  slot needs 64).  Shapes and the resolved lowering, nothing else; what the
  mixer asks (models/blocks.py:LatentAttention) and what the engine's
  record follows (serving/kv_cache.py:tile_attn_out)."""
  return (tile_attn_out(impl, narrower) == "flat"
          and tile_positions(chunk, num_heads) < chunk
          and tile_attn_fits(cache_shape, dtype, chunk, num_heads, v_width))


def resolve_tile_attn_impl(cache_shape, dtype, chunk: int, num_heads: int,
                           v_width: int, ring: bool = False,
                           sharded: bool = False) -> str:
  """The dispatch rule of the two forms, as
  :func:`resolve_slot_attn_impl`."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not tile_attn_fits(cache_shape, dtype, chunk, num_heads,
                                    v_width, ring)):
    return "reference"
  return impl


def ring_positions(bound, length: int):
  """The position each row of a ring of ``length`` rows holds when the
  newest position written is ``bound - 1``: the largest ``p < bound``
  with ``p mod length = row`` (negative: the row was never written).
  ``bound`` int32 ``[...]`` -> ``[..., length]``."""
  newest = bound[..., None] - 1
  row = jnp.arange(length, dtype=jnp.int32)
  return newest - jnp.mod(newest - row, length)


def slot_attention_selected_reference(q, latent, scores, threshold, cursors,
                                      v_width: int, scale: float):
  """Every query against every row of its slot's leaf, masked to the
  causal prefix AND the query's selection (``scores >= threshold``)."""
  B, C, H, W = q.shape
  Lc = latent.shape[1]
  dtype = q.dtype
  keys = latent[:, :, 0]
  logits = jnp.einsum("bqhd,bkd->bhqk", q, keys) * jnp.asarray(scale, dtype)
  pos = cursors[:, None, None] + jnp.arange(C)[None, :, None]
  seen = (jnp.arange(Lc)[None, None, :] <= pos) & (
      scores >= threshold[..., None])
  logits = jnp.where(seen[:, None], logits, jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  return jnp.einsum("bhqk,bkd->bqhd", probs.astype(dtype),
                    keys[..., :v_width])


def slot_attention_window_reference(q, ring, cursors, num_valid, window: int,
                                    v_width: int, scale: float):
  """Every query against every row of its slot's ring, masked to the
  positions ``t - window < p <= t`` the rows hold."""
  B, C, H, W = q.shape
  R = ring.shape[1]
  dtype = q.dtype
  nv = (jnp.full((B,), C, jnp.int32) if num_valid is None
        else num_valid.astype(jnp.int32))
  keys = ring[:, :, 0]
  held = ring_positions(cursors.astype(jnp.int32) + nv, R)[:, None, :]
  t = cursors[:, None, None] + jnp.arange(C)[None, :, None]
  seen = (held >= 0) & (held <= t) & (held > t - window)
  logits = jnp.einsum("bqhd,bkd->bhqk", q, keys) * jnp.asarray(scale, dtype)
  logits = jnp.where(seen[:, None], logits, jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  # A row no query of the slot sees may hold anything (a previous
  # occupant's rows, a dead position's): keep it out of the product.
  live = jnp.any(seen, axis=1)[..., None]
  values = jnp.where(live, keys[..., :v_width], jnp.zeros((), dtype))
  return jnp.einsum("bhqk,bkd->bqhd", probs.astype(dtype), values)


def slot_attention_kv_window_reference(q, ring_k, ring_v, cursors, num_valid,
                                       window: int, scale: float):
  """Every query against every row of its slot's K/V rings (either order:
  ``[B, R, H_kv x hd]`` or ``[B, R, H_kv, hd]``), grouped heads, masked to
  the positions ``t - window < p <= t`` the rows hold."""
  B, C, H, hd = q.shape
  R = ring_k.shape[1]
  dtype = q.dtype
  nv = (jnp.full((B,), C, jnp.int32) if num_valid is None
        else num_valid.astype(jnp.int32))
  keys = ring_k.reshape(B, R, -1, hd)
  values = ring_v.reshape(B, R, -1, hd)
  Hkv = keys.shape[2]
  held = ring_positions(cursors.astype(jnp.int32) + nv, R)[:, None, :]
  t = cursors[:, None, None] + jnp.arange(C)[None, :, None]
  seen = (held >= 0) & (held <= t) & (held > t - window)
  logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, C, Hkv, H // Hkv, hd),
                      keys) * jnp.asarray(scale, dtype)
  logits = jnp.where(seen[:, None, None], logits,
                     jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  # A row no query of the slot sees may hold anything: keep it out of the
  # product (``slot_attention_window_reference``).
  live = jnp.any(seen, axis=1)[..., None, None]
  values = jnp.where(live, values, jnp.zeros((), dtype))
  out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(dtype), values)
  return out.reshape(B, C, H, hd)


def _tile_shift(start, tp: int, flat_rows: int):
  """Rows a tile that starts at flat row ``start`` lies further down in
  the block it is read as: blocks start at ``flat_rows - tp`` at the
  latest."""
  return jnp.maximum(start - (flat_rows - tp), 0)


def _tile_out_copies(slot_ref, tile_ref, cur_ref, bound_ref, starts_ref,
                     o_buf, o_hbm, sem, i, act, *, tp: int, flat_rows: int):
  """``act`` on each copy that takes the live positions of the ``i``-th
  tile of the grid from ``o_buf`` ``[tp, heads, v_width]`` to their rows of
  the flat output ``o_hbm``: a count of 1 to ``tp`` positions as the copies
  of static sizes its bits name (.., 4, 2, 1 positions of every head, a
  semaphore each), the walk's :func:`_piece_copies` one level up.  A tile
  of no live position (the one a launch with nothing to do visits at an
  idle slot) is no copy."""
  b = slot_ref[i]
  first = tile_ref[i] * tp
  start = starts_ref[b] + first
  shift = _tile_shift(start, tp, flat_rows)
  n = jnp.clip(bound_ref[b] - cur_ref[b] - first, 0, tp - shift)
  for k in range(tp.bit_length()):
    size = 1 << k
    off = n - jax.lax.rem(n, 2 * size)

    @pl.when((n & size) != 0)
    def _():
      act(pltpu.make_async_copy(
          o_buf.at[pl.ds(shift + off, size)],
          o_hbm.at[pl.ds(start + off, size)], sem.at[k]))


def _tile_attn_kernel(slot_ref, tile_ref, count_ref, cur_ref, bound_ref,
                      starts_ref, into_ref, pos_ref, q_ref, k_ref, *refs,
                      block: int, num_blocks: int, scale: float,
                      v_width: int, tp: int, heads: int,
                      window: Optional[int], ring: int, pair=None,
                      flat_rows: Optional[int] = None, selected: bool = True):
  """One (live tile, leaf block) grid step of the plain (``window`` None,
  not ``selected``: every row ``s <= t`` under the bound), the selected
  (``window`` None) or a windowed form.  ``q_ref`` ``[tp, heads, W]``, the
  tile's rows of the flat batch, taken as ``tp x heads`` rows (position,
  head);
  ``k_ref`` ``[1, 1, W, block]``, position-minor; ``pos_ref`` each query
  row's position in its tile; ``into_ref`` the output as it was handed in
  (aliased, untouched).  ``refs``: the selected form's score block ``[1,
  tp, block]`` and thresholds ``[1, tp, 1]``, then the output, the three
  scratches and the output's VMEM tile and its DMA semaphores.

  The one-leaf forms' output is the flat batch ``[flat_rows, heads,
  v_width]`` itself, left in HBM: a tile's LIVE positions are copied out of
  VMEM to the rows they are read from, in the static sizes their number
  decomposes into (:func:`_tile_out_copies`), so a slot's partial last
  tile writes nothing of the next slot's rows.  A tile that starts within
  ``tp`` rows of the batch's end is read from ``flat_rows - tp`` on (a
  block beyond the array is no block): its rows lie ``shift`` rows further
  down in ``q_ref``, and the tile is worked as if it began ``shift``
  positions earlier, its first ``shift`` rows (another tile's queries) dead.

  The PAIR form (``pair = (H_kv, G, hd)``, behind a window): ``q_ref``
  and the output block ``[1, positions, heads x hd]``,
  a head its own lanes; ``k_ref`` and ``refs[0]`` the K and V blocks ``[1,
  block, H_kv x hd]`` as the leaves hold them; a fourth scratch holds the
  query rows stacked a K/V head, (head of the group, position), filled on
  the tile's first step: whole ``[tp, hd]`` pieces, or, for one position,
  a row a head in float32 (a 16-bit row alone is half a sublane word),
  the group padded to a sublane tile."""
  del into_ref
  i = pl.program_id(0)
  kb = pl.program_id(1)
  b = slot_ref[i]
  first = tile_ref[i] * tp                  # the tile's first chunk position
  shift = 0
  if pair is not None:
    *refs, qs_ref = refs
    Hkv, G, hd = pair
    rows_g = qs_ref.shape[0] // Hkv      # stacked rows of one K/V head
    lanes = lambda i: slice(i * hd, (i + 1) * hd)
    of_head = lambda g: slice(g * rows_g, (g + 1) * rows_g)
  else:
    *refs, o_buf, o_sem = refs
    shift = _tile_shift(starts_ref[b] + first, tp, flat_rows)
    first = first - shift
    out_copies = functools.partial(
        _tile_out_copies, slot_ref, tile_ref, cur_ref, bound_ref, starts_ref,
        o_buf, refs[-4], o_sem, tp=tp, flat_rows=flat_rows)
  o_ref, m_ref, l_ref, acc_ref = refs[-4:]
  cur = cur_ref[b]
  bound = bound_ref[b]
  # Positions of the tile's first and last live query.
  t_lo = cur + first
  t_hi = jnp.minimum(bound, t_lo + tp) - 1

  @pl.when(kb == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    if pair is not None:
      if tp == 1:
        qs_ref[...] = jnp.zeros_like(qs_ref)
      for h in range(heads):
        at = (h // G) * rows_g + (h % G) * tp
        qs_ref[at:at + tp] = q_ref[0, :, lanes(h)].astype(qs_ref.dtype)[:tp]

  col0 = kb * block
  if window is None:
    live = col0 <= t_hi
  else:
    # Row j of the ring holds base + j up to the newest row, base - R + j
    # beyond it; the block is live if one of its rows holds a position in
    # [t_lo - window + 1, t_hi].
    newest = jnp.maximum(bound - 1, 0)
    base = (newest // ring) * ring
    r0 = newest - base
    lo = jnp.maximum(t_lo - window + 1, 0)
    last = col0 + block - 1
    live = (bound > 0) & (
        ((col0 <= r0) & (base + jnp.minimum(last, r0) >= lo))
        | ((last > r0) & (base - ring + last >= lo)))

  @pl.when(live)
  def _fold():
    precision = (None if k_ref.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    if pair is None:
      q, k = q_ref[...].reshape(tp * heads, -1), k_ref[0, 0]
      s = jax.lax.dot_general(
          q, k, (((1,), (0,)), ((), ())), precision=precision,
          preferred_element_type=jnp.float32) * scale     # [rows, block]
      vcol = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    else:
      # A K/V head's stacked rows against its own lanes of the K block,
      # contracted over the lanes of both (K is not transposed).
      k = k_ref[0]                                         # [block, W]
      s = jnp.concatenate([
          jax.lax.dot_general(
              qs_ref[of_head(g)].astype(k.dtype), k[:, lanes(g)],
              (((1,), (1,)), ((), ())), precision=precision,
              preferred_element_type=jnp.float32)
          for g in range(Hkv)], axis=0) * scale            # [rows, block]
      vcol = col0 + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    t = t_lo + pos_ref[...]                                # [rows, 1]
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window is None:
      if selected:
        sc_ref, thr_ref = refs[0], refs[1]
        picked = jnp.where(sc_ref[0] >= thr_ref[0], 0.0, NEG_INF)  # [tp, block]
        if tp > 1:
          # position p's scores to the row its query lies in, p + shift
          picked = pltpu.roll(picked, shift, 0)
        s = s + jnp.concatenate(
            [jnp.broadcast_to(picked[p:p + 1], (heads, block))
             for p in range(tp)], axis=0)
      s = jnp.where((col <= t) & (col < bound), s, NEG_INF)
      dead = vcol >= bound
    else:
      held = jnp.where(col <= r0, base + col, base - ring + col)
      s = jnp.where((held >= 0) & (held <= t) & (held > t - window)
                    & (t < bound), s, NEG_INF)
      vheld = jnp.where(vcol <= r0, base + vcol, base - ring + vcol)
      dead = vheld < lo
    # Nothing of a row no query sees may reach the sums through V either
    # (``0 * NaN = NaN``).
    v = k[:v_width] if pair is None else refs[0][0]
    v = jnp.where(dead, jnp.zeros_like(v), v)
    p, corr = _online_softmax_fold(s, m_ref, l_ref, ...)
    p = p.astype(v.dtype)
    if pair is None:
      pv = jax.lax.dot_general(
          p, v, (((1,), (1,)), ((), ())), precision=precision,
          preferred_element_type=jnp.float32)              # [rows, vd]
    else:
      pv = jnp.concatenate([
          jax.lax.dot_general(
              p[of_head(g)], v[:, lanes(g)], (((1,), (0,)), ((), ())),
              precision=precision, preferred_element_type=jnp.float32)
          for g in range(Hkv)], axis=0)                    # [rows, hd]
    acc_ref[...] = acc_ref[...] * corr + pv

  @pl.when(kb == num_blocks - 1)
  def _emit():
    l_col = jnp.maximum(l_ref[...][:, :1], 1e-30)
    real = t_lo + pos_ref[...] < bound
    out = jnp.where(real, acc_ref[...] / l_col, 0.0)
    if pair is None:
      # The tile before this one has had this tile's folds to land in.
      @pl.when(i > 0)
      def _():
        out_copies(i - 1, lambda dma: dma.wait())
      o_buf[...] = out.astype(o_buf.dtype).reshape(o_buf.shape)
      out_copies(i, lambda dma: dma.start())

      @pl.when(i == count_ref[0] - 1)
      def _():
        out_copies(i, lambda dma: dma.wait())
      return
    # A head's rows to its own lanes of the output block; one position
    # fills the block's first row and leaves the others zeros, which is
    # what a slot that feeds one position holds there.
    first = jax.lax.broadcasted_iota(
        jnp.int32, (o_ref.shape[1], hd), 0) == 0
    for h in range(heads):
      at = (h // G) * rows_g + (h % G) * tp
      piece = out[at:at + tp]
      if tp == 1 and o_ref.shape[1] > 1:
        piece = jnp.where(first, jnp.broadcast_to(piece, first.shape), 0.0)
      o_ref[0, :, lanes(h)] = piece.astype(o_ref.dtype)


def _tile_attention(q, leaf, cursors, num_valid, scores, threshold,
                    window: Optional[int], interpret: bool,
                    block: Optional[int], v_width: int, scale: float,
                    starts=None, chunk: Optional[int] = None, values=None):
  """The forms' shared call: ``window`` None is the selected form
  (``scores``, ``threshold`` given) or, without them, the plain one (every
  row ``s <= t``, under the first grid's name); else the leaf is a ring;
  ``values`` (the V ring beside ``leaf``, the K ring, both kept in rows) is
  the pair form.  ``q`` is ``[B, C, H, W]``, or (the one-leaf forms), with ``starts``
  and ``chunk``, the step's token-flat batch ``[T, H, W]`` in which slot
  ``b``'s live positions are the rows from ``starts[b]`` on
  (models/slot_core.py:SlotRows): a tile's query rows are read where they
  lie and its result is written to the same rows of ``[T, H, v_width]``,
  which is then what comes back; no ``[slots, chunk]``-ordered copy of
  either exists.  Decoding slots take a launch of their own on their one
  position (:func:`split_decodes`)."""
  B = cursors.shape[0]
  H, W = q.shape[-2:]
  pair = values is not None
  flat = starts is not None
  if pair:
    # In [slots, chunk] order, a row as the projection leaves it, H x hd
    # lanes: an offset in rows into a rank-2 flat batch is one Mosaic
    # cannot prove whole sublane tiles.
    chunk = q.shape[1]
    q = q.reshape(B, chunk, H * W)
    starts = jnp.zeros((B,), jnp.int32)
  elif not flat:
    # Every position of every slot is a flat batch too, slot ``b``'s rows
    # from ``b x chunk`` on.
    chunk = q.shape[1]
    q = q.reshape(B * chunk, H, W)
    starts = jnp.arange(B, dtype=jnp.int32) * chunk
  elif q.shape[0] < tile_positions(chunk, H):
    raise ValueError(
        f"a flat batch of {q.shape[0]} rows is less than one tile of "
        f"{tile_positions(chunk, H)} positions")
  L = leaf.shape[1]
  nv = (jnp.full((B,), chunk, jnp.int32) if num_valid is None
        else jnp.clip(num_valid.astype(jnp.int32), 0, chunk))
  if window is not None:
    cur = jnp.maximum(cursors.astype(jnp.int32), 0)
  else:
    cur = jnp.clip(cursors.astype(jnp.int32), 0, L - chunk)
  bound = jnp.where(nv > 0, cur + nv, 0)
  launch = functools.partial(
      _tile_launch, q.astype(leaf.dtype), starts.astype(jnp.int32), leaf,
      cur, bound, window=window, interpret=interpret, block=block,
      v_width=v_width, scale=scale, values=values)
  # Every launch writes what it computes into ONE buffer that starts as
  # zeros (aliased in and out): what no tile covers stays zeros, and the
  # decoding slots' launch lands beside the other's with no merge.  Both
  # work under every slot's TRUE bound, so the one tile a launch with
  # nothing to do still visits (a grid has at least one step) writes what
  # the other launch writes there.
  out = jnp.zeros((B, chunk, H * v_width) if pair
                  else (q.shape[0], H, v_width), leaf.dtype)
  split = split_decodes(num_valid, chunk)
  if split is None:
    out = launch(out, chunk, nv, scores, threshold)
  elif pair:
    # The one-position launch writes a block of several positions (its
    # result and zeros: a row alone is no block of a 16-bit array), so it
    # goes FIRST: where it had nothing to do, the block it still visits
    # is one the other launch then writes whole, or an idle slot's.
    many, one = split
    out = launch(out, 1, one, None, None)
    out = launch(out, chunk, many, None, None)
  else:
    # The one-leaf forms write live positions alone: in either order.
    many, one = split
    first = lambda x: None if x is None else x[:, :1]
    out = launch(out, chunk, many, scores, threshold)
    out = launch(out, 1, one, first(scores), first(threshold))
  return out if flat else out.reshape(B, chunk, H, v_width)


def _tile_launch(q, starts, leaf, cur, bound, into, C: int, feeds, scores,
                 threshold, *, window: Optional[int], interpret: bool,
                 block: Optional[int], v_width: int, scale: float,
                 values=None):
  """One launch over the (slot, tile of ``C``'s positions) pairs that
  cover the first ``feeds[b]`` positions of each slot (int32 ``[B]``, at
  most ``C``); ``q`` ``[T, H, W]``, slot ``b``'s position ``i`` at row
  ``starts[b] + i``, ``cur`` and ``bound`` each slot's cursor and true
  bound.  ``into`` ``[T, H, v_width]`` is the output, handed in and left in
  HBM: the launch copies each tile's positions under the bound to the rows
  their queries lie in and leaves every other row as it was.  The pair form
  (``values``): ``q`` and ``into`` ``[B, chunk, heads x hd]``, ``leaf`` and
  ``values`` ``[B, R, H_kv x hd]``, the output a block a tile (its
  positions at or beyond the bound zeros)."""
  L = leaf.shape[1]
  dtype = leaf.dtype
  ring = window is not None
  pair = None
  if values is None:
    T, H, W = q.shape
    tp = tile_positions(C, H)
    rows = tp * H
    pos = (jnp.arange(rows, dtype=jnp.int32) // H)[:, None]
  else:
    T, H, W, hd = None, q.shape[2] // v_width, leaf.shape[2], v_width
    pair = (W // hd, H // (W // hd), hd)
    tp = pair_tile_positions(C, H)
    rows = pair[0] * _pair_rows(tp, pair[1], dtype)
    # Stacked rows are (K/V head, head of its group, position).
    pos = (jnp.arange(rows, dtype=jnp.int32) % tp)[:, None]
  if block is None:
    block = _tile_block(L, W, dtype, rows, v_width, ring, pair is not None)
  nb = pl.cdiv(L, block)
  slot, tile, count = live_tiles(feeds, C, tp)

  def leaf_idx(i, kb, slot, tile, count, cur, bound, starts):
    # A step beyond the tile's last live block (a dead one: the kernel
    # decides, this only follows) stays on a block the pipeline holds, so
    # it issues no DMA of its own.
    b = slot[i]
    if ring:
      # A ring not yet gone round holds nothing beyond its newest row.
      held = jnp.maximum(bound[b] - 1, 0) % L // block
      return b, 0, 0, jnp.where(
          bound[b] > L, kb, jnp.where(bound[b] > 0, jnp.minimum(kb, held),
                                      held))
    t_hi = jnp.minimum(bound[b], cur[b] + (tile[i] + 1) * tp) - 1
    return b, 0, 0, jnp.minimum(kb, jnp.maximum(t_hi, 0) // block)

  in_specs = [pl.BlockSpec(memory_space=pl.ANY),
              pl.BlockSpec((rows, 1), lambda i, kb, *_: (0, 0))]
  if pair is None:
    # The tile's query rows, read where they lie in the flat batch: an
    # offset in rows, not in blocks (every dimension an element offset),
    # held inside the batch (the kernel knows the shift:
    # :func:`_tile_shift`).
    in_specs += [
        pl.BlockSpec(
            (pl.Element(tp), pl.Element(H), pl.Element(W)),
            lambda i, kb, slot, tile, count, cur, bound, starts: (
                jnp.minimum(starts[slot[i]] + tile[i] * tp, T - tp), 0, 0)),
        pl.BlockSpec((1, 1, W, block), leaf_idx)]
    operands = [into, pos, q, jnp.transpose(leaf, (0, 2, 3, 1))]
    out_spec = pl.BlockSpec(memory_space=pl.ANY)
    # The output's tile and a semaphore a size of copy out of it.
    scratch = [pltpu.VMEM((tp, H, v_width), dtype),
               pltpu.SemaphoreType.DMA((tp.bit_length(),))]
  else:
    def rows_idx(*a):
      b, _, _, kb = leaf_idx(*a)
      return b, kb, 0
    # One position is the first row of a block of a sublane tile's rows,
    # read and written (a row alone is no block of a 16-bit array).
    out_block = (1, tp if tp > 1 else min(into.shape[1], sublane_tile(dtype)),
                 H * hd)
    tile_idx = lambda i, kb, slot, tile, *_: (slot[i], tile[i], 0)
    in_specs += [pl.BlockSpec(out_block, tile_idx)] + [
        pl.BlockSpec((1, block, W), rows_idx)] * 2
    operands = [into, pos, q, leaf, values]
    out_spec = pl.BlockSpec(out_block, tile_idx)
    scratch = [pltpu.VMEM((rows, hd), dtype if tp > 1 else jnp.float32)]
  selected = scores is not None
  if selected:
    def score_idx(i, kb, slot, tile, *rest):
      return slot[i], tile[i], leaf_idx(i, kb, slot, tile, *rest)[3]
    in_specs += [pl.BlockSpec((1, tp, block), score_idx),
                 pl.BlockSpec((1, tp, 1),
                              lambda i, kb, slot, tile, *_: (slot[i], tile[i],
                                                             0))]
    operands += [scores.astype(jnp.float32),
                 threshold.astype(jnp.float32)[..., None]]
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_TILE_VMEM_LIMIT)
  out = pl.pallas_call(
      functools.partial(
          _tile_attn_kernel, block=block, num_blocks=nb, scale=float(scale),
          v_width=v_width, tp=tp, heads=H, window=window, ring=L, pair=pair,
          flat_rows=T, selected=selected),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=6,
          grid=(count[0], nb),
          in_specs=in_specs,
          out_specs=out_spec,
          scratch_shapes=[
              pltpu.VMEM((rows, LANES), jnp.float32),      # running max
              pltpu.VMEM((rows, LANES), jnp.float32),      # running sum
              pltpu.VMEM((rows, v_width), jnp.float32),    # accumulator
          ] + scratch),
      out_shape=jax.ShapeDtypeStruct(into.shape, dtype),
      # Operands count the six scalar-prefetch arrays: ``into`` follows.
      input_output_aliases={6: 0},
      interpret=interpret,
      name=(SLOT_ATTN_KVWIN if pair is not None else SLOT_ATTN_WIN if ring
            else SLOT_ATTN_SEL if selected else SLOT_ATTN),
      **kwargs,
  )(slot, tile, count, cur, bound, starts, *operands)
  return out


@functools.partial(jax.jit, static_argnames=("interpret", "block",
                                             "v_width", "scale", "chunk"))
def slot_attention_selected_pallas(q, latent, scores, threshold, cursors,
                                   num_valid=None, interpret: bool = False,
                                   block: Optional[int] = None, starts=None,
                                   chunk: Optional[int] = None, *,
                                   v_width: int, scale: float):
  return _tile_attention(q, latent, cursors, num_valid, scores, threshold,
                         None, interpret, block, v_width, scale, starts,
                         chunk)


@functools.partial(jax.jit, static_argnames=("interpret", "block",
                                             "v_width", "scale", "chunk"))
def slot_attention_tiled_pallas(q, latent, cursors, num_valid=None,
                                interpret: bool = False,
                                block: Optional[int] = None, starts=None,
                                chunk: Optional[int] = None, *,
                                v_width: int, scale: float):
  return _tile_attention(q, latent, cursors, num_valid, None, None, None,
                         interpret, block, v_width, scale, starts, chunk)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "block",
                                             "v_width", "scale", "chunk"))
def slot_attention_window_pallas(q, ring, cursors, num_valid=None,
                                 interpret: bool = False,
                                 block: Optional[int] = None, starts=None,
                                 chunk: Optional[int] = None, *, window: int,
                                 v_width: int, scale: float):
  return _tile_attention(q, ring, cursors, num_valid, None, None, window,
                         interpret, block, v_width, scale, starts, chunk)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "block",
                                             "scale"))
def slot_attention_kv_window_pallas(q, ring_k, ring_v, cursors,
                                    num_valid=None, interpret: bool = False,
                                    block: Optional[int] = None, *,
                                    window: int, scale: float):
  return _tile_attention(q, ring_k, cursors, num_valid, None, None, window,
                         interpret, block, q.shape[-1], scale, values=ring_v)


# --------------------------------------------------------------- dispatch --


def _check_impl(impl: str) -> None:
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")


def slot_attention(q, cached_k, cached_v, cursors, num_valid=None,
                   impl: Optional[str] = None,
                   v_width: Optional[int] = None,
                   scale: Optional[float] = None, starts=None,
                   chunk: Optional[int] = None):
  """Attend each slot's chunk over its own cache (module docstring);
  returns ``out [B, C, H, hd]`` (``[B, C, H, v_width]`` for a one-leaf
  layer: ``cached_v=None``, the values the keys' leading ``v_width``
  columns).  ``impl=None`` applies the dispatch rule
  to the shapes at hand (and to ``Env.mesh_built``).  A one-leaf layer
  whose caller found :func:`plain_tile_form` to hold hands ``q`` as the
  step's flat batch ``[T, H, hd]`` with ``starts`` and ``chunk``, as
  :func:`slot_attention_selected` takes it, and gets ``[T, H, v_width]``
  back: the same rows under the same mask, on the tile grid (a kernel's
  lowering only: the reference takes ``[B, C, H, hd]``)."""
  flat = starts is not None
  if impl is None:
    impl = resolve_slot_attn_impl(
        cached_k.shape, cached_k.dtype, chunk if flat else q.shape[1],
        q.shape[-2], sharded=Env.get().mesh_built(), head_dim=q.shape[-1])
  _check_impl(impl)
  if (cached_v is None) != (v_width is not None):
    raise ValueError("a one-leaf attend passes cached_v=None AND v_width; "
                     "a K/V pair passes neither")
  if flat:
    if cached_v is not None or impl == "reference":
      raise ValueError("the flat batch (starts=) is read by the tile kernel "
                       "of a one-leaf cache alone (plain_tile_form)")
    return slot_attention_tiled_pallas(
        q, cached_k, cursors, num_valid, interpret=impl == "interpret",
        starts=starts, chunk=chunk, v_width=v_width,
        scale=1.0 / math.sqrt(q.shape[-1]) if scale is None else float(scale))
  if impl == "reference":
    return slot_attention_reference(q, cached_k, cached_v, cursors,
                                    v_width=v_width, scale=scale)
  return slot_attention_pallas(q, cached_k, cached_v, cursors, num_valid,
                               interpret=impl == "interpret",
                               v_width=v_width, scale=scale)


def slot_attention_selected(q, latent, scores, threshold, cursors,
                            num_valid=None, *, impl: Optional[str] = None,
                            v_width: int, scale: float, starts=None):
  """Attend each slot's chunk over the SELECTED rows of its one-leaf
  cache ``[B, Lc, 1, W]``: query ``i`` of slot ``b`` sees row ``s <=
  cursors[b] + i`` iff ``scores[b, i, s] >= threshold[b, i]`` (the index
  scores of kernels/dsa_index.py and the query's k-th largest).  The
  values are the rows' leading ``v_width`` columns; ``out [B, C, H,
  v_width]``.  ``impl=None`` applies :func:`resolve_tile_attn_impl` to the
  operands at hand, as :func:`slot_attention` does.  The kernel also takes
  ``q`` as the step's flat batch ``[T, H, W]`` with ``starts`` (int32
  ``[B]``: the flat row of each slot's first position) and then returns
  ``[T, H, v_width]``, each live position's result at the row its query
  lies in and zeros elsewhere: no copy into ``[B, C]`` order on the way in
  or out.  The reference takes ``[B, C, H, W]`` only."""
  if impl is None:
    impl = resolve_tile_attn_impl(latent.shape, latent.dtype,
                                  scores.shape[1], q.shape[-2], v_width,
                                  sharded=Env.get().mesh_built())
  _check_impl(impl)
  if impl == "reference":
    return slot_attention_selected_reference(q, latent, scores, threshold,
                                             cursors, v_width, scale)
  return slot_attention_selected_pallas(
      q, latent, scores, threshold, cursors, num_valid,
      interpret=impl == "interpret", starts=starts,
      chunk=None if starts is None else scores.shape[1], v_width=v_width,
      scale=scale)


def slot_attention_window(q, ring, cursors, num_valid=None, *,
                          impl: Optional[str] = None, window: int,
                          v_width: int, scale: float, starts=None,
                          chunk: Optional[int] = None):
  """Attend each slot's chunk over a RING leaf ``[B, R, 1, W]`` (position
  ``p`` at row ``p mod R``, written through ``kv_write(..., ring=True)``):
  query ``i`` of slot ``b``, at ``t = cursors[b] + i``, sees the positions
  ``t - window < p <= t``.  ``out [B, C, H, v_width]``.  ``starts`` and
  ``chunk``: ``q`` as the flat batch and the result as its rows ``[T, H,
  v_width]``, as :func:`slot_attention_selected` takes and gives them, and
  ``impl=None`` as it resolves it."""
  if impl is None:
    impl = resolve_tile_attn_impl(
        ring.shape, ring.dtype, q.shape[1] if starts is None else chunk,
        q.shape[-2], v_width, ring=True, sharded=Env.get().mesh_built())
  _check_impl(impl)
  if impl == "reference":
    return slot_attention_window_reference(q, ring, cursors, num_valid,
                                           window, v_width, scale)
  return slot_attention_window_pallas(
      q, ring, cursors, num_valid, interpret=impl == "interpret",
      starts=starts, chunk=chunk, window=window, v_width=v_width,
      scale=scale)


def slot_attention_kv_window(q, ring_k, ring_v, cursors, num_valid=None, *,
                             impl: Optional[str] = None, window: int,
                             scale: Optional[float] = None):
  """Attend each slot's chunk over a RING of K/V PAIRS (``ring_k``,
  ``ring_v`` ``[B, R, H_kv x hd]``, position ``p`` at row ``p mod R``,
  written through ``kv_write(..., ring=True)``) under grouped heads: query
  ``i`` of slot ``b``, at ``t = cursors[b] + i``, sees the positions ``t -
  window < p <= t``; query head ``h`` reads K/V head ``h // (H / H_kv)``.
  ``q`` and ``out`` ``[B, C, H, hd]``.  ``scale`` defaults to ``1 /
  sqrt(hd)``.  ``impl=None`` applies :func:`resolve_tile_attn_impl` to the
  ring's shape as it lies (rank 3, kept in rows, is what it takes)."""
  if impl is None:
    impl = resolve_tile_attn_impl(ring_k.shape, ring_k.dtype, q.shape[1],
                                  q.shape[2], q.shape[3], ring=True,
                                  sharded=Env.get().mesh_built())
  _check_impl(impl)
  scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else float(scale)
  if impl == "reference":
    return slot_attention_kv_window_reference(q, ring_k, ring_v, cursors,
                                              num_valid, window, scale)
  # The kernel reads rows of ``H_kv x hd`` lanes: what the rule takes on a
  # TPU is kept so; a pair kept in positions (interpreted, a toy width)
  # folds its heads here.
  in_rows = lambda ring: ring.reshape(ring.shape[0], ring.shape[1], -1)
  return slot_attention_kv_window_pallas(
      q, in_rows(ring_k), in_rows(ring_v), cursors, num_valid,
      interpret=impl == "interpret", window=window, scale=scale)
