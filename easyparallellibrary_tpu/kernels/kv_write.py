"""In-place K/V window write — Pallas TPU kernel + the XLA reference.

The serving engine's fused step appends a ``chunk``-wide K/V window to
every layer's contiguous cache at each slot's own cursor
(``models.slot_core.slot_cache_attend``; the layout note in
``serving/kv_cache.py`` is the contract: the full ``chunk``-wide window
lands at ``cursor``, never clamped, never shifted).  One algorithm, two
lowerings, behind one dispatcher:

* **reference** — ``jax.vmap(dynamic_update_slice)``, one cursor per
  slot.  XLA makes it a ``scatter``, which the TPU compiler expands into
  a serial loop of one trip per slot and leaf (bounds check, slice one
  slot's update, write it into the whole leaf in place).  Correct
  everywhere, and cheap wherever the leaf is small.
* **pallas** — one launch per layer, K and V as two operands aliased to
  their outputs, cursors scalar-prefetched, in the form the leaf's ORDER
  asks for (read off its rank; ``serving/kv_cache.py:cache_leaves``
  decides the order from the heads' width alone):

  - **rows** (rank 3, ``[slot, position, H * hd]``: the heads' width
    fills whole lane tiles, so the TPU keeps a position's values
    contiguous).  A window is ``chunk`` rows and lies in at most two
    STRIPES of ``chunk`` rows rounded up to a sublane tile (16 rows of
    bfloat16 x 1024 lanes = 32 KB, contiguous in memory).  Grid over the
    slots the step FEEDS (``num_valid > 0``; their number is a value, so
    the grid's first dimension is dynamic and the program compiles
    once) x the window's stripes: each step reads the stripe, rolls the
    chunk's rows to the window's offset along the sublanes, selects them
    in under a row mask and writes the stripe back.  A 16-bit leaf packs
    two rows into one 32-bit sublane word, so an odd cursor would move
    half-words: the roll and the select run in float32, which holds every
    bfloat16 value exactly, and round back.  An idle slot costs no grid
    step and its window is not written (nothing reads it:
    ``slot_cache_attend``'s note on rows beyond a bound).  The chunk needs
    no relayout: it leaves the QKV matmul as rows of ``H * hd``.
  - **positions** (rank 4, ``[slot, position, H, hd]``, which the TPU
    keeps position-minor, ``[slot, H, hd, position]`` in memory, because
    ``hd`` minor would pad its lanes to 128).  The kernel addresses the
    leaf in that order — the transposes around the call are bitcasts —
    and a window is a run of lanes inside one 128-position tile, or two
    when it straddles a boundary.  Grid over ALL slots: each step reads
    the tile, rotates the chunk to the window's lane offset, selects it in
    under a lane mask and writes the tile back.

  Either way no other byte of the donated leaf moves, and a FED slot's
  window afterwards is bit-identical to the reference's (data movement
  only: no arithmetic changes a value).

Dispatch rule (docs/serving.md): the kernel runs when the backend is TPU,
the leaf is not spread over a multi-device mesh and the shapes fit its
tiles (:func:`kv_write_fits`); the reference runs everywhere else.  The
rule reads nothing but what it is handed and the backend — no
configuration field, no environment variable, no setter.  A third impl,
``interpret``, runs the kernel in Pallas interpreter mode: the parity
tests' CPU vehicle, reached by naming it or by patching
:func:`_backend_impl`.  The engine resolves
the lowering ONCE when it builds its step and records it (trace metadata
``serving/kv_write_impl``, ``engine.lowerings["kv_write_impl"]``).

Shapes: ``cached_k/cached_v`` ``[B, Lc, H * hd]`` (rows) or ``[B, Lc, H,
hd]`` (positions); ``k/v`` ``[B, C, H, hd]`` or ``[B, C, H * hd]``;
``cursors``, ``num_valid`` int32 ``[B]``.

A layer whose cache is ONE leaf (models/glm_moe.py: the latent ``[B, Lc,
1, 576]`` whose values are its keys' leading columns) passes ``cached_v =
v = None``: the same call with one operand instead of two, and ``None``
back in the second place.

A cache kept as a RING (``ring=True``: ``R`` rows, a whole number of
128-position tiles) takes position ``p`` at row ``p mod R``: the window
starts at ``cursor mod R`` and a chunk that crosses ``R`` lands in two
blocks, the second at the leaf's head.  Both forms: positions (models/
dots3_note.py's window layers, one latent leaf ``[B, R, 1, W]``: the
second TILE is the one that holds the window's last row going round) and
rows (models/smallthinker.py's window layers, a K/V pair ``[B, R, H_kv x
hd]``, ``R`` in whole stripes: the stripe after the last IS the first, so
the kernel's arithmetic is the straight leaf's and its index map alone
goes round).  The reference writes the same rows by their indices.
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
    live_order, sublane_tile)

# The kernel's name in a device trace (see ``flash_attention.FLASH_FWD``).
# The benchmark reads it (PERF.md section 3).
KV_WRITE = "kv_write"

IMPLS = ("pallas", "reference", "interpret")

# Positions per tile: the lane width of the position-minor leaf, and the
# width the heads of a leaf kept in rows fill in whole multiples.
LANES = 128
# VMEM the kernel may ask for.  It holds 13 blocks (a 128-position tile of
# the positions form, a stripe of the rows form): K and V blocks in and
# out, double-buffered (8), the two chunks, padded to a block and
# double-buffered (4), and the staging block.  v5e's scoped default is 16
# MiB; GPT-2 medium's bf16 leaf takes 3.3 MiB in positions, 0.8 in rows.
_VMEM_BUDGET = 12 * 1024 * 1024
_VMEM_TILES = 13


def _backend_impl() -> str:
  """The lowering this backend takes when the shapes allow it.  The CPU
  parity tests patch it to ``interpret``."""
  return "pallas" if jax.default_backend() == "tpu" else "reference"


def stripe_rows(chunk: int, dtype) -> int:
  """Rows of one stripe of a leaf kept in rows: the chunk rounded up to
  whole sublane tiles, so a window touches two stripes at most."""
  tile = sublane_tile(dtype)
  return -(-chunk // tile) * tile


def kv_write_fits(cache_shape, dtype, chunk: int,
                  ring: bool = False) -> bool:
  """Whether the kernel can tile a leaf of ``dtype`` for ``chunk``-wide
  windows, in the form its rank asks for: a 32-bit or 16-bit float leaf,
  a window of at most 128 positions, blocks within the VMEM budget, a
  ring in whole 128-position tiles, and

  * rows ``[B, Lc, W]``: ``W`` whole lane tiles and at least one whole
    stripe (:func:`stripe_rows`), a ring whole stripes;
  * positions ``[B, Lc, H, hd]``: at least one whole 128-position tile
    (so a window touches two at most) and an ``hd`` that fills whole
    sublane tiles."""
  dtype = jnp.dtype(dtype)
  if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
    return False
  if not 1 <= chunk <= LANES:
    return False
  if ring and cache_shape[1] % LANES:
    return False
  if len(cache_shape) == 3:
    _, Lc, W = cache_shape
    stripe = stripe_rows(chunk, dtype)
    if W % LANES or Lc < stripe or (ring and Lc % stripe):
      return False
    # The staging block and the arithmetic are float32 whatever the leaf.
    return _VMEM_TILES * stripe * W * 4 <= _VMEM_BUDGET
  _, Lc, H, hd = cache_shape
  if Lc < LANES or hd % sublane_tile(dtype):
    return False
  return _VMEM_TILES * H * hd * LANES * dtype.itemsize <= _VMEM_BUDGET


def resolve_kv_write_impl(cache_shape, dtype, chunk: int,
                          sharded: bool = False, ring: bool = False) -> str:
  """The dispatch rule: the backend's lowering (``pallas`` on a TPU,
  ``reference`` elsewhere), and ``reference`` whenever the leaf lives on
  a multi-device mesh (``sharded``: the SPMD partitioner cannot split a
  Mosaic call) or the shapes do not fit (:func:`kv_write_fits`)."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not kv_write_fits(cache_shape, dtype, chunk, ring)):
    return "reference"
  return impl


# -------------------------------------------------------------- reference --


def kv_write_reference(cached_k, cached_v, k, v, cursors,
                       ring: bool = False):
  """One ``dynamic_update_slice`` per slot at its own cursor, a leaf of
  either order (the chunk takes the leaf's own trailing dimensions: heads
  folded into rows or apart).  A ring takes the chunk's rows at their
  positions modulo its length."""
  def write(cache, new):
    if cache is None:
      return None
    new = new.astype(cache.dtype).reshape(new.shape[:2] + cache.shape[2:])
    if ring:
      at = jnp.mod(cursors[:, None] + jnp.arange(new.shape[1])[None],
                   cache.shape[1])
      return jax.vmap(lambda row, chunk, idx: row.at[idx].set(chunk))(
          cache, new, at)
    start = (0,) * (cache.ndim - 2)
    return jax.vmap(
        lambda row, chunk, cur: jax.lax.dynamic_update_slice(
            row, chunk, (cur,) + start))(cache, new, cursors)
  return write(cached_k, k), write(cached_v, v)


# ----------------------------------------------------------------- pallas --


def _window_block(cur, j, chunk: int, width: int):
  """Index of the block of ``width`` positions (a tile of the positions
  form, a stripe of the rows form) that holds the window's first (``j ==
  0``) or last (``j == 1``) position; the same block twice when the
  window does not straddle."""
  return (cur + j * (chunk - 1)) // width


def _kv_write_rows_kernel(order_ref, live_ref, cur_ref, fed_ref, *refs,
                          chunk: int, stripe: int):
  """One (fed slot, stripe) grid step of the rows form: lay the slot's
  chunk over rows ``[cursor, cursor + chunk)`` of this stripe, for K and
  V (``refs``: the leaves' chunks, their stripes in, their stripes out,
  the staging stripe; one leaf or two).

  Values are ``[row, H * hd]``: positions on sublanes.  The chunk is
  staged into rows ``[0, chunk)`` of a float32 scratch stripe, rolled
  along the sublanes to the window's offset and selected in under the
  row mask; rows the roll wraps around fall outside the mask.  All of it
  in float32: a 16-bit leaf packs two rows into a sublane word, an odd
  cursor would move half-words, and float32 holds every bfloat16 value
  exactly.  ``order_ref`` names the slot of this grid row (fed slots
  only are visited; ``live_ref`` is the grid's alone); when no slot is
  fed the one row the grid still has writes its stripe back as it was."""
  del live_ref
  *refs, stage_ref = refs
  n = len(refs) // 3
  b = order_ref[pl.program_id(0)]
  j = pl.program_id(1)
  cur = cur_ref[b]
  # Where the window starts relative to this stripe: negative in the
  # second stripe of a straddling window.
  off = cur - _window_block(cur, j, chunk, stripe) * stripe
  shift = jnp.where(off < 0, off + stripe, off)
  row = jax.lax.broadcasted_iota(jnp.int32, stage_ref.shape, 0)
  window = (row >= off) & (row < off + chunk) & (fed_ref[b] > 0)
  for new_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
    stage_ref[:chunk] = new_ref[0].astype(jnp.float32)
    moved = pltpu.roll(stage_ref[...], shift, 0)
    out_ref[0] = jnp.where(window, moved,
                           in_ref[0].astype(jnp.float32)).astype(
                               out_ref.dtype)


def _kv_write_rows(caches, news, cursors, num_valid, interpret: bool,
                   ring: bool = False):
  """The rows form over ``[B, Lc, W]`` leaves and ``[B, C, W]`` chunks.  A
  ring is whole stripes, so the stripe after its last is its first: the
  index map goes round and the kernel, which works from the window's
  offset in the stripe it is handed, is the straight leaf's."""
  B, Lc, W = caches[0].shape
  C = news[0].shape[1]
  n = len(caches)
  stripe = stripe_rows(C, caches[0].dtype)
  fed = (jnp.ones((B,), jnp.bool_) if num_valid is None
         else num_valid > 0)
  order, live = live_order(fed)

  def stripe_idx(i, j, order, live, cur, fed):
    b = order[i]
    at = _window_block(cur[b], j, C, stripe)
    return (b, at % (Lc // stripe) if ring else at, 0)

  chunk_spec = pl.BlockSpec((1, C, W),
                            lambda i, j, order, *_: (order[i], 0, 0))
  stripe_spec = pl.BlockSpec((1, stripe, W), stripe_idx)
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=4,
      grid=(live[0], 1 if C == 1 else 2),
      in_specs=[chunk_spec] * n + [stripe_spec] * n,
      out_specs=[stripe_spec] * n,
      scratch_shapes=[pltpu.VMEM((stripe, W), jnp.float32)],
  )
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
  written = pl.pallas_call(
      functools.partial(_kv_write_rows_kernel, chunk=C, stripe=stripe),
      grid_spec=grid_spec,
      out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
      # Operands count the four scalar-prefetch vectors: the leaves
      # follow them and the chunks.
      input_output_aliases={4 + n + i: i for i in range(n)},
      interpret=interpret,
      name=KV_WRITE,
      **kwargs,
  )(order, live, cursors, fed.astype(jnp.int32), *news, *caches)
  return written[0], (written[1] if n == 2 else None)


def _ring_tile(cur, j, chunk: int, ring: int):
  """The 128-position tile of a ring that holds the window's first (``j
  == 0``) or last (``j == 1``) row; the last row of a window that crosses
  the ring's end lies at the leaf's head (``ring`` is a whole number of
  tiles, so such a window wraps between tiles, or, in a ring of one tile,
  inside it)."""
  last = cur + chunk - 1
  return jnp.where(j == 0, cur, jnp.where(last >= ring, last - ring,
                                          last)) // LANES


def _kv_write_kernel(cur_ref, *refs, chunk: int, ring: int = 0):
  """One (slot, tile) grid step of the positions form: lay the slot's
  chunk over the lanes ``[cursor, cursor + chunk)`` of this 128-position
  tile, for K and V (``refs``: the leaves' chunks, their tiles in, their
  tiles out, the staging tile; one leaf or two).

  Values keep ``[H, hd, position]`` — ``hd`` on sublanes, positions on
  lanes.  The chunk is staged into lanes ``[0, chunk)`` of a scratch
  tile, rotated to the window's offset and selected in under the lane
  mask; lanes the rotation wraps around fall outside the mask.  A
  window inside one tile visits it twice and writes the same values
  twice: the block stays resident between the two steps, so nothing
  moves, and skipping the second pass saved no time on the chip (the
  tile's DMA bounds a step, not its arithmetic)."""
  *refs, stage_ref = refs
  n = len(refs) // 3
  k_in_ref = refs[n]
  b = pl.program_id(0)
  j = pl.program_id(1)
  cur = cur_ref[b]
  # Where the window starts relative to this tile: negative in the second
  # tile of a straddling window.
  lane = jax.lax.broadcasted_iota(jnp.int32, stage_ref.shape, 2)
  if ring:
    # A lane is in the window iff its row lies less than a chunk ahead of
    # the cursor, going round the ring; the chunk's row i belongs at lane
    # (cursor + i) mod 128 in whichever tile holds it.
    ahead = _ring_tile(cur, j, chunk, ring) * LANES + lane - cur
    window = jnp.where(ahead < 0, ahead + ring, ahead) < chunk
    shift = cur % LANES
  else:
    off = cur - _window_block(cur, j, chunk, LANES) * LANES
    shift = jnp.where(off < 0, off + LANES, off)
    window = (lane >= off) & (lane < off + chunk)
  # Mosaic rotates 32-bit lanes only: a 16-bit leaf goes through as the
  # uint32 words its sublane pairs already are in a register.
  packed = k_in_ref.dtype.itemsize < 4
  as32 = (lambda x: pltpu.bitcast(x, jnp.uint32)) if packed else (lambda x: x)
  for new_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
    stage_ref[:, :, :chunk] = as32(new_ref[0])
    moved = pltpu.roll(stage_ref[...], shift, 2)
    merged = jnp.where(window, moved, as32(in_ref[0]))
    out_ref[0] = pltpu.bitcast(merged, out_ref.dtype) if packed else merged


@functools.partial(jax.jit, static_argnames=("interpret", "ring"))
def kv_write_pallas(cached_k, cached_v, k, v, cursors, num_valid=None,
                    interpret: bool = False, ring: bool = False):
  """The in-place window write, in the form the leaf's rank asks for
  (module docstring); ``interpret`` runs the kernel in Pallas
  interpreter mode (any backend).  ``num_valid`` (rows form: the slots
  to visit; ``None`` = all) is not read by the positions form, which
  writes every slot's window.  Jitted, so that the layers of one
  step share one trace and one Mosaic lowering of the kernel (a
  ``pallas_call`` per layer, lowered apart, cost the 24-layer serving
  step seconds of set-up); XLA inlines the calls."""
  Lc = cached_k.shape[1]
  C = k.shape[1]
  dtype = cached_k.dtype
  caches, news = ((cached_k, cached_v), (k, v)) if cached_v is not None \
      else ((cached_k,), (k,))
  n = len(caches)
  # ``dynamic_update_slice`` clamps a start that would run the window
  # off the leaf; the contract keeps cursors inside (kv_cache.py), and
  # the clamp keeps the two lowerings equal outside it too.
  cursors = (jnp.mod(cursors.astype(jnp.int32), Lc) if ring
             else jnp.clip(cursors.astype(jnp.int32), 0, Lc - C))
  if cached_k.ndim == 3:
    news = [x.astype(dtype).reshape(x.shape[:2] + cached_k.shape[2:])
            for x in news]
    return _kv_write_rows(caches, news, cursors, num_valid, interpret, ring)
  B, _, H, hd = cached_k.shape
  # Position-minor views: bitcasts on the TPU, whose layout of the leaf
  # is already this.
  to_minor = lambda x: jnp.transpose(x.astype(dtype), (0, 2, 3, 1))
  n_tiles = 1 if C == 1 else 2

  def tile_idx(b, j, cur):
    if ring:
      return (b, 0, 0, _ring_tile(cur[b], j, C, Lc))
    return (b, 0, 0, _window_block(cur[b], j, C, LANES))

  chunk_spec = pl.BlockSpec((1, H, hd, C), lambda b, j, cur: (b, 0, 0, 0))
  tile_spec = pl.BlockSpec((1, H, hd, LANES), tile_idx)
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(B, n_tiles),
      in_specs=[chunk_spec] * n + [tile_spec] * n,
      out_specs=[tile_spec] * n,
      # 32-bit words: a 16-bit leaf packs two ``hd`` rows into each.
      scratch_shapes=[pltpu.VMEM((H, hd * dtype.itemsize // 4, LANES),
                                 jnp.float32 if dtype.itemsize == 4
                                 else jnp.uint32)],
  )
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
  leaf = jax.ShapeDtypeStruct((B, H, hd, Lc), dtype)
  written = pl.pallas_call(
      functools.partial(_kv_write_kernel, chunk=C, ring=Lc if ring else 0),
      grid_spec=grid_spec,
      out_shape=[leaf] * n,
      # Operands count the scalar-prefetch cursors: the leaves follow
      # them and the chunks (3, 4 of two leaves; 2 of one).
      input_output_aliases={1 + n + i: i for i in range(n)},
      interpret=interpret,
      name=KV_WRITE,
      **kwargs,
  )(cursors, *map(to_minor, news), *map(to_minor, caches))
  to_major = lambda x: jnp.transpose(x, (0, 3, 1, 2))
  return (to_major(written[0]),
          to_major(written[1]) if n == 2 else None)


# --------------------------------------------------------------- dispatch --


def kv_write(cached_k, cached_v, k, v, cursors, num_valid=None,
             impl: Optional[str] = None, ring: bool = False):
  """Write each slot's K/V chunk at its cursor (module docstring);
  returns ``(new_cached_k, new_cached_v)``, the second ``None`` for a
  one-leaf layer (``cached_v = v = None``).  ``num_valid`` (``None`` =
  every slot is fed) lets the rows form skip the slots the step does not
  feed.  ``ring`` writes the cache as a ring (module docstring).
  ``impl=None`` applies the dispatch rule to the shapes at hand
  (and to ``Env.mesh_built``)."""
  if impl is None:
    impl = resolve_kv_write_impl(cached_k.shape, cached_k.dtype, k.shape[1],
                                 sharded=Env.get().mesh_built(), ring=ring)
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  if impl == "reference":
    return kv_write_reference(cached_k, cached_v, k, v, cursors, ring=ring)
  return kv_write_pallas(cached_k, cached_v, k, v, cursors, num_valid,
                         interpret=impl == "interpret", ring=ring)
