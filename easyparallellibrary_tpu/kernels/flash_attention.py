"""Flash attention — Pallas TPU kernel.

The framework's hot-op kernel layer (the role the reference's csrc/ plays
for communication, played here for compute): attention without
materializing the [S, S] score matrix in HBM.  Forward and backward are
blockwise with online softmax, keeping tiles in VMEM and feeding the MXU
with [block, d] matmuls.

Algorithm: FlashAttention-2 style.  Forward saves (out, logsumexp);
backward recomputes P blockwise from (q, k, lse) — one kernel produces
dk/dv (walking KV blocks), another dq (walking Q blocks).

Two implementations per kernel, dispatched by sequence length:

* **resident** (short S): a grid step owns a whole head, with q, k, v,
  dO, o, lse and delta of the head in VMEM, and walks its ``[tq, tk]``
  tiles in two nested loops.  Where heads of ``D`` lanes pack whole
  128-lane tiles (:func:`flash_layout`: ``D`` 128, 64 or 32 and a head
  count that divides) the operands stay in ROWS, ``[B, S, H x D]``, the
  layout the ``qkv`` and ``proj`` matmuls write and read, and a grid
  step owns the ``[S, 128]`` of the heads that share a tile (a pair at
  ``D`` 64), walking them one after the other: each head's products
  contract over the whole tile with the other heads' lanes of one
  operand zeroed, so every load, store and matmul operand is a full
  tile and nothing is shuffled between lanes.  ``[B, S, H, D]`` is the
  same bytes, so nothing is transposed; q, k and v may be the three
  column blocks of ONE fused projection's output
  (:func:`flash_attention_qkv`).  Elsewhere the operands are head-major,
  ``[B, H, S, D]``, behind a transpose either way.  Under a causal mask a row tile walks the
  key tiles wholly under the diagonal with no mask arithmetic, then the
  tile or tiles the diagonal crosses with one compare and one select;
  tiles beyond it are never touched (:func:`causal_tile_counts`).  The
  tile is not the grid's block: it is chosen for the chip
  (:func:`_default_block`).  Heads of up to ``_UNROLL_PAIRS`` pairs
  (S 1024) are walked by loops unrolled at trace time.
* **streaming** (long S): a fourth grid dimension streams the inner
  blocks with VMEM scratch accumulators carried across steps, so VMEM
  holds only [block, D] tiles and usage is INDEPENDENT of S (the
  resident layout exceeds the ~16 MB VMEM budget at S·D ≳ 1M, e.g.
  S=16k at D=64).  Under a causal mask the inner index map clamps to
  the last live block, so fully-masked blocks are neither fetched
  (Mosaic elides the DMA when the mapped block index repeats) nor
  computed (``pl.when``), and blocks default wider (1024) to amortize
  the grid's steps.

Measured on one TPU v5e, the three kernels alone at the train cell's
shape a chip (B 8, H 20, S 1024, D 64, bfloat16, causal; microseconds a
call of ``flash_fwd`` / ``flash_dkv`` / ``flash_dq``, device time of the
custom calls in a profiler trace; PERF.md section 6, PR 43, has the whole
sweep).  Before: blocks of 512 as the grid's blocks, the mask on every
block, 738 / 1017 / 645.  Unrolled tiles of 256: 413 / 602 / 458; of
512: 425 / 687 / 523; of 128 (64 tiles a head, with ``_UNROLL_TILES``
lifted): 440 / 596 / 550.  The same tiles under
``fori_loop``: 256 1095 / 1218 / 1031, 512 833 / 981 / 842: an
iteration's chain of matmul, lane reduce, exp and matmul is paid a tile
and nothing overlaps it, which is what made small blocks slow, not the
grid's steps.  At D 64 every product fills half the MXU, and the backward
kernels are bound by it.  The crossover (``_RESIDENT_MAX_BYTES``) is the
VMEM wall: streaming is the only option past it.

Rows against head-major at that shape (PERF.md section 6, PR 49): XLA
keeps an array whose minor dimension is 64 position-minor (a 64-wide
minor dimension pads its 128 lanes) and a Mosaic call demands row-major,
so every head-major operand and result of the three kernels cost a
relayout copy, 13 a layer of the four-chip train step, again under remat.

Used by models via ``attn_impl="pallas_flash"`` and as the local block of
ring attention (head-major primitives ``_fwd`` / ``_bwd_kernels``).  Off-TPU the kernels run in Pallas interpreter mode so
tests exercise identical code paths on CPU.
"""

from __future__ import annotations

import functools
import json
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.env import Env
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.utils.compat import (
    ambient_manual_axes, shard_map)
from easyparallellibrary_tpu.utils.sharding import constrain

NEG_INF = -1e30

# The kernels' names in a device trace: each ``pallas_call`` below passes
# one as ``name=``, which makes it the innermost scope of the Mosaic custom
# call and so the name of its instruction on the trace's ``XLA Ops`` line.
# The resident and the streaming variant of a kernel do the same required
# work and share a name.  The benchmark reads these (PERF.md section 3):
# renaming one takes a ``benchmark`` issue.
FLASH_FWD = "flash_fwd"
FLASH_DKV = "flash_dkv"
FLASH_DQ = "flash_dq"


def _interpret() -> bool:
  return jax.default_backend() != "tpu"


def _score_tile(qblk, kblk, q_start, k_start, causal: bool, scale: float):
  """Masked fp32 score tile for one [BQ, D] x [BK, D] block pair.

  Matmul inputs stay in the storage dtype (bf16 on the bench path): the
  MXU multiplies bf16 natively with fp32 accumulation
  (preferred_element_type), which is ~4x the fp32-matmul rate on v5e;
  upcasting the operands first would force full fp32 matmuls — measured
  at a large fraction of the kernel's runtime.  Softmax stays fp32.  The
  causal mask compares GLOBAL positions via the block offsets
  (q_start, k_start)."""
  bq, bk = qblk.shape[0], kblk.shape[0]
  s = jax.lax.dot_general(qblk, kblk, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32) * scale
  if causal:
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(q_pos >= k_pos, s, NEG_INF)
  return s


# --------------------------------------------------------------- forward --

# Largest per-array S*D footprint (BYTES, so fp32 operands halve the
# sequence reach) the resident kernels may hold whole in VMEM: 1 MB per
# array; with double-buffering and 2-4 resident arrays per kernel this
# stays well inside the 16 MB budget (bf16 S=8192 at D=64 measured fine;
# S=16384 overflows).
_RESIDENT_MAX_BYTES = 1024 * 1024


def _scale_folds(scale: float, dtype) -> bool:
  """Whether the softmax scale can be folded into a ``[tile, D]`` operand
  of the score product instead of multiplying the ``[tq, tk]`` score
  tile: exact when the scale is a power of two (D 16, 64, 256) and the
  operand is a binary float, where the multiply only moves exponents."""
  return (math.frexp(scale)[0] == 0.5
          and jnp.issubdtype(dtype, jnp.floating))


def _clamp(x, n: int):
  return min(x, n) if isinstance(x, int) else jnp.minimum(x, n)


def _key_tiles(i, tq: int, tk: int, num_k: int, causal: bool):
  """``(full, live)`` for row tile ``i``: key tiles ``[0, full)`` lie
  wholly at or under the diagonal and are walked with no mask, tiles
  ``[full, live)`` are crossed by it and are walked with the mask, tiles
  from ``live`` on hold no live pair and are skipped.  Plain arithmetic
  on a Python int (:func:`causal_tile_counts`, an unrolled walk) or on a
  ``fori_loop``'s index alike.  Without a mask every tile is full."""
  if not causal:
    return num_k, num_k
  return (_clamp((i * tq + 1) // tk, num_k),
          _clamp(((i + 1) * tq + tk - 1) // tk, num_k))


def _row_tiles(j, tq: int, tk: int, num_q: int, causal: bool):
  """``(lo, full)`` for key tile ``j``, the dK/dV kernel's view of the
  same triangle: row tiles before ``lo`` are skipped, ``[lo, full)`` are
  crossed by the diagonal, tiles from ``full`` on lie wholly under it."""
  if not causal:
    return 0, 0
  return (_clamp((j * tk) // tq, num_q),
          _clamp(((j + 1) * tk + tq - 2) // tq, num_q))


def causal_tile_counts(S: int, Skv: int, tq: int, tk: int):
  """``(unmasked, masked, skipped)``: the ``[tq, tk]`` tiles of a causal
  ``[S, Skv]`` call that the resident kernels walk with no mask
  arithmetic, walk with the mask, and never touch.  Shapes decide it, so
  it is static; the kernels' loop bounds are the same arithmetic
  (:func:`_key_tiles`).  Computed pairs are ``(unmasked + masked)
  * tq * tk`` against the triangle's ``sum_q min(q + 1, Skv)``."""
  num_q, num_k = S // tq, Skv // tk
  unmasked = masked = 0
  for i in range(num_q):
    full, live = _key_tiles(i, tq, tk, num_k, True)
    unmasked += full
    masked += live - full
  return unmasked, masked, num_q * num_k - unmasked - masked


# A head of at most this many (query, key) pairs in at most this many tiles
# is walked by loops unrolled at trace time (every bound a Python int), so
# that the scheduler overlaps one tile's matmuls with its neighbour's
# softmax: an iteration's chain of matmul, lane reduce, exp and matmul is
# ~500 cycles whatever the tile's size, and a ``fori_loop`` pays it a
# tile.  Longer heads keep ``fori_loop`` on the same bounds: unrolled,
# their temporaries overflow the kernel's VMEM (v5e: S 2048 in float32 at
# tiles of 256, S 4096 in bfloat16 at 512 are refused at compile time);
# the cap on tiles bounds the kernel's code, which the default tile never
# reaches (16 tiles at S 1024).
_UNROLL_PAIRS = 1024 * 1024
_UNROLL_TILES = 16


def _tile_start(i, t: int):
  return i * t if isinstance(i, int) else pl.multiple_of(i * t, t)


def _walk(lo, hi, body, carry, unroll: bool):
  """``fori_loop``, or its trips unrolled at trace time (every bound of
  an unrolled head is a Python int).  A range that is empty by its Python
  bounds (the masked walk of a call with no mask) traces nothing."""
  if isinstance(lo, int) and isinstance(hi, int) and lo >= hi:
    return carry
  if unroll:
    for i in range(lo, hi):
      carry = body(i, carry)
    return carry
  return jax.lax.fori_loop(lo, hi, body, carry)


def _rel_pos(rows: int, cols: int, row_axis: int):
  """``[rows, cols]`` int32 of (row position - key position) within a
  tile whose query rows run along ``row_axis``.  A pair at tile offsets
  ``(q0, k0)`` is live iff ``rel >= k0 - q0``: one compare against a
  scalar on the tiles the diagonal crosses, none elsewhere."""
  r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), row_axis)
  c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1 - row_axis)
  return r - c


_NT = (((1,), (1,)), ((), ()))      # [m, d] x [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))      # [m, n] x [n, d] -> [m, d]


def _dot(a, b, dims):
  # Operands stay in the storage dtype (bf16 on the bench path): the MXU
  # multiplies bf16 natively and accumulates in fp32.
  return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _head_block(rows: int, cols: int):
  """A head's whole ``[rows, cols]`` of a ``[B, H, rows, cols]`` array, on
  the resident kernels' ``(B, H)`` grid."""
  return pl.BlockSpec((1, 1, rows, cols), lambda b, h: (b, h, 0, 0))


# The lanes of a vector tile: an array kept in ROWS, ``[B, S, H x D]`` as
# the projections write and read it, is taken by the resident kernels in
# blocks of ``[S, _LANES]``, the ``_LANES // D`` heads that share a tile.
_LANES = 128
# At most this many heads a tile: a grid step walks its heads one after the
# other, unrolled, and eight heads of 16 lanes at S 1024 overflow VMEM
# (18.2 MB of 16, refused at compile time for a described v5e).
_TILE_HEADS = 4


def _tile_block(rows: int, first: int = 0):
  """The whole ``[rows, 128]`` of lane tile ``first + p`` of a ``[B, rows,
  n x 128]`` array kept in rows, on the ``(B, lane tiles of a head group)``
  grid.  ``first`` picks a column block of a wider array (q, k and v of one
  fused projection's output)."""
  return pl.BlockSpec((1, rows, _LANES), lambda b, p: (b, 0, first + p))


def _group_block(heads: int, cols: int):
  """The ``[heads, 8, cols]`` of lse / delta that belong to a lane tile's
  heads: those stay ``[B, H, 8, S]`` under either layout."""
  return pl.BlockSpec((1, heads, 8, cols), lambda b, p: (b, p, 0, 0))


def _rows_at(ref, start, size: int):
  """Index of rows ``[start, start + size)``, every lane, of a grid step's
  block: one head's ``[1, 1, S, D]`` of a head-major array or one lane
  tile's ``[1, S, 128]`` of an array kept in rows."""
  return (0,) * (len(ref.shape) - 2) + (pl.ds(start, size), slice(None))


def _head_lanes(lanes: int, d: int):
  """``[1, lanes]`` int32, the head within the block that each lane
  belongs to; None where the block is one head's."""
  if lanes == d:
    return None
  lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
  return jax.lax.shift_right_logical(lane, d.bit_length() - 1)


def _of_head(x, head_of, h: int, factor: float = 1.0):
  """``x`` times ``factor`` in head ``h``'s lanes and zero in the other
  heads' of the tile, in ``x``'s dtype: the operand of a product that
  contracts over all the tile's lanes and sees one head.  The zeros add
  nothing to a float32 sum, so a head's scores are what its own ``D``
  lanes give; at ``D`` 64 a product already fills half the MXU's depth,
  so the zeroed half costs no push.  A block of one head (``head_of``
  None) is only scaled."""
  if head_of is None:
    return x if factor == 1.0 else (x * factor).astype(x.dtype)
  keep = jnp.where(head_of == h, jnp.float32(factor), jnp.float32(0.0))
  return (x.astype(jnp.float32) * keep).astype(x.dtype)


def _into_head(whole, part, head_of, h: int):
  """``whole`` with head ``h``'s lanes taken from ``part``: a product
  whose right operand is the tile (``P V``, ``dS K``) fills every lane,
  and only the head's own are its result."""
  if head_of is None or whole is None:
    return part
  return jnp.where(head_of == h, part, whole)


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, d: int,
                         tq: int, tk: int, causal: bool, scale: float,
                         unroll: bool):
  """One block a grid step, a head's ``[S, D]`` or the ``[S, 128]`` of
  the heads that share a lane tile: q, k, v, o and lse whole in VMEM, the
  ``[tq, tk]`` tiles of each head walked by two nested loops."""
  lanes = q_ref.shape[-1]
  num_q, num_k = q_ref.shape[-2] // tq, k_ref.shape[-2] // tk
  fold = _scale_folds(scale, q_ref.dtype)
  rel = _rel_pos(tq, tk, 0) if causal else None
  head_of = _head_lanes(lanes, d)

  def row_tile(i, carry):
    q0 = _tile_start(i, tq)
    q_rows = q_ref[_rows_at(q_ref, q0, tq)]                # [tq, lanes]
    out, sums = None, []
    for h in range(lanes // d):
      q = _of_head(q_rows, head_of, h, scale if fold else 1.0)

      def key_tile(j, carry, masked):
        m, l, acc = carry
        k0 = _tile_start(j, tk)
        kblk = k_ref[_rows_at(k_ref, k0, tk)]              # [tk, lanes]
        vblk = v_ref[_rows_at(v_ref, k0, tk)]
        s = _dot(q, kblk, _NT)                             # [tq, tk] fp32
        if not fold:
          s = s * scale
        if masked:
          s = jnp.where(rel >= k0 - q0, s, NEG_INF)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + _dot(p.astype(vblk.dtype), vblk, _NN)
        return new_m, l, acc

      state = (jnp.full((tq, 1), NEG_INF, jnp.float32),
               jnp.zeros((tq, 1), jnp.float32),
               jnp.zeros((tq, lanes), jnp.float32))
      full, live = _key_tiles(i, tq, tk, num_k, causal)
      state = _walk(0, full, functools.partial(key_tile, masked=False),
                    state, unroll)
      state = _walk(full, live, functools.partial(key_tile, masked=True),
                    state, unroll)
      m, l, acc = state

      l_safe = jnp.maximum(l, 1e-30)
      out = _into_head(out, acc / l_safe, head_of, h)
      sums.append((m, l_safe))
    o_ref[_rows_at(o_ref, q0, tq)] = out.astype(o_ref.dtype)
    # TPU tiling wants the last two dims (8, 128)-aligned, so the [tq]
    # logsumexp row is broadcast across 8 sublanes: lse is [B, H, 8, S].
    for h, (m, l_safe) in enumerate(sums):
      lse = (m + jnp.log(l_safe))[:, 0]
      lse_ref[0, h, :, pl.ds(q0, tq)] = jnp.broadcast_to(lse[None, :],
                                                         (8, tq))
    return carry

  _walk(0, num_q, row_tile, 0, unroll)


def _bwd_dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, dk_ref, dv_ref, *, d: int, tq: int,
                             tk: int, causal: bool, scale: float,
                             unroll: bool):
  """dK/dV of one block a grid step.  The score tile is built TRANSPOSED,
  ``[tk, tq]`` = k q^T: both accumulating products (p^T dO, ds^T q) are
  then plain ``[tk, tq] x [tq, lanes]`` matmuls with no transpose of a
  score tile, and lse / delta broadcast along sublanes straight from the
  ``[8, S]`` rows they are stored in."""
  lanes = k_ref.shape[-1]
  num_q, num_k = q_ref.shape[-2] // tq, k_ref.shape[-2] // tk
  fold = _scale_folds(scale, k_ref.dtype)
  rel = _rel_pos(tk, tq, 1) if causal else None
  head_of = _head_lanes(lanes, d)

  def key_tile(j, carry):
    k0 = _tile_start(j, tk)
    k_rows = k_ref[_rows_at(k_ref, k0, tk)]                # [tk, lanes]
    v_rows = v_ref[_rows_at(v_ref, k0, tk)]
    dk_out = dv_out = None
    for h in range(lanes // d):
      kblk = _of_head(k_rows, head_of, h, scale if fold else 1.0)
      vblk = _of_head(v_rows, head_of, h)

      def row_tile(i, carry, masked):
        dk, dv = carry
        q0 = _tile_start(i, tq)
        qblk = q_ref[_rows_at(q_ref, q0, tq)]              # [tq, lanes]
        doblk = do_ref[_rows_at(do_ref, q0, tq)]
        lse = lse_ref[0, h, 0:1, pl.ds(q0, tq)]            # [1, tq]
        delta = delta_ref[0, h, 0:1, pl.ds(q0, tq)]
        st = _dot(kblk, qblk, _NT)                         # [tk, tq] fp32
        if not fold:
          st = st * scale
        if masked:
          st = jnp.where(rel >= k0 - q0, st, NEG_INF)
        pt = jnp.exp(st - lse)
        dv = dv + _dot(pt.astype(doblk.dtype), doblk, _NN)
        dpt = _dot(vblk, doblk, _NT)
        dst = pt * (dpt - delta)
        dk = dk + _dot(dst.astype(qblk.dtype), qblk, _NN)
        return dk, dv

      acc = (jnp.zeros((tk, lanes), jnp.float32),
             jnp.zeros((tk, lanes), jnp.float32))
      lo, full = _row_tiles(j, tq, tk, num_q, causal)
      acc = _walk(lo, full, functools.partial(row_tile, masked=True), acc,
                  unroll)
      acc = _walk(full, num_q, functools.partial(row_tile, masked=False),
                  acc, unroll)
      dk, dv = acc
      # dk accumulated ds^T q with unscaled q: the s-scale goes in once
      # here.
      dk_out = _into_head(dk_out, dk * scale, head_of, h)
      dv_out = _into_head(dv_out, dv, head_of, h)
    dk_ref[_rows_at(dk_ref, k0, tk)] = dk_out.astype(dk_ref.dtype)
    dv_ref[_rows_at(dv_ref, k0, tk)] = dv_out.astype(dv_ref.dtype)
    return carry

  _walk(0, num_k, key_tile, 0, unroll)


def _bwd_dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, dq_ref, *, d: int, tq: int, tk: int,
                            causal: bool, scale: float, unroll: bool):
  """dQ of one block a grid step, tiles walked as the forward walks
  them."""
  lanes = q_ref.shape[-1]
  num_q, num_k = q_ref.shape[-2] // tq, k_ref.shape[-2] // tk
  fold = _scale_folds(scale, q_ref.dtype)
  rel = _rel_pos(tq, tk, 0) if causal else None
  head_of = _head_lanes(lanes, d)

  def row_tile(i, carry):
    q0 = _tile_start(i, tq)
    q_rows = q_ref[_rows_at(q_ref, q0, tq)]                # [tq, lanes]
    do_rows = do_ref[_rows_at(do_ref, q0, tq)]
    dq_out = None
    for h in range(lanes // d):
      lse = lse_ref[0, h, 0, pl.ds(q0, tq)][:, None]       # [tq, 1]
      delta = delta_ref[0, h, 0, pl.ds(q0, tq)][:, None]
      qblk = _of_head(q_rows, head_of, h, scale if fold else 1.0)
      doblk = _of_head(do_rows, head_of, h)

      def key_tile(j, dq, masked):
        k0 = _tile_start(j, tk)
        kblk = k_ref[_rows_at(k_ref, k0, tk)]              # [tk, lanes]
        vblk = v_ref[_rows_at(v_ref, k0, tk)]
        s = _dot(qblk, kblk, _NT)                          # [tq, tk] fp32
        if not fold:
          s = s * scale
        if masked:
          s = jnp.where(rel >= k0 - q0, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(doblk, vblk, _NT)
        ds = p * (dp - delta)
        return dq + _dot(ds.astype(kblk.dtype), kblk, _NN)

      dq = jnp.zeros((tq, lanes), jnp.float32)
      full, live = _key_tiles(i, tq, tk, num_k, causal)
      dq = _walk(0, full, functools.partial(key_tile, masked=False), dq,
                 unroll)
      dq = _walk(full, live, functools.partial(key_tile, masked=True), dq,
                 unroll)
      dq_out = _into_head(dq_out, dq * scale, head_of, h)
    dq_ref[_rows_at(dq_ref, q0, tq)] = dq_out.astype(dq_ref.dtype)
    return carry

  _walk(0, num_q, row_tile, 0, unroll)


def _resident_ok(S: int, Skv: int, D: int, itemsize: int) -> bool:
  return max(S, Skv) * D * itemsize <= _RESIDENT_MAX_BYTES


def _walk_of(S: int, Skv: int, D: int, itemsize: int, bq: int,
             bk: int) -> str:
  """How the kernels walk a head of this call: ``stream`` past the VMEM
  wall, else resident, ``unrolled`` or ``looped`` (``_UNROLL_PAIRS``)."""
  if not _resident_ok(S, Skv, D, itemsize):
    return "stream"
  tiles = (S // bq) * (Skv // bk)
  return ("unrolled" if S * Skv <= _UNROLL_PAIRS and tiles <= _UNROLL_TILES
          else "looped")


def _kv_clamp_idx(bq: int, bk: int, causal: bool):
  """[b, h, q-block, kv-block] index map for KV operands streamed in the
  innermost grid dim, clamped to the Q block's last live KV block under
  a causal mask: Mosaic skips the DMA when consecutive mapped indices
  coincide, so the fully-masked tail of a causal row costs neither
  bandwidth nor compute."""
  def idx(b, h, i, j):
    if causal:
      j = jnp.minimum(j, (((i + 1) * bq - 1) // bk))
    return (b, h, j, 0)
  return idx


def _q_clamp_idx(bq: int, bk: int, causal: bool, row: bool = False):
  """Streamed-Q counterpart for the dk/dv grid (Q blocks strictly above
  the KV block's diagonal are dead — clamp up to the first live block).
  `row=True` indexes the 8-sublane lse/delta tiles instead of [S, D]."""
  def idx(b, h, j, i):
    if causal:
      i = jnp.maximum(i, (j * bk) // bq)
    return (b, h, 0, i) if row else (b, h, i, 0)
  return idx


def _compiler_params(n_outer: int, interpret: bool):
  """Outer grid dims parallel, innermost (streamed/accumulated) dim
  sequential.  Interpret mode ignores TPU compiler params but rejects
  unknown ones on some versions — only pass them on real TPU."""
  if interpret:
    return None
  return pltpu.CompilerParams(
      dimension_semantics=("parallel",) * n_outer + ("arbitrary",))


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                       acc_ref, *, block_k: int, causal: bool,
                       scale: float, num_kv: int):
  bq = q_ref.shape[2]
  qi = pl.program_id(2)
  kj = pl.program_id(3)

  @pl.when(kj == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  # A KV block is live iff it intersects the causal triangle of this Q
  # block; masked blocks skip compute entirely (their DMA is already
  # elided by the clamped index map).
  live = (kj * block_k < (qi + 1) * bq) if causal else True

  @pl.when(live)
  def _compute():
    q = q_ref[0, 0]                                      # [BQ, D]
    kblk = k_ref[0, 0]                                   # [BK, D]
    vblk = v_ref[0, 0]
    s = _score_tile(q, kblk, qi * bq, kj * block_k, causal, scale)
    m_prev = m_ref[...][:, :1]                           # [BQ, 1]
    l_prev = l_ref[...][:, :1]
    new_m = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - new_m)
    corr = jnp.exp(m_prev - new_m)
    new_l = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(new_l, l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  @pl.when(kj == num_kv - 1)
  def _finalize():
    l_col = jnp.maximum(l_ref[...][:, :1], 1e-30)        # [BQ, 1]
    o_ref[0, 0] = (acc_ref[...] / l_col).astype(o_ref.dtype)
    # TPU tiling wants the last two dims (8, 128)-aligned, so the [BQ]
    # logsumexp row is broadcast across 8 sublanes: lse has shape
    # [B, H, 8, S].
    lse = m_ref[...][:, 0] + jnp.log(l_col[:, 0])
    lse_ref[0, 0] = jnp.broadcast_to(lse[None, :].astype(jnp.float32),
                                     (8, bq))


def _check_blocks(S, Skv, bq, bk):
  # Kernels grid by S // bq and Skv // bk: a non-dividing block would
  # silently drop the tail (wrong attention, no error) — refuse instead.
  if S % bq or Skv % bk:
    raise ValueError(
        f"block sizes ({bq}, {bk}) must divide the sequence lengths "
        f"(q={S}, kv={Skv})")


def _fwd(q, k, v, causal: bool, block_q: int, block_k: int):
  B, H, S, D = q.shape
  Skv = k.shape[2]
  bq = min(block_q, S)
  bk = min(block_k, Skv)
  _check_blocks(S, Skv, bq, bk)
  return _fwd_call(q, k, v, causal=causal, bq=bq, bk=bk,
                   walk=_walk_of(S, Skv, D, q.dtype.itemsize, bq, bk),
                   interpret=_interpret())


def _fwd_rows(q, k, v, d: int, causal: bool, bq: int, bk: int,
              fused: bool = False):
  """``_fwd`` over operands kept in rows, ``[B, S, H x d]``
  (:func:`flash_layout` says where): out in rows, lse ``[B, H, 8, S]``.
  ``fused``: q, k and v are ONE array, ``[B, S, 3 x H x d]``, the three
  column blocks of a fused projection's output."""
  S = q.shape[1]
  return _fwd_call(q, k, v, causal=causal, bq=bq, bk=bk,
                   walk=_walk_of(S, S, d, q.dtype.itemsize, bq, bk),
                   interpret=_interpret(), d=d, fused=fused)


def _qkv_tiles(width: int, fused: bool):
  """``(lane tiles of H x d, first tile of q, of k, of v)`` in operands
  kept in rows: each its own array, or the three column blocks of one."""
  n = width // (3 * _LANES) if fused else width // _LANES
  return (n, 0, n, 2 * n) if fused else (n, 0, 0, 0)


# Jitted so that the layers of a model share ONE trace and one Mosaic
# lowering of a kernel: the unrolled bodies are long, and traced a layer
# they added 7 s to the set-up of a 36-layer train step.  What the trace
# depends on beside its arguments (backend, the regime's limits, the head
# size of operands kept in rows) is read by the caller and passed in as
# static arguments.
@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "walk",
                                             "interpret", "d", "fused"))
def _fwd_call(q, k, v, *, causal: bool, bq: int, bk: int, walk: str,
              interpret: bool, d: Optional[int] = None,
              fused: bool = False):
  if walk != "stream":
    # A block a grid step; (bq, bk) is the tile its loops walk.
    if d is not None:
      # Operands in rows: a lane tile's heads a grid step.
      B, S, _ = q.shape
      n, *first = _qkv_tiles(q.shape[2], fused)
      grid, out_dims = (B, n), (B, S, n * _LANES)
      in_specs = [_tile_block(S, f) for f in first]
      out_specs = [_tile_block(S), _group_block(_LANES // d, S)]
      lse_dims = (B, n * _LANES // d, 8, S)
    else:
      # Head-major: one head a grid step.
      B, H, S, d = q.shape
      Skv = k.shape[2]
      grid, out_dims, lse_dims = (B, H), (B, H, S, d), (B, H, 8, S)
      in_specs = [_head_block(S, d), _head_block(Skv, d),
                  _head_block(Skv, d)]
      out_specs = [_head_block(S, d), _head_block(8, S)]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_resident, d=d, tq=bq, tk=bk,
                          causal=causal, scale=1.0 / math.sqrt(d),
                          unroll=walk == "unrolled"),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct(out_dims, q.dtype),
            jax.ShapeDtypeStruct(lse_dims, jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(q, k, v)
    return out, lse

  B, H, S, D = q.shape
  Skv = k.shape[2]
  scale = 1.0 / math.sqrt(D)
  num_kv = Skv // bk
  grid = (B, H, S // bq, num_kv)

  kv_idx = _kv_clamp_idx(bq, bk, causal)

  out, lse = pl.pallas_call(
      functools.partial(_fwd_kernel_stream, block_k=bk, causal=causal,
                        scale=scale, num_kv=num_kv),
      grid=grid,
      in_specs=[
          pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
          pl.BlockSpec((1, 1, bk, D), kv_idx),
          pl.BlockSpec((1, 1, bk, D), kv_idx),
      ],
      out_specs=[
          pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
          pl.BlockSpec((1, 1, 8, bq), lambda b, h, i, j: (b, h, 0, i)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
          jax.ShapeDtypeStruct((B, H, 8, S), jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((bq, 128), jnp.float32),            # running max
          pltpu.VMEM((bq, 128), jnp.float32),            # running denom
          pltpu.VMEM((bq, D), jnp.float32),              # output acc
      ],
      compiler_params=_compiler_params(3, interpret),
      interpret=interpret,
      name=FLASH_FWD,
  )(q, k, v)
  return out, lse


# -------------------------------------------------------------- backward --

def _bwd_dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                           causal: bool, scale: float, num_q: int):
  bk = k_ref.shape[2]
  ki = pl.program_id(2)
  qi = pl.program_id(3)

  @pl.when(qi == 0)
  def _init():
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

  live = ((qi + 1) * block_q > ki * bk) if causal else True

  @pl.when(live)
  def _compute():
    kblk = k_ref[0, 0]                                   # [BK, D]
    vblk = v_ref[0, 0]
    qblk = q_ref[0, 0]                                   # [BQ, D]
    doblk = do_ref[0, 0]
    lse = lse_ref[0, 0, 0]                               # [BQ]
    delta = delta_ref[0, 0, 0]
    s = _score_tile(qblk, kblk, qi * block_q, ki * bk, causal, scale)
    p = jnp.exp(s - lse[:, None])                        # [BQ, BK]
    dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
        p.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(doblk, vblk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])                       # [BQ, BK]
    dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
        ds.astype(qblk.dtype), qblk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  @pl.when(qi == num_q - 1)
  def _finalize():
    # dk accumulates ds @ q with unscaled q; fold the s-scale in once.
    dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_acc, *, block_k: int, causal: bool,
                          scale: float, num_kv: int):
  bq = q_ref.shape[2]
  qi = pl.program_id(2)
  kj = pl.program_id(3)

  @pl.when(kj == 0)
  def _init():
    dq_acc[...] = jnp.zeros_like(dq_acc)

  live = (kj * block_k < (qi + 1) * bq) if causal else True

  @pl.when(live)
  def _compute():
    qblk = q_ref[0, 0]
    doblk = do_ref[0, 0]
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    kblk = k_ref[0, 0]
    vblk = v_ref[0, 0]
    s = _score_tile(qblk, kblk, qi * bq, kj * block_k, causal, scale)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(doblk, vblk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
        ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  @pl.when(kj == num_kv - 1)
  def _finalize():
    dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _tile8(x):
  """Broadcast a [B, H, S] row across 8 sublanes -> [B, H, 8, S] (the
  TPU-tiled layout the backward kernels read lse/delta in)."""
  B, H, S = x.shape
  return jnp.broadcast_to(x[:, :, None, :], (B, H, 8, S)).copy()


def _bwd_kernels(q, k, v, dout, lse8, delta8, causal, block_q, block_k):
  """The two backward pallas calls with caller-supplied (lse, delta)
  tiles.  Shared by the plain flash vjp (per-call lse, delta from
  rowsum(dO*O) - dlse) and the ring-attention backward (GLOBAL lse over
  all ring blocks, delta from the merged output)."""
  B, H, S, D = q.shape
  Skv = k.shape[2]
  bq = min(block_q, S)
  bk = min(block_k, Skv)
  _check_blocks(S, Skv, bq, bk)
  return _bwd_call(q, k, v, dout, lse8, delta8, causal=causal, bq=bq, bk=bk,
                   walk=_walk_of(S, Skv, D, q.dtype.itemsize, bq, bk),
                   interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "walk",
                                             "interpret", "d", "fused"))
def _bwd_call(q, k, v, dout, lse8, delta8, *, causal: bool, bq: int,
              bk: int, walk: str, interpret: bool, d: Optional[int] = None,
              fused: bool = False):
  if walk != "stream":
    if d is not None:
      # q, k, v, dO in and dQ, dK, dV out in rows; lse8 / delta8 as ever.
      B, S, _ = q.shape
      n, *first = _qkv_tiles(q.shape[2], fused)
      rows, row8 = _tile_block(S), _group_block(_LANES // d, S)
      grid = (B, n)
      in_specs = [_tile_block(S, f) for f in first] + [rows, row8, row8]
      dq_spec = dk_spec = rows
      dq_dims = dk_dims = (B, S, n * _LANES)
    else:
      B, H, S, d = q.shape
      Skv = k.shape[2]
      grid = (B, H)
      in_specs = [_head_block(S, d), _head_block(Skv, d),
                  _head_block(Skv, d), _head_block(S, d),
                  _head_block(8, S), _head_block(8, S)]
      dq_spec, dk_spec = _head_block(S, d), _head_block(Skv, d)
      dq_dims, dk_dims = (B, H, S, d), (B, H, Skv, d)
    tiles = dict(d=d, tq=bq, tk=bk, causal=causal,
                 scale=1.0 / math.sqrt(d), unroll=walk == "unrolled")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_resident, **tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=[dk_spec, dk_spec],
        out_shape=[
            jax.ShapeDtypeStruct(dk_dims, q.dtype),
            jax.ShapeDtypeStruct(dk_dims, q.dtype),
        ],
        interpret=interpret,
        name=FLASH_DKV,
    )(q, k, v, dout, lse8, delta8)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_resident, **tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=dq_spec,
        out_shape=jax.ShapeDtypeStruct(dq_dims, q.dtype),
        interpret=interpret,
        name=FLASH_DQ,
    )(q, k, v, dout, lse8, delta8)
    return dq, dk, dv

  B, H, S, D = q.shape
  Skv = k.shape[2]
  scale = 1.0 / math.sqrt(D)
  num_q, num_kv = S // bq, Skv // bk

  # dk/dv: grid streams Q blocks innermost, accumulating into VMEM
  # scratch.
  q_idx = _q_clamp_idx(bq, bk, causal)
  row_idx = _q_clamp_idx(bq, bk, causal, row=True)

  dk, dv = pl.pallas_call(
      functools.partial(_bwd_dkv_kernel_stream, block_q=bq, causal=causal,
                        scale=scale, num_q=num_q),
      grid=(B, H, num_kv, num_q),
      in_specs=[
          pl.BlockSpec((1, 1, bq, D), q_idx),
          pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
          pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
          pl.BlockSpec((1, 1, bq, D), q_idx),
          pl.BlockSpec((1, 1, 8, bq), row_idx),
          pl.BlockSpec((1, 1, 8, bq), row_idx),
      ],
      out_specs=[
          pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
          pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((B, H, Skv, D), q.dtype),
          jax.ShapeDtypeStruct((B, H, Skv, D), q.dtype),
      ],
      scratch_shapes=[
          pltpu.VMEM((bk, D), jnp.float32),
          pltpu.VMEM((bk, D), jnp.float32),
      ],
      compiler_params=_compiler_params(3, interpret),
      interpret=interpret,
      name=FLASH_DKV,
  )(q, k, v, dout, lse8, delta8)

  # dq: grid streams KV blocks innermost (same layout as the forward).
  kv_idx = _kv_clamp_idx(bq, bk, causal)

  dq = pl.pallas_call(
      functools.partial(_bwd_dq_kernel_stream, block_k=bk, causal=causal,
                        scale=scale, num_kv=num_kv),
      grid=(B, H, num_q, num_kv),
      in_specs=[
          pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
          pl.BlockSpec((1, 1, bk, D), kv_idx),
          pl.BlockSpec((1, 1, bk, D), kv_idx),
          pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
          pl.BlockSpec((1, 1, 8, bq), lambda b, h, i, j: (b, h, 0, i)),
          pl.BlockSpec((1, 1, 8, bq), lambda b, h, i, j: (b, h, 0, i)),
      ],
      out_specs=pl.BlockSpec((1, 1, bq, D),
                             lambda b, h, i, j: (b, h, i, 0)),
      out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
      scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
      compiler_params=_compiler_params(3, interpret),
      interpret=interpret,
      name=FLASH_DQ,
  )(q, k, v, dout, lse8, delta8)
  return dq, dk, dv


def _bwd(causal, block_q, block_k, residuals, dout, dlse=None):
  q, k, v, out, lse = residuals
  # delta = rowsum(dO * O) — cheap elementwise, plain XLA.  An lse
  # cotangent folds in here: d lse_i/d s_ij = p_ij, so
  # ds = p*(dp - delta + dlse) == p*(dp - (delta - dlse)).
  delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                  axis=-1)                                 # [B, H, S]
  if dlse is not None:
    delta = delta - dlse.astype(jnp.float32)
  return _bwd_kernels(q, k, v, dout, lse, _tile8(delta), causal,
                      block_q, block_k)


# ------------------------------------------------------------ public API --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
  out, _ = _fwd(q, k, v, causal, block_q, block_k)
  return out


def _flash_fwd(q, k, v, causal, block_q, block_k):
  out, lse = _fwd(q, k, v, causal, block_q, block_k)
  # Tag the kernel outputs so a names-aware remat policy (models'
  # remat_policy="dots_flash") can SAVE them: jax.checkpoint cannot see
  # inside a custom_vjp, so under a plain `dots` policy the whole flash
  # forward would re-run in the backward.  With (out, lse) saved, the
  # backward's recompute of the forward kernel is dead code (q/k/v come
  # from saved projection dots) and DCE removes it.
  out = checkpoint_name(out, "flash_out")
  lse = checkpoint_name(lse, "flash_lse")
  return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, residuals, dout):
  return _bwd(causal, block_q, block_k, residuals, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fwd_rows_saved(q, k, v, d, causal, bq, bk, fused):
  """``_fwd_rows`` with its results tagged for a names-aware remat policy,
  under the same names as ``_flash_fwd``'s: one policy saves either."""
  out, lse = _fwd_rows(q, k, v, d, causal, bq, bk, fused)
  return (checkpoint_name(out, "flash_out"),
          checkpoint_name(lse, "flash_lse"))


def _rows_bwd(q, k, v, out, lse, dout, d, causal, bq, bk, fused):
  B, S, HD = out.shape
  # delta = rowsum(dO * O) a head, head-major like lse.  As a product with
  # the heads' 0/1 lane map and not a sum over a [.., H, d] view: XLA keeps
  # such a view position-minor and copied the float32 [B, S, H x d] whole
  # to get there.
  lane_of = (jnp.arange(HD)[None, :] // d
             == jnp.arange(HD // d)[:, None]).astype(jnp.float32)
  delta = jnp.einsum(
      "bsk,hk->bhs", dout.astype(jnp.float32) * out.astype(jnp.float32),
      lane_of, precision=jax.lax.Precision.HIGHEST)        # [B, H, S]
  return _bwd_call(q, k, v, dout, lse, _tile8(delta), causal=causal, bq=bq,
                   bk=bk, walk=_walk_of(S, S, d, q.dtype.itemsize, bq, bk),
                   interpret=_interpret(), d=d, fused=fused)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_rows(q, k, v, d, causal, bq, bk):
  """:func:`_flash` over operands kept in rows, ``[B, S, H x d]``."""
  return _fwd_rows(q, k, v, d, causal, bq, bk)[0]


def _flash_rows_fwd(q, k, v, d, causal, bq, bk):
  out, lse = _fwd_rows_saved(q, k, v, d, causal, bq, bk, False)
  return out, (q, k, v, out, lse)


def _flash_rows_bwd(d, causal, bq, bk, residuals, dout):
  return _rows_bwd(*residuals, dout, d, causal, bq, bk, False)


_flash_rows.defvjp(_flash_rows_fwd, _flash_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv(qkv, d, causal, bq, bk):
  """:func:`_flash_rows` with q, k and v read where a fused projection
  wrote them, the three column blocks of ``[B, S, 3 x H x d]``: a Mosaic
  call takes no slice fused into its operand, so three operands would be
  three copies."""
  return _fwd_rows(qkv, qkv, qkv, d, causal, bq, bk, True)[0]


def _flash_qkv_fwd(qkv, d, causal, bq, bk):
  out, lse = _fwd_rows_saved(qkv, qkv, qkv, d, causal, bq, bk, True)
  return out, (qkv, out, lse)


def _flash_qkv_bwd(d, causal, bq, bk, residuals, dout):
  qkv, out, lse = residuals
  return (jnp.concatenate(
      _rows_bwd(qkv, qkv, qkv, out, lse, dout, d, causal, bq, bk, True),
      axis=-1),)


_flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse(q, k, v, causal, block_q, block_k):
  out, lse8 = _fwd(q, k, v, causal, block_q, block_k)
  return out, lse8[:, :, 0, :]


def _flash_lse_fwd(q, k, v, causal, block_q, block_k):
  out, lse8 = _fwd(q, k, v, causal, block_q, block_k)
  # Same remat contract as _flash_fwd: tagged so dots_flash saves the
  # kernel outputs instead of re-running the forward under jax.checkpoint.
  out = checkpoint_name(out, "flash_out")
  lse8 = checkpoint_name(lse8, "flash_lse")
  return (out, lse8[:, :, 0, :]), (q, k, v, out, lse8)


def _flash_lse_bwd(causal, block_q, block_k, residuals, cts):
  dout, dlse = cts
  return _bwd(causal, block_q, block_k, residuals, dout, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q, k, v, causal: bool = True,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None):
  """Like :func:`flash_attention` but also returns the per-position
  log-sum-exp, fp32 ``[B, S, H]`` — the quantity needed to MERGE
  attention over KV chunks (ring attention / blockwise decoding):
  given per-chunk ``(o_c, lse_c)``, the combined output is
  ``sum_c o_c * exp(lse_c - logaddexp_c(lse_c))``.  The vjp accepts a
  cotangent for lse (folded into the kernel's delta term).

  The bundled ring attention performs this merge against the same
  ``_fwd``/``_bwd_kernels`` primitives directly in their [B, H, S, D]
  layout (saving per-step transposes and using the global-LSE backward);
  this wrapper is the layout-friendly public entry point for external
  composition, e.g. KV-chunked decoding."""
  B, S, H, D = q.shape
  bq, bk = _blocks(S, D, q.dtype.itemsize, block_q, block_k)
  qt = q.transpose(0, 2, 1, 3)
  kt = k.transpose(0, 2, 1, 3)
  vt = v.transpose(0, 2, 1, 3)
  out, lse = _flash_lse(qt, kt, vt, causal, bq, bk)
  return out.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1)


# Autotuned tile widths: {(S, d, itemsize): want}, loaded lazily from
# flash_block_table.json next to this module when present (format
# {"device": <device_kind>, "entries": {"S:d:itemsize": want}}).  None is
# shipped: the v5e sweep's choice at the train cell's shape is what
# `_heuristic_want` gives every resident shape up to S 1024.
# Entries override the heuristic for their exact shape ONLY
# when the file's device kind matches the current backend — widths
# tuned for one TPU generation must not silently apply to another (or
# to CPU test runs).  Loading is lazy because it consults
# jax.devices(), which must not run at import time.
_BLOCK_TABLE: Optional[dict] = None
_BLOCK_TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "flash_block_table.json")


def _ensure_block_table() -> dict:
  global _BLOCK_TABLE
  if _BLOCK_TABLE is not None:
    return _BLOCK_TABLE
  _BLOCK_TABLE = {}
  try:
    with open(_BLOCK_TABLE_PATH) as f:
      raw = json.load(f)
  except FileNotFoundError:
    # No table shipped: the heuristic stands.  A table that IS there
    # but malformed raises — it was written to be used.
    return _BLOCK_TABLE
  if raw["device"] == jax.devices()[0].device_kind:
    for key, want in raw["entries"].items():
      s_, d_, it_ = (int(x) for x in key.split(":"))
      _BLOCK_TABLE[(s_, d_, it_)] = int(want)
  return _BLOCK_TABLE


def set_block_want(S: int, d: int, itemsize: int, want: int) -> None:
  """Programmatic autotune-table entry."""
  _ensure_block_table()[(S, d, itemsize)] = int(want)


def _heuristic_want(S: int, d: int, itemsize: int) -> int:
  """The tile a shape the table lacks is walked in.  Resident regime: 256
  up to S 1024, whose heads are walked unrolled (the v5e sweep at the
  train cell's shape, module docstring); 512 beyond, where ``fori_loop``
  pays its chain a tile and the larger tile amortises it; streaming
  regime: 1024."""
  if S * d * itemsize > _RESIDENT_MAX_BYTES:
    return 1024
  return 256 if S <= 1024 else 512


def _default_block(S: int, want: int = 0, *, d: int,
                   itemsize: int = 2) -> int:
  """Largest block <= `want` that divides S (halving from `want`, floor
  8 to stay sublane-aligned); S itself when shorter than `want`;
  0 when NO such block divides S (e.g. S = 515) — callers must either
  raise or fall back to a non-kernel path, never truncate the grid.

  In the resident regime the block is the TILE a head is walked in, in
  the streaming regime the grid's block.  Default `want`: the autotuned
  table entry for (S, d, itemsize) when one exists, else
  :func:`_heuristic_want`.  `d` must match the head dim the kernel
  will run with so this agrees with `_resident_ok`'s dispatch."""
  if not want:
    want = _ensure_block_table().get((S, d, itemsize))
    if not want:
      want = _heuristic_want(S, d, itemsize)
  if S <= want:
    return S
  b = want
  while b > 8 and S % b:
    b //= 2
  return b if S % b == 0 else 0


def flash_blockable(S: int, *, d: int, itemsize: int = 2) -> bool:
  """Whether the flash kernels can tile sequence length S with the
  default block search (dispatchers use this to fall back to einsum
  formulations instead of raising).  `d` is required so blockability
  can never silently disagree with `_resident_ok`'s dispatch for the
  head dim actually in use."""
  return _default_block(S, d=d, itemsize=itemsize) > 0


def _mesh_shard_spec(B: int, H: int):
  """``(mesh, spec)`` for running the kernel per chip, or None.

  The SPMD partitioner cannot split a Mosaic custom call (jax refuses to
  lower one outside a manual region on a multi-device mesh), so on a
  built mesh of more than one device the kernel entry runs inside a
  ``shard_map``: batch over ``data`` and heads over ``model`` — the
  layout the models constrain q/k/v to — and replicated over every
  other axis.  A dim its axis does not divide stays whole on each chip.
  Inside an ambient manual region (ring / Ulysses / the smap engines)
  the caller already holds per-shard values: no wrap."""
  cluster = Env.get().cluster
  mesh = cluster.built_mesh if cluster is not None else None
  if mesh is None or mesh.size == 1 or ambient_manual_axes():
    return None
  sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
  b_axis = (constants.DATA_AXIS
            if B % sizes[constants.DATA_AXIS] == 0 else None)
  h_axis = (constants.MODEL_AXIS
            if H % sizes[constants.MODEL_AXIS] == 0 else None)
  return mesh, P(b_axis, None, h_axis, None)


def flash_layout(S: int, H: int, D: int, itemsize: int,
                 fused: bool = False) -> str:
  """The layout the kernels take a call's operands in, from its shapes
  alone (``H``: the heads ONE chip holds; ``fused``: q, k and v arrive as
  one projection's ``[B, S, 3 x H x D]``, not as ``[B, S, H, D]``
  arrays): ``"rows"``, ``[B, S, H x D]`` as the projections write and
  read them, where heads of ``D`` lanes pack whole 128-lane tiles (``D``
  128, 64, 32; ``H`` a multiple of ``128 // D``) and a head is resident
  in VMEM; ``"heads"``, ``[B, H, S, D]`` behind a transpose either way,
  elsewhere (the streaming kernels, an odd head count, ``D`` 80 or 16).
  A ``[.., S, 64]`` array pads its lanes to 128 in HBM and XLA keeps it
  position-minor, so every head-major operand of a Mosaic call cost a
  relayout copy: 13 a layer of the four-chip train step (PERF.md section
  6, PR 49).  Heads of 128 lanes pad nothing: head arrays of that width
  stay head-major (XLA lays a free-standing one out that way at no cost,
  and relaying it into rows read +48% on the chip), and rows are taken
  only from the fused projection, where no head array exists at all."""
  group = _LANES // D if D and _LANES % D == 0 else 0
  if (0 < group <= _TILE_HEADS and H % group == 0 and (fused or group > 1)
      and _resident_ok(S, S, D, itemsize)):
    return "rows"
  return "heads"


def _blocks(S: int, D: int, itemsize: int, block_q: Optional[int] = None,
            block_k: Optional[int] = None):
  bq = (min(block_q, S) if block_q else
        _default_block(S, d=D, itemsize=itemsize))
  bk = (min(block_k, S) if block_k else
        _default_block(S, d=D, itemsize=itemsize))
  if not bq or not bk or S % bq or S % bk:
    raise ValueError(f"block sizes ({bq}, {bk}) must divide seq len {S}")
  return bq, bk


def _chip_layout(sharded, S: int, H: int, D: int, itemsize: int,
                 fused: bool = False):
  """``(layout, heads a chip)`` of a call, recorded for a traced run
  (trace metadata ``train/flash_layout``: once a compile)."""
  if sharded is not None and sharded[1][2] is not None:
    H //= sharded[0].shape[sharded[1][2]]
  layout = flash_layout(S, H, D, itemsize, fused)
  trace_lib.get_tracer().metadata("train/flash_layout", {"layout": layout})
  return layout, H


def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
  """Flash attention over [B, S, H, D] inputs (models' layout).

  The scale 1/sqrt(D) is applied inside the kernel.  An explicitly
  passed block size must divide the sequence length; when omitted,
  :func:`_default_block` chooses it (256 up to S 1024: PERF.md section 6,
  PR 43).  While a head fits VMEM the block is the tile its loops walk,
  not a block of the grid.

  On a multi-device mesh each chip runs the kernel on its own
  batch/head shard (:func:`_mesh_shard_spec`).  Where
  :func:`flash_layout` says ``rows`` for a chip's heads, the kernels read
  q, k, v (and o, dO) and write o (and dQ, dK, dV) as ``[B, S, H x D]``,
  which ``[B, S, H, D]`` is without moving a byte: nothing is transposed.
  """
  B, S, H, D = q.shape
  bq, bk = _blocks(S, D, q.dtype.itemsize, block_q, block_k)
  sharded = _mesh_shard_spec(B, H)
  layout, _ = _chip_layout(sharded, S, H, D, q.dtype.itemsize)

  def per_shard(q, k, v):
    if layout == "rows":
      b, _, h, _ = q.shape
      out = _flash_rows(q.reshape(b, S, h * D), k.reshape(b, S, h * D),
                        v.reshape(b, S, h * D), D, causal, bq, bk)
      return out.reshape(b, S, h, D)
    # The head-major kernels use [B, H, S, D].
    out = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                 v.transpose(0, 2, 1, 3), causal, bq, bk)
    return out.transpose(0, 2, 1, 3)

  if sharded is None:
    return per_shard(q, k, v)
  mesh, spec = sharded
  return shard_map(per_shard, mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)(q, k, v)


def flash_attention_qkv(qkv, num_heads: int, causal: bool = True):
  """Self-attention straight from a fused projection: ``qkv`` ``[B, S,
  3 x H x D]`` (the column blocks q, k, v, each its heads side by side)
  -> ``[B, S, H x D]``, what the output projection reads.

  Where a chip holds every head and :func:`flash_layout` says ``rows``,
  the kernels read the three column blocks of the ONE array where the
  projection wrote them and no ``[B, S, H, D]`` or ``[B, H, S, D]`` array
  exists on the way: XLA keeps an array whose minor dimension is 64
  position-minor and relays it out for every Mosaic call.  Elsewhere
  (heads divided over the ``model`` axis, a layout of ``heads``) the
  three are cut as the models always cut them and go through
  :func:`flash_attention`."""
  B, S, W = qkv.shape
  H, D = num_heads, W // (3 * num_heads)
  sharded = _mesh_shard_spec(B, H)
  layout, chip_heads = _chip_layout(sharded, S, H, D, qkv.dtype.itemsize,
                                    fused=True)
  if layout != "rows" or chip_heads != H:
    # Heads ride the model axis (a column-parallel projection already
    # produced the sharded feature dim; this re-expresses it on heads).
    qkv = constrain(qkv.reshape(B, S, 3, H, D),
                    P(constants.DATA_AXIS, None, None, constants.MODEL_AXIS,
                      None))
    return flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                           causal=causal).reshape(B, S, H * D)

  bq, bk = _blocks(S, D, qkv.dtype.itemsize)
  per_shard = lambda qkv: _flash_qkv(qkv, D, causal, bq, bk)
  if sharded is None:
    return per_shard(qkv)
  mesh, spec = sharded
  spec = P(spec[0], None, None)
  return shard_map(per_shard, mesh, in_specs=(spec,), out_specs=spec)(qkv)
