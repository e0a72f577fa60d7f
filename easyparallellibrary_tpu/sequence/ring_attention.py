"""Ring attention — context parallelism over the ``seq`` mesh axis.

Green-field subsystem: the reference has NO sequence/context parallelism
(SURVEY §2.8/§5.7 — it scales parameters, not sequence length; its
nearest building block is the grouped send/recv AllToAll family,
csrc/communicators/tensorflow_nccl.h:186-301).

Blockwise attention with online softmax (Liu et al. ring attention):
the sequence dim is split into one block per ``seq``-axis device; each
ring step every query block attends to the KV block it currently holds,
then KV rotates one position around the ICI ring — compute on the
current block overlaps the transfer of the next.  Two implementations:

* **flash ring** (default, ``sequence.ring_impl="flash"``): shard_map
  over the seq axis, the Pallas flash kernel as the per-block compute,
  explicit ``lax.ppermute`` rotation, and a custom_vjp backward that
  RE-COMMUNICATES the KV blocks instead of saving them — per-device
  live memory stays O(S/n) in both passes, which is the point of ring
  attention.  (XLA cannot partition a pallas custom call, hence the
  shard_map.)

* **einsum ring** (``ring_impl="einsum"``, or automatically when
  ``sequence.block_size``/``num_blocks`` asks for finer-than-device
  blocking): global-array form — the rotate is ``jnp.roll`` along the
  seq-sharded block dim (lowered to collective-permute by GSPMD), each
  step wrapped in ``jax.checkpoint`` so backward rematerializes
  per-step scores.  Composes with any surrounding GSPMD program.

Causality is enforced block-wise in both: a query block fully attends
to earlier blocks, triangularly to its own, not at all to later ones —
fully-masked ring steps still rotate but contribute zeros (uniform SPMD
work).  The contiguous layout wastes ~2x on causal masks; setting
``sequence.ring_layout="zigzag"`` assigns half-chunks ``(i, 2n-1-i)``
to device i so every device carries an equal mix of early and late
positions and per-step work is balanced (``_zz_fwd_pass`` below).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.env import Env

NEG_INF = -1e30


from easyparallellibrary_tpu.utils.sharding import (  # noqa: E402
    constrain as _constrain, manual_axes as _manual_axes)


def _seq_axis_size() -> int:
  env = Env.get()
  if env.cluster is None or env.cluster._mesh is None:
    return 1
  return env.cluster.axis_size(constants.SEQ_AXIS)


def _block_spec() -> P:
  # [B, nb, s, H, D] with the block dim on the seq axis; head/feature
  # dims are UNCONSTRAINED so tensor-parallel head sharding survives.
  return P(constants.DATA_AXIS, constants.SEQ_AXIS,
           P.UNCONSTRAINED, P.UNCONSTRAINED, P.UNCONSTRAINED)


@functools.partial(jax.checkpoint, static_argnums=(5, 6),
                   prevent_cse=False)
def _ring_step(qb, kb, vb, acc, r, n, causal):
  """One ring step: blockwise attention + online-softmax accumulate.

  qb: [B, nb, s, H, D]; kb/vb hold block (i - r) mod n at row i.
  acc = (o, m, l): numerator [.., s, H, D], running max / denom [.., s, H].
  """
  o, m, l = acc
  scale = 1.0 / jnp.sqrt(qb.shape[-1]).astype(jnp.float32)
  scores = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kb).astype(jnp.float32)
  scores = scores * scale

  if causal:
    nb = qb.shape[1]
    s = qb.shape[2]
    block_idx = jnp.arange(nb)                   # query block i
    k_block = (block_idx - r) % n                # source block of current kv
    # Block-level relation: k_block > i → fully masked; == → triangular.
    fully_masked = (k_block > block_idx)[None, :, None, None, None]
    diagonal = (k_block == block_idx)[None, :, None, None, None]
    tri = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, None, None]
    mask = jnp.where(diagonal, tri, True) & ~fully_masked
    scores = jnp.where(mask, scores, NEG_INF)

  step_max = jnp.max(scores, axis=-1)                         # [b,n,h,q]
  new_m = jnp.maximum(m, step_max.transpose(0, 1, 3, 2))      # [b,n,q,h]
  correction = jnp.exp(m - new_m)
  probs = jnp.exp(scores - new_m.transpose(0, 1, 3, 2)[..., None])
  step_l = jnp.sum(probs, axis=-1).transpose(0, 1, 3, 2)      # [b,n,q,h]
  new_l = l * correction + step_l
  step_o = jnp.einsum("bnhqk,bnkhd->bnqhd", probs.astype(qb.dtype), vb)
  new_o = o * correction[..., None].astype(o.dtype) + step_o.astype(o.dtype)
  return new_o, new_m, new_l


# ----------------------------------------------------- flash ring path --
#
# The design-point implementation: shard_map over the seq axis, the
# Pallas flash kernel as the per-block compute, explicit ppermute KV
# rotation, and a custom_vjp backward that RE-COMMUNICATES the KV blocks
# instead of saving them — per-device live memory stays O(S/n) in both
# passes, which is the entire point of ring attention.  (The global-array
# einsum path below stays as the GSPMD-composable fallback: XLA cannot
# partition a pallas custom call, so the kernel path must be a shard_map.)
#
# Backward math: with the GLOBAL logsumexp L saved from the forward,
# every per-block backward is an ordinary flash backward against L —
# p = exp(s - L) is the globally-normalized probability block, so the
# standard ds = p * (dp - delta) with delta = rowsum(dO * O) is exact per
# block and dk/dv accumulate additively as their block rides the ring
# (they rotate WITH the block and arrive home after n steps).


def _rot(x, n):
  # Shared ring-step primitive with the chunked collective-matmuls
  # (communicators/overlap.py) — one ring plan, two consumers.
  from easyparallellibrary_tpu.communicators.overlap import ring_step
  return ring_step(x, constants.SEQ_AXIS, n)


# ---------------------------------------------------- block-compute impl --
#
# The shard_map ring's per-block attention is pluggable:
# ``sequence.ring_impl="flash"`` (default) uses the Pallas kernels;
# "dense" uses plain XLA einsums with the SAME (o, lse8) contract — the
# pallas-free fallback, and the fully-COMPILED measurement path for the
# layout benchmarks (pallas on CPU only runs in interpret mode, so
# interpret-free CPU evidence needs this).


def _use_dense_blocks() -> bool:
  return Env.get().config.sequence.ring_impl == "dense"


def _dense_scores(q, k, causal):
  """Scaled (and causally masked) fp32 score block — shared by the dense
  fwd and bwd so mask/scale semantics can never drift between them.
  Matmul operands stay in storage dtype with fp32 accumulation (the
  kernels' MXU recipe)."""
  scale = q.shape[-1] ** -0.5
  s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                 preferred_element_type=jnp.float32) * scale
  if causal:
    Sq, Sk = s.shape[-2], s.shape[-1]
    mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
    s = jnp.where(mask, s, NEG_INF)
  return s, scale


def _dense_block_fwd(q, k, v, causal):
  """XLA block attention with `_fwd`'s contract: ([B,H,S,D] in q.dtype,
  lse8 [B,H,8,S] fp32); softmax fp32."""
  s, _ = _dense_scores(q, k, causal)
  m = jnp.max(s, axis=-1)
  p = jnp.exp(s - m[..., None])
  l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
  o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                 preferred_element_type=jnp.float32) / l[..., None]
  lse = m + jnp.log(l)
  lse8 = jnp.broadcast_to(lse[:, :, None, :],
                          lse.shape[:2] + (8,) + lse.shape[-1:])
  return o.astype(q.dtype), lse8


def _dense_block_bwd(q, k, v, dout, lse8, delta8, causal):
  """XLA twin of `_bwd_kernels`: block backward against the GLOBAL
  logsumexp/delta (p = exp(s - L) is globally normalized, so dk/dv
  accumulate additively across ring steps).  Matmul operands stay in
  storage dtype with fp32 accumulation — full-fp32 matmuls are ~4x
  slower on the MXU (measured note in kernels/flash_attention.py)."""
  lse = lse8[:, :, 0, :]
  delta = delta8[:, :, 0, :]
  s, scale = _dense_scores(q, k, causal)
  p = jnp.exp(s - lse[..., None])                       # masked -> 0
  pc = p.astype(dout.dtype)
  dv = jnp.einsum("bhqk,bhqd->bhkd", pc, dout,
                  preferred_element_type=jnp.float32)
  dp = jnp.einsum("bhqd,bhkd->bhqk", dout, v,
                  preferred_element_type=jnp.float32)
  ds = (p * (dp - delta[..., None])).astype(q.dtype)
  dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k,
                  preferred_element_type=jnp.float32) * scale
  dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q,
                  preferred_element_type=jnp.float32) * scale
  return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _block_fwd(q, k, v, causal, bq, bk):
  if _use_dense_blocks():
    return _dense_block_fwd(q, k, v, causal)
  from easyparallellibrary_tpu.kernels.flash_attention import _fwd
  return _fwd(q, k, v, causal, bq, bk)


def _block_bwd(q, k, v, dout, lse8, delta8, causal, bq, bk):
  if _use_dense_blocks():
    return _dense_block_bwd(q, k, v, dout, lse8, delta8, causal)
  from easyparallellibrary_tpu.kernels.flash_attention import _bwd_kernels
  return _bwd_kernels(q, k, v, dout, lse8, delta8, causal, bq, bk)


def _ring_fwd_pass(n, causal, q, k0, v0):
  """Per-device ring forward in kernel layout [B, H, s, D].  Returns the
  merged (O fp32, L fp32 [B, H, s])."""
  from easyparallellibrary_tpu.kernels.flash_attention import (
      _default_block)
  s = q.shape[2]
  bq = bk = _default_block(s, d=q.shape[3],
                           itemsize=q.dtype.itemsize)
  idx = jax.lax.axis_index(constants.SEQ_AXIS) if n > 1 else 0
  O = jnp.zeros(q.shape, jnp.float32)
  L = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
  k_cur, v_cur = k0, v0
  for r in range(n):
    o_r, lse8 = _block_fwd(q, k_cur, v_cur, causal and r == 0,
                           bq, bk)
    lse_r = lse8[:, :, 0, :]
    if causal and r > 0:
      # Device idx holds KV block (idx - r) mod n at step r: wrapped
      # blocks (idx < r) are entirely in the future — masked out.
      masked = idx < r
      lse_r = jnp.where(masked, NEG_INF, lse_r)
      o_r = jnp.where(masked, jnp.zeros_like(o_r), o_r)
    L_new = jnp.logaddexp(L, lse_r)
    O = (O * jnp.exp(L - L_new)[..., None]
         + o_r.astype(jnp.float32) * jnp.exp(lse_r - L_new)[..., None])
    L = L_new
    if r != n - 1:
      k_cur = _rot(k_cur, n)
      v_cur = _rot(v_cur, n)
  return O, L


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ring_local(n, causal, q, k0, v0):
  O, _ = _ring_fwd_pass(n, causal, q, k0, v0)
  return O.astype(q.dtype)


def _ring_local_fwd(n, causal, q, k0, v0):
  from jax.ad_checkpoint import checkpoint_name
  O, L = _ring_fwd_pass(n, causal, q, k0, v0)
  out = O.astype(q.dtype)
  # Same remat contract as the plain flash kernel: tag the residuals so
  # the models' dots_flash policy SAVES them — without this, a
  # jax.checkpoint around the layer would re-run the entire ring forward
  # (n kernels + n-1 ppermutes) during the backward.
  out = checkpoint_name(out, "flash_out")
  L = checkpoint_name(L, "flash_lse")
  return out, (q, k0, v0, out, L)


def _ring_local_bwd(n, causal, residuals, dO):
  from easyparallellibrary_tpu.kernels.flash_attention import (
      _default_block, _tile8)
  q, k0, v0, O, L = residuals
  s = q.shape[2]
  bq = bk = _default_block(s, d=q.shape[3],
                           itemsize=q.dtype.itemsize)
  idx = jax.lax.axis_index(constants.SEQ_AXIS) if n > 1 else 0
  dO = dO.astype(q.dtype)
  delta = jnp.sum(dO.astype(jnp.float32) * O.astype(jnp.float32), axis=-1)
  L8, delta8 = _tile8(L), _tile8(delta)
  dq = jnp.zeros(q.shape, jnp.float32)
  k_cur, v_cur = k0, v0
  dk_cur = jnp.zeros(k0.shape, jnp.float32)
  dv_cur = jnp.zeros(v0.shape, jnp.float32)
  for r in range(n):
    dq_r, dk_r, dv_r = _block_bwd(q, k_cur, v_cur, dO, L8, delta8,
                                  causal and r == 0, bq, bk)
    if causal and r > 0:
      masked = idx < r
      dq_r = jnp.where(masked, jnp.zeros_like(dq_r), dq_r)
      dk_r = jnp.where(masked, jnp.zeros_like(dk_r), dk_r)
      dv_r = jnp.where(masked, jnp.zeros_like(dv_r), dv_r)
    dq = dq + dq_r.astype(jnp.float32)
    dk_cur = dk_cur + dk_r.astype(jnp.float32)
    dv_cur = dv_cur + dv_r.astype(jnp.float32)
    # Rotate grads WITH their block every step (n rotations total) so
    # each dk/dv arrives back at its block's home device; k/v themselves
    # are not read after the last step.
    if r != n - 1:
      k_cur, v_cur = _rot(k_cur, n), _rot(v_cur, n)
    dk_cur, dv_cur = _rot(dk_cur, n), _rot(dv_cur, n)
  return dq.astype(q.dtype), dk_cur.astype(k0.dtype), dv_cur.astype(v0.dtype)


_ring_local.defvjp(_ring_local_fwd, _ring_local_bwd)


# ------------------------------------------------- zigzag causal layout --
#
# The contiguous layout wastes ~2x on causal masks: at ring step r the
# first r devices hold wholly-future KV and contribute zeros (but SPMD
# runs their kernels anyway).  The zigzag layout assigns each device TWO
# half-chunks — chunk c and chunk 2n-1-c of 2n global chunks — so every
# device holds one "early" and one "late" piece and the causal work per
# step is uniform: one always-live half-pair (late queries vs early KV)
# plus one selected half-pair ((early q, early k) when the visiting block
# is older, (late q, late k) when it is newer).  Total causal compute
# drops from n full-block kernels to 3/4 + (n-1)/2 half-block work ≈ half.
#
# The layout exchange happens INSIDE the shard_map on entry/exit (two
# ppermutes each way, O(S·D) — negligible next to the O(S²/n·D) kernel
# work it halves) and is plain traced code, so autodiff transposes the
# ppermutes for the backward automatically; only the ring itself is a
# custom_vjp.


def _halves(x):
  h = x.shape[2] // 2
  return x[:, :, :h], x[:, :, h:]


def _zig_entry(x, n):
  """Contiguous shard (chunks 2i, 2i+1) -> zigzag (chunks i, 2n-1-i)."""
  idx = jax.lax.axis_index(constants.SEQ_AXIS)
  a, b = _halves(x)
  evens = jax.lax.ppermute(
      a, constants.SEQ_AXIS,
      [(i, 2 * i if 2 * i < n else 2 * n - 1 - 2 * i) for i in range(n)])
  odds = jax.lax.ppermute(
      b, constants.SEQ_AXIS,
      [(i, 2 * i + 1 if 2 * i + 1 < n else 2 * n - 2 - 2 * i)
       for i in range(n)])
  even_dev = (idx % 2 == 0)
  new_a = jnp.where(even_dev, evens, odds)   # chunk idx (parity == idx's)
  new_b = jnp.where(even_dev, odds, evens)   # chunk 2n-1-idx
  return jnp.concatenate([new_a, new_b], axis=2)


def _zig_exit(x, n):
  """Inverse of :func:`_zig_entry`."""
  idx = jax.lax.axis_index(constants.SEQ_AXIS)
  a, b = _halves(x)
  even_dev = (idx % 2 == 0)
  even_chunk = jnp.where(even_dev, a, b)     # chunk idx or 2n-1-idx, even
  odd_chunk = jnp.where(even_dev, b, a)
  evens = jax.lax.ppermute(
      even_chunk, constants.SEQ_AXIS,
      [(i, (i if i % 2 == 0 else 2 * n - 1 - i) // 2) for i in range(n)])
  odds = jax.lax.ppermute(
      odd_chunk, constants.SEQ_AXIS,
      [(i, ((2 * n - 1 - i) if i % 2 == 0 else i) // 2) for i in range(n)])
  return jnp.concatenate([evens, odds], axis=2)


def _merge(o1, l1, o2, l2):
  """LSE-merge two (output, logsumexp) contributions (fp32)."""
  l = jnp.logaddexp(l1, l2)
  o = (o1 * jnp.exp(l1 - l)[..., None] + o2 * jnp.exp(l2 - l)[..., None])
  return o, l


def _zz_fwd_pass(n, q, k0, v0):
  """Zigzag causal ring forward ([B, H, s, D] locals, s = 2 half-chunks).
  Returns merged (O fp32, L fp32)."""
  from easyparallellibrary_tpu.kernels.flash_attention import (
      _default_block)
  half = q.shape[2] // 2
  bq = bk = _default_block(half, d=q.shape[3],
                           itemsize=q.dtype.itemsize)
  idx = jax.lax.axis_index(constants.SEQ_AXIS)
  qa, qb = _halves(q)

  def fwd_half(qh, kh, vh, causal):
    o, lse8 = _block_fwd(qh, kh, vh, causal, bq, bk)
    return o.astype(jnp.float32), lse8[:, :, 0, :]

  O = jnp.zeros(q.shape, jnp.float32)
  L = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
  k_cur, v_cur = k0, v0
  for r in range(n):
    ka, kb = _halves(k_cur)
    va, vb = _halves(v_cur)
    if r == 0:
      o_a, l_a = fwd_half(qa, ka, va, True)          # diag (early, early)
      o1, l1 = fwd_half(qb, ka, va, False)           # late q vs early k
      o2, l2 = fwd_half(qb, kb, vb, True)            # diag (late, late)
      o_b, l_b = _merge(o1, l1, o2, l2)
    else:
      # Visiting block j = (idx - r) mod n.  cond: j < idx (no wrap) —
      # then (qa, ka) is live (early q sees older early k); wrapped
      # (j > idx) makes (qb, kb) live instead (late q sees older late k).
      cond = idx >= r
      q_sel = jnp.where(cond, qa, qb)
      k_sel = jnp.where(cond, ka, kb)
      v_sel = jnp.where(cond, va, vb)
      o_aw, l_aw = fwd_half(qb, ka, va, False)       # always live
      o_sl, l_sl = fwd_half(q_sel, k_sel, v_sel, False)
      o_a = jnp.where(cond, o_sl, 0.0)
      l_a = jnp.where(cond, l_sl, NEG_INF)
      o_b, l_b = _merge(o_aw, l_aw,
                        jnp.where(cond, 0.0, o_sl),
                        jnp.where(cond, NEG_INF, l_sl))
    o_r = jnp.concatenate([o_a, o_b], axis=2)
    lse_r = jnp.concatenate([l_a, l_b], axis=2)
    O, L = _merge(O, L, o_r, lse_r)
    if r != n - 1:
      k_cur, v_cur = _rot(k_cur, n), _rot(v_cur, n)
  return O, L


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_local_zz(n, q, k0, v0):
  O, _ = _zz_fwd_pass(n, q, k0, v0)
  return O.astype(q.dtype)


def _ring_local_zz_fwd(n, q, k0, v0):
  from jax.ad_checkpoint import checkpoint_name
  O, L = _zz_fwd_pass(n, q, k0, v0)
  out = checkpoint_name(O.astype(q.dtype), "flash_out")
  L = checkpoint_name(L, "flash_lse")
  return out, (q, k0, v0, out, L)


def _ring_local_zz_bwd(n, residuals, dO):
  """Recommunicating zigzag backward: same half-pair structure as the
  forward, with each half-pair running the flash bwd kernels against the
  GLOBAL per-half logsumexp, and dk/dv halves accumulating as their
  block rides the ring home."""
  from easyparallellibrary_tpu.kernels.flash_attention import (
      _default_block, _tile8)
  q, k0, v0, O, L = residuals
  half = q.shape[2] // 2
  bq = bk = _default_block(half, d=q.shape[3],
                           itemsize=q.dtype.itemsize)
  idx = jax.lax.axis_index(constants.SEQ_AXIS)
  dO = dO.astype(q.dtype)
  delta = jnp.sum(dO.astype(jnp.float32) * O.astype(jnp.float32), axis=-1)
  qa, qb = _halves(q)
  dOa, dOb = _halves(dO)
  La, Lb = L[:, :, :half], L[:, :, half:]
  da, db = delta[:, :, :half], delta[:, :, half:]
  La8, Lb8, da8, db8 = _tile8(La), _tile8(Lb), _tile8(da), _tile8(db)

  dqa = jnp.zeros(qa.shape, jnp.float32)
  dqb = jnp.zeros(qb.shape, jnp.float32)
  k_cur, v_cur = k0, v0
  dk_cur = jnp.zeros(k0.shape, jnp.float32)
  dv_cur = jnp.zeros(v0.shape, jnp.float32)

  def bwd_half(qh, kh, vh, dOh, L8, d8, causal):
    return _block_bwd(qh, kh, vh, dOh, L8, d8, causal, bq, bk)

  for r in range(n):
    ka, kb = _halves(k_cur)
    va, vb = _halves(v_cur)
    dka = jnp.zeros(ka.shape, jnp.float32)
    dkb = jnp.zeros(kb.shape, jnp.float32)
    dva = jnp.zeros(va.shape, jnp.float32)
    dvb = jnp.zeros(vb.shape, jnp.float32)
    if r == 0:
      g = bwd_half(qa, ka, va, dOa, La8, da8, True)
      dqa += g[0].astype(jnp.float32)
      dka += g[1].astype(jnp.float32)
      dva += g[2].astype(jnp.float32)
      g = bwd_half(qb, ka, va, dOb, Lb8, db8, False)
      dqb += g[0].astype(jnp.float32)
      dka += g[1].astype(jnp.float32)
      dva += g[2].astype(jnp.float32)
      g = bwd_half(qb, kb, vb, dOb, Lb8, db8, True)
      dqb += g[0].astype(jnp.float32)
      dkb += g[1].astype(jnp.float32)
      dvb += g[2].astype(jnp.float32)
    else:
      cond = idx >= r
      g = bwd_half(qb, ka, va, dOb, Lb8, db8, False)     # always live
      dqb += g[0].astype(jnp.float32)
      dka += g[1].astype(jnp.float32)
      dva += g[2].astype(jnp.float32)
      q_sel = jnp.where(cond, qa, qb)
      k_sel = jnp.where(cond, ka, kb)
      v_sel = jnp.where(cond, va, vb)
      dO_sel = jnp.where(cond, dOa, dOb)
      L_sel = jnp.where(cond, La8, Lb8)
      d_sel = jnp.where(cond, da8, db8)
      gq, gk, gv = bwd_half(q_sel, k_sel, v_sel, dO_sel, L_sel, d_sel,
                            False)
      dqa += jnp.where(cond, gq, 0.0).astype(jnp.float32)
      dqb += jnp.where(cond, 0.0, gq).astype(jnp.float32)
      dka += jnp.where(cond, gk, 0.0).astype(jnp.float32)
      dkb += jnp.where(cond, 0.0, gk).astype(jnp.float32)
      dva += jnp.where(cond, gv, 0.0).astype(jnp.float32)
      dvb += jnp.where(cond, 0.0, gv).astype(jnp.float32)
    dk_cur = dk_cur + jnp.concatenate([dka, dkb], axis=2)
    dv_cur = dv_cur + jnp.concatenate([dva, dvb], axis=2)
    if r != n - 1:
      k_cur, v_cur = _rot(k_cur, n), _rot(v_cur, n)
    dk_cur, dv_cur = _rot(dk_cur, n), _rot(dv_cur, n)
  dq = jnp.concatenate([dqa, dqb], axis=2)
  return (dq.astype(q.dtype), dk_cur.astype(k0.dtype),
          dv_cur.astype(v0.dtype))


_ring_local_zz.defvjp(_ring_local_zz_fwd, _ring_local_zz_bwd)


def _ring_manual(q, k, v, causal: bool):
  """Per-device ring body for callers ALREADY inside a shard_map region
  that is manual over the seq axis (the smap pipeline engines' stage
  programs, models/gpt.py:make_gpt_smap_grad_fn): q/k/v arrive
  seq-LOCAL ``[B_loc, s, H, D]`` and the ring's ppermutes execute
  directly in the ambient region — no nested shard_map.

  Deadlock-safe by the engines' collective-safety invariant
  (parallel/pipeline_smap.py module docstring): seq peers share a stage
  index, hence identical branch predicates, so every device in a
  seq-axis channel reaches each collective together.  (The round-4
  hazard was a NESTED shard_map, whose lowered channels span all
  devices regardless of the outer grouping.)  Requires ring_impl
  "flash"/"dense" — the einsum ring is a global-array GSPMD program and
  cannot run on local shards.

  TP caveat: under tensor parallelism the head dim rides the AUTO model
  axis, and XLA cannot partition a pallas custom call over an auto
  axis — with ring_impl="flash" GSPMD will all-gather the heads around
  each block kernel.  Use ring_impl="dense" for TP x ring x smap (the
  XLA block einsums partition cleanly), or keep flash when TP is off.
  """
  env = Env.get()
  n = env.cluster.axis_size(constants.SEQ_AXIS)
  seq_cfg = env.config.sequence
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_blockable)
  s_loc, D = q.shape[1], q.shape[3]
  if seq_cfg.ring_impl not in ("flash", "dense"):
    raise ValueError(
        f"sequence.ring_impl={seq_cfg.ring_impl!r} cannot run inside a "
        "seq-manual region (the einsum ring is a global-array GSPMD "
        "program); use ring_impl='flash' or 'dense' with the smap "
        "pipeline engine")
  dense = _use_dense_blocks()
  zigzag = (seq_cfg.ring_layout == "zigzag" and causal and n > 1
            and s_loc % 2 == 0
            and (dense or flash_blockable(s_loc // 2, d=D,
                                          itemsize=q.dtype.itemsize)))
  if not dense and not zigzag and not flash_blockable(
      s_loc, d=D, itemsize=q.dtype.itemsize):
    raise ValueError(
        f"per-device sequence block {s_loc} (d={D}) has no flash "
        "tiling; set sequence.ring_impl='dense' for the XLA block path "
        "inside the smap engine")
  qt = q.transpose(0, 2, 1, 3)
  kt = k.transpose(0, 2, 1, 3)
  vt = v.transpose(0, 2, 1, 3)
  if zigzag:
    qt, kt, vt = (_zig_entry(x, n) for x in (qt, kt, vt))
    out = _ring_local_zz(n, qt, kt, vt)
    out = _zig_exit(out, n)
  else:
    out = _ring_local(n, causal, qt, kt, vt)
  return out.transpose(0, 2, 1, 3)


def _ring_flash(q, k, v, causal: bool):
  env = Env.get()
  mesh = env.cluster._mesh
  n = env.cluster.axis_size(constants.SEQ_AXIS)
  B, S, H, D = q.shape
  # Zigzag only helps (and is only defined for) the causal case; needs
  # an even per-device split into two half-chunks the kernels can tile.
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_blockable)
  zigzag = (env.config.sequence.ring_layout == "zigzag" and causal
            and n > 1 and (S // n) % 2 == 0
            and (_use_dense_blocks()
                 or flash_blockable(S // n // 2, d=D,
                                    itemsize=q.dtype.itemsize)))

  def local(q_l, k_l, v_l):
    qt = q_l.transpose(0, 2, 1, 3)
    kt = k_l.transpose(0, 2, 1, 3)
    vt = v_l.transpose(0, 2, 1, 3)
    if zigzag:
      qt, kt, vt = (_zig_entry(x, n) for x in (qt, kt, vt))
      out = _ring_local_zz(n, qt, kt, vt)
      out = _zig_exit(out, n)
    else:
      out = _ring_local(n, causal, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

  # Inside a manual region that is NOT manual over seq, the ring cannot
  # run: nesting a shard_map compiles (abstract-mesh shard_map over the
  # seq axis works), but the NESTED map's collectives get lowered
  # channels spanning ALL devices, so when the region's real `lax.cond`
  # branches diverge across stage groups (ramp ticks) half the devices
  # never reach the collective and the program deadlocks (observed as an
  # XLA rendezvous termination).  The supported in-region path is the
  # seq-manual engine (handled in ring_attention -> _ring_manual, where
  # the ppermutes ride the AMBIENT region and channels stay seq-local).
  outer_manual = _manual_axes()
  if outer_manual:
    raise ValueError(
        "ring attention cannot nest inside a manual shard_map region "
        f"without the seq axis (manual axes {sorted(outer_manual)}): a "
        "nested map's collective channels span all devices and deadlock "
        "under divergent branches.  Make the region manual over "
        f"{constants.SEQ_AXIS!r} too (the smap engines do this when "
        "attn_impl='ring'), or use the vmapped pipeline engines "
        "(pipeline.engine=''), or attn_impl='pallas_flash'/'xla'.")

  # Batch on data, sequence on seq, heads on model (survives TP head
  # sharding); stage/expert axes replicated.
  from easyparallellibrary_tpu.sequence._util import axis_if_divisible
  bax = axis_if_divisible(B, mesh, constants.DATA_AXIS)
  hax = axis_if_divisible(H, mesh, constants.MODEL_AXIS)
  spec = P(bax, constants.SEQ_AXIS, hax, None)
  from easyparallellibrary_tpu.utils.compat import shard_map
  return shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                   out_specs=spec, check=False)(q, k, v)


def ring_attention(q, k, v, causal: bool = True,
                   num_blocks: Optional[int] = None):
  """Blockwise ring attention; q, k, v: [B, S, H, D] (seq-sharded under
  GSPMD).  Returns [B, S, H, D].

  With an active ``seq`` mesh axis (and no explicit ``num_blocks``
  override), dispatches to the shard_map + Pallas-flash ring
  (``sequence.ring_impl="flash"``, the default); set
  ``sequence.ring_impl="einsum"`` or pass ``num_blocks`` for the
  global-array einsum formulation (GSPMD-composable, e.g. finer
  blocking via ``sequence.block_size``).  Falls back to one block
  (= standard blockwise attention) when no seq axis is active."""
  B, S, H, D = q.shape
  # Inside a seq-manual shard_map region (the smap pipeline engines) the
  # arrays are already per-device shards: run the ring body directly in
  # the ambient region (see _ring_manual).
  if constants.SEQ_AXIS in _manual_axes():
    return _ring_manual(q, k, v, causal)
  axis = max(_seq_axis_size(), 1)
  seq_cfg = Env.get().config.sequence
  if (axis > 1 and num_blocks is None
      and seq_cfg.ring_impl in ("flash", "dense")
      and not seq_cfg.block_size):  # finer blocking → einsum path
    if S % axis:
      raise ValueError(f"sequence length {S} not divisible by "
                       f"{axis} ring devices")
    from easyparallellibrary_tpu.kernels.flash_attention import (
        flash_blockable)
    if seq_cfg.ring_impl == "dense" or flash_blockable(
        S // axis, d=D, itemsize=q.dtype.itemsize):
      return _ring_flash(q, k, v, causal)
    # Per-device block length the kernels can't tile (no power-of-two
    # divisor <= 512): fall through to the einsum formulation rather
    # than raise — it has no blocking constraint.
  if num_blocks is None:
    n = axis
    # Finer blocking than one block per device when sequence.block_size
    # asks for it (more, smaller, blocks rotate through the same ring).
    block_size = Env.get().config.sequence.block_size
    if block_size and S > block_size:
      finer = S // block_size
      # Must divide S and be a multiple of the seq axis size.
      if S % finer == 0 and finer % axis == 0:
        n = max(n, finer)
  else:
    n = num_blocks
  if S % n != 0:
    raise ValueError(f"sequence length {S} not divisible by "
                     f"{n} ring blocks")
  s = S // n

  def block(x):
    return _constrain(x.reshape(B, n, s, H, D), _block_spec())

  qb, kb, vb = block(q), block(k), block(v)
  o = jnp.zeros((B, n, s, H, D), jnp.float32)
  m = jnp.full((B, n, s, H), NEG_INF, jnp.float32)
  l = jnp.zeros((B, n, s, H), jnp.float32)

  for r in range(n):
    o, m, l = _ring_step(qb, kb, vb, (o, m, l), r, n, causal)
    if r != n - 1:
      # Rotate KV blocks around the ring (collective-permute on the
      # seq-sharded dim).
      kb = _constrain(jnp.roll(kb, shift=1, axis=1), _block_spec())
      vb = _constrain(jnp.roll(vb, shift=1, axis=1), _block_spec())

  out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
  return _constrain(out, _block_spec()).reshape(B, S, H, D)
