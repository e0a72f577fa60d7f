"""Gated delta rule over a chunk — Pallas TPU kernel + the ``lax.scan``
reference.

One linear-attention layer's step of the serving engine's fused call
(``models/gigachat.py`` ``GatedDeltaNet``; Yang, Kautz, Hatamizadeh, "Gated
Delta Networks", 2024): every slot advances the state of each of its value
heads, a float32 MATRIX ``S`` ``[dk, dv]``, by the ``num_valid`` positions
of its chunk,

    (q, k, v)_t <- silu(sum_j taps[j] x_{t - K + 1 + j})     the convolution
    q_t <- l2norm(q_t) / sqrt(dk),   k_t <- l2norm(k_t)
    S <- exp(g_t) S;   u_t = beta_t (v_t - S^T k_t);   S <- S + k_t u_t^T
    o_t = S^T q_t

value head ``h`` reading key head ``h // (Hv / Hk)``, all in float32.
Contract, shared by the lowerings:

* ``state`` float32 ``[B, Hv, dk, dv]`` — the carried state, VALUE-HEAD
  major: a head's ``[dk, dv]`` is one run of tiles; ``window`` ``[B, K - 1,
  W]`` in the compute dtype, the convolution's last inputs (``W = 2 Hk dk +
  Hv dv``); ``x`` ``[B, C, W]`` in the compute dtype, the chunk's inputs of
  the convolution as the projection leaves them: the queries' columns, the
  keys', the values', heads major within each; ``taps`` float32 ``[K, W]``,
  tap ``K - 1`` on the current position (depthwise, causal, no bias);
  ``g`` float32 ``[B, C, Hv]``, the log of the decay (at most 0); ``beta``
  float32 ``[B, C, Hv]``; ``num_valid`` int32 ``[B]``; ``reset`` bool
  ``[B]``.  The convolution stands INSIDE the contract: left to XLA it is a
  pass over ``[B, K - 1 + C, W]`` a layer (16,384 channels at 128 slots x
  32 positions: ~4 M cycles a layer in the step compiled for a described
  v5e, twice the recurrence itself), where the kernel has the chunk's rows
  in VMEM anyway.
* A slot with ``reset`` starts from zero state and a zero window (a new
  request: stale state is masked by nothing).  Positions at or beyond ``num_valid`` leave the
  state as it was (``g = 0``, ``beta = 0`` is the identity of the
  recurrence) and give ``out = 0``; an idle slot (``num_valid = 0``) keeps
  its state and its window bit for bit.
* Returns ``(out [B, C, Hv dv] in x's dtype — before the gated norm — ,
  new_state, new_window)``: the window advanced by ``num_valid`` inputs
  (:func:`advance_window`, a gather of ``K - 1`` rows a slot, the same for
  every lowering).

Two FORMS of the same update, equal to float32 rounding:

* one position (:func:`one_position`): the three lines above on the
  ``[dk, dv]`` tile, what a decoding slot pays: the state read and written
  once, a few dozen vector operations a head;
* a chunk (:func:`chunk_wy`): with ``G_t = g_1 + .. + g_t`` the chunk's
  ``u`` solve ``(I + A) U = beta (V - exp(G) K S_0)``, ``A[t, i] = beta_t
  exp(G_t - G_i) (k_t . k_i)`` for ``i < t`` (the WY / UT transform: unit
  lower triangular, solved by forward substitution), then ``O = exp(G) Q
  S_0 + (exp(G_t - G_i) q_t . k_i)_{i <= t} U`` and ``S_C = exp(G_C) S_0 +
  K^T (exp(G_C - G) U)``: five matrix products a head on the MXU in place
  of ``C`` dependent updates, what a prefilling slot's chunk wants and
  what the full forward of a whole sequence runs (:func:`gdn_sequence`).

Lowerings, behind one dispatcher as ``kernels/ssm_scan.py`` has them:

* **reference** — ``lax.scan`` over the chunk's positions, one position's
  form on ``[B, Hv, dk, dv]`` values; correct everywhere.
* **pallas** — one launch a layer named ``gdn_scan``, grid over the slots
  and blocks of value heads, the state block aliased onto its output: HBM
  sees the state once in and once out.  ``x``, ``window`` and ``taps`` are
  each ONE operand read through three block maps (no slice of them is ever
  made).  A slot with
  ``num_valid = 1`` runs the one-position form, a slot with more the
  chunk's, an idle slot copies its block through.

Dispatch rule (:func:`resolve_gdn_scan_impl`): the kernel when the backend
is a TPU, the state sits whole on one chip and the shapes fit its tiles
(:func:`gdn_scan_fits`); the reference everywhere else.  It reads what it
is handed and the backend — no configuration field, environment variable
or setter; ``interpret`` runs the kernel in Pallas interpreter mode (the
CPU parity tests, by name or by patching :func:`_backend_impl`).  The
engine resolves it once when it builds its step and records it
(``engine.lowerings["gdn_scan_impl"]``, trace metadata
``serving/gdn_scan_impl``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easyparallellibrary_tpu.env import Env

# The kernel's name in a device trace; the benchmark reads it (PERF.md
# section 3).
GDN_SCAN = "gdn_scan"

IMPLS = ("pallas", "reference", "interpret")

LANES = 128
# The chunk's forward substitution is unrolled in the kernel body.
MAX_CHUNK = 32
# Value heads of one grid step: their state block ``[heads, dk, dv]``
# float32 is held four times (in and out, double-buffered).  16 heads of
# [128, 128] are 1 MiB.
_HEAD_BLOCK = 16
# What ``l2norm`` adds to the sum of squares (Qwen3-Next's, whose shapes the
# layer has).
L2_EPS = 1e-6

_HI = jax.lax.Precision.HIGHEST


def _backend_impl() -> str:
  """The lowering this backend takes when the shapes allow it.  The CPU
  parity tests patch it to ``interpret``."""
  return "pallas" if jax.default_backend() == "tpu" else "reference"


def key_heads(state_shape, width: int) -> int:
  """Key heads of a ``qkv`` of ``width`` columns beside a state ``[B, Hv,
  dk, dv]``: ``width = 2 Hk dk + Hv dv``."""
  _, Hv, dk, dv = state_shape
  Hk, rest = divmod(width - Hv * dv, 2 * dk)
  if Hk < 1 or rest or Hv % Hk:
    raise ValueError(f"qkv of {width} columns beside a state {state_shape}: "
                     "no whole number of key heads")
  return Hk


def _head_block(Hv: int, Hk: int) -> int:
  """Value heads of one grid step: the most under :data:`_HEAD_BLOCK` that
  divide ``Hv`` and hold whole key heads; 0 if none."""
  group = Hv // Hk
  for hb in range(min(Hv, _HEAD_BLOCK), 0, -1):
    if Hv % hb == 0 and hb % group == 0:
      return hb
  return 0


def gdn_scan_fits(state_shape, width: int, dtype, chunk: int) -> bool:
  """Whether the kernel can tile a ``[B, Hv, dk, dv]`` state beside a
  ``qkv`` of ``width`` columns of ``dtype``: heads of whole lane tiles
  both ways, a chunk short enough to unroll and of whole sublane tiles
  (or one position), a 32-bit or 16-bit float activation, and the values'
  columns starting on a block of theirs."""
  _, Hv, dk, dv = state_shape
  dtype = jnp.dtype(dtype)
  if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
    return False
  if dk % LANES or dv % LANES:
    return False
  rows = 8 * 4 // dtype.itemsize
  if not 1 <= chunk <= MAX_CHUNK or (chunk > 1 and chunk % rows):
    return False
  try:
    Hk = key_heads(state_shape, width)
  except ValueError:
    return False
  hb = _head_block(Hv, Hk)
  return hb > 0 and (2 * Hk * dk) % (hb * dv) == 0


def resolve_gdn_scan_impl(state_shape, width: int, dtype, chunk: int,
                          sharded: bool = False) -> str:
  """The dispatch rule: the backend's lowering, and ``reference``
  whenever the state lives on a multi-device mesh (``sharded``: the SPMD
  partitioner cannot split a Mosaic call) or the shapes do not fit."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not gdn_scan_fits(state_shape, width, dtype, chunk)):
    return "reference"
  return impl


# ------------------------------------------------------------ the two forms


def l2norm(x, scale: float = 1.0):
  """``x / sqrt(sum(x^2) + eps) * scale`` over the last axis, float32."""
  x = x.astype(jnp.float32)
  return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
              * scale)


def convolved(full, taps, C: int):
  """``silu`` of the causal depthwise convolution of the last ``C``
  positions of ``full`` ``[.., K - 1 + C, W]`` (the window, then the
  chunk), float32: what a full forward and the reference lowering run."""
  K = taps.shape[0]
  f32 = jnp.float32
  return jax.nn.silu(sum(full[..., j:j + C, :].astype(f32) * taps[j]
                         for j in range(K)))


def advance_window(window, x, num_valid, reset):
  """The convolution's carried inputs after a chunk: the last ``K - 1`` of
  the window's rows followed by the chunk's first ``num_valid``, per slot;
  a gather of ``K - 1`` whole rows a slot from each and a select (no pass
  over the chunk).  ``num_valid = 0`` returns the old window bit for bit
  (zeros after a ``reset``)."""
  B, keep, W = window.shape
  C = x.shape[1]
  i32 = jnp.int32
  window = jnp.where(reset[:, None, None], jnp.zeros((), window.dtype),
                     window)
  # New row r is the input at chunk position num_valid - keep + r: the
  # chunk's where that is not negative, else the old window's row
  # num_valid + r.
  pos = num_valid.astype(i32)[:, None] - keep + jnp.arange(keep, dtype=i32)
  base = jnp.arange(B, dtype=i32)[:, None]
  take = lambda rows, idx, n: jnp.take(
      rows.reshape(B * n, W), (base * n + jnp.clip(idx, 0, n - 1)).reshape(-1),
      axis=0).reshape(B, keep, W)
  return jnp.where((pos >= 0)[..., None], take(x, pos, C).astype(window.dtype),
                   take(window, pos + keep, keep))


def one_position(S, k_col, q_col, v_row, decay, beta):
  """One position of one head on the ``[dk, dv]`` tile: ``k_col``,
  ``q_col`` ``[dk, 1]`` (normed), ``v_row`` ``[1, dv]``, ``decay`` ``[1,
  dv]`` (``exp(g)`` along the lanes: Mosaic broadcasts one direction at a
  time) and ``beta`` ``[1, 1]``.  Returns ``(o [1, dv], S)``."""
  S = S * decay
  u = beta * (v_row - jnp.sum(S * k_col, axis=0, keepdims=True))
  S = S + k_col * u
  return jnp.sum(S * q_col, axis=0, keepdims=True), S


def chunk_wy(S, q, k, kT, v, G_col, G_row, beta_col, beta_row, kk=None,
             qk=None, decay=None):
  """A chunk of ``C`` positions of one head (module docstring): ``q``,
  ``k`` ``[C, dk]`` (normed, float32), ``kT`` ``[dk, C]``, ``v`` ``[C,
  dv]``, the cumulative log decay as a column ``[C, 1]`` and as a row ``[1,
  C]``, ``beta`` likewise; ``kk`` / ``qk`` the ``[C, C]`` products ``k_t .
  k_i`` / ``q_t . k_i`` where the caller has them (they are the key
  head's, shared by its value heads), ``decay`` ``[1, dv]`` the whole
  chunk's ``exp(G_C)`` along the lanes likewise.  Positions beyond a slot's
  live ones are handed ``g = 0`` and ``beta = 0``.  Returns ``(o [C, dv],
  S)``."""
  C = q.shape[0]
  f32 = jnp.float32
  dot = functools.partial(jnp.dot, precision=_HI, preferred_element_type=f32)
  nt = lambda a, b: jax.lax.dot_general(
      a, b, (((1,), (1,)), ((), ())), precision=_HI,
      preferred_element_type=f32)
  kk = nt(k, k) if kk is None else kk
  qk = nt(q, k) if qk is None else qk
  t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
  i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
  # AT[i, t] = A[t, i]: beta_t exp(G_t - G_i) (k_t . k_i) for i < t (here
  # the row index is i, the column index t).
  AT = jnp.where(t < i, kk * jnp.exp(jnp.minimum(G_row - G_col, 0.0))
                 * beta_row, 0.0)
  P = jnp.where(t >= i, qk * jnp.exp(jnp.minimum(G_col - G_row, 0.0)), 0.0)
  within = jnp.exp(G_col)                                    # [C, 1]
  U = beta_col * (v.astype(f32) - within * dot(k, S))
  row = jax.lax.broadcasted_iota(jnp.int32, U.shape, 0)
  for s in range(1, C):
    # u_s = r_s - sum_{i < s} A[s, i] u_i; rows at or beyond s of AT's
    # column s are zero.
    fix = jnp.sum(AT[:, s:s + 1] * U, axis=0, keepdims=True)
    U = jnp.where(row == s, U - fix, U)
  o = within * dot(q, S) + dot(P, U)
  last = G_col[C - 1:C]                                      # [1, 1]
  if decay is None:
    decay = jnp.broadcast_to(jnp.exp(last), (1, S.shape[1]))
  S = decay * S + dot(kT, jnp.exp(last - G_col) * U)
  return o, S


# -------------------------------------------------------------- reference --


def _normed(state_shape, qkv):
  """A CONVOLVED ``qkv`` ``[.., 2 Hk dk + Hv dv]`` with its heads apart,
  the queries and keys normed and each key head repeated for its value
  heads: ``q``, ``k`` ``[.., Hv, dk]``, ``v`` ``[.., Hv, dv]``, float32."""
  _, Hv, dk, dv = state_shape
  Hk = key_heads(state_shape, qkv.shape[-1])
  lead = qkv.shape[:-1]
  q, k, v = jnp.split(qkv, [Hk * dk, 2 * Hk * dk], axis=-1)
  heads = lambda x: jnp.repeat(x.reshape(*lead, Hk, dk), Hv // Hk, axis=-2)
  return (l2norm(heads(q), dk ** -0.5), l2norm(heads(k)),
          v.reshape(*lead, Hv, dv).astype(jnp.float32))


def gdn_scan_reference(state, window, x, taps, g, beta, num_valid, reset):
  """``lax.scan`` over the chunk's positions (module docstring)."""
  B, C, _ = x.shape
  f32 = jnp.float32
  s0 = jnp.where(reset[:, None, None, None], jnp.zeros((), f32), state)
  live = jnp.arange(C)[None, :] < num_valid[:, None]            # [B, C]
  old = jnp.where(reset[:, None, None], jnp.zeros((), window.dtype), window)
  qkv = convolved(jnp.concatenate([old.astype(x.dtype), x], axis=1),
                  taps.astype(f32), C)
  q, k, v = _normed(state.shape, qkv)

  def step(S, xs):
    q_t, k_t, v_t, g_t, b_t, live_t = xs          # [B, Hv, d], [B, Hv], [B]
    on = live_t[:, None]
    g_t, b_t = jnp.where(on, g_t, 0.0), jnp.where(on, b_t, 0.0)
    S = S * jnp.exp(g_t)[..., None, None]
    u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                           precision=_HI))
    # An idle position adds exactly nothing: its state stays bit for bit.
    S = jnp.where(on[..., None, None], S + k_t[..., None] * u[..., None, :],
                  S)
    o = jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)
    return S, jnp.where(on[..., None], o, 0.0).astype(x.dtype)

  t_major = lambda a: jnp.moveaxis(a, 1, 0)
  S, out = jax.lax.scan(step, s0, tuple(
      t_major(a) for a in (q, k, v, g.astype(f32), beta.astype(f32), live)))
  return (t_major(out).reshape(B, C, -1), S,
          advance_window(window, x, num_valid, reset))


def gdn_sequence(qkv, g, beta, state_shape, chunk: int = MAX_CHUNK):
  """The full forward's linear attention of whole sequences from zero
  state: ``qkv`` ``[B, S, ..]`` CONVOLVED (:func:`convolved` over the
  sequence behind a zero window), ``g`` and ``beta`` ``[B, S, Hv]``;
  :func:`chunk_wy` over the sequence's chunks of ``chunk`` positions (the
  last one padded with identity positions), every head of every sequence at
  once.  Returns ``out [B, S, Hv dv]`` float32."""
  B, S, _ = qkv.shape
  _, Hv, dk, dv = state_shape
  f32 = jnp.float32
  n = -(-S // chunk)
  pad = lambda x: jnp.pad(x, ((0, 0), (0, n * chunk - S)) + ((0, 0),)
                          * (x.ndim - 2))
  q, k, v = _normed(state_shape, pad(qkv))
  # [n, B, Hv, C, ..]: chunks in front for the scan, heads before positions.
  chunks = lambda x: jnp.moveaxis(
      x.reshape(B, n, chunk, *x.shape[2:]), (1, 3), (0, 2))
  G = jnp.cumsum(chunks(pad(g.astype(f32))[..., None]), axis=3)
  b = chunks(pad(beta.astype(f32))[..., None])
  head = jax.vmap(jax.vmap(
      lambda S0, q, k, v, G, b: chunk_wy(S0, q, k, k.T, v, G, G.T, b, b.T)))

  def step(S0, xs):
    o, S1 = head(S0, *xs)
    return S1, o

  _, out = jax.lax.scan(step, jnp.zeros((B, Hv, dk, dv), f32),
                        (chunks(q), chunks(k), chunks(v), G, b))
  # [n, B, Hv, C, dv] -> [B, S, Hv dv]
  out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(B, n * chunk, Hv * dv)
  return out[:, :S]


# ----------------------------------------------------------------- pallas --


def _gdn_scan_kernel(nv_ref, reset_ref, s_ref, q_ref, k_ref, v_ref, wq_ref,
                     wk_ref, wv_ref, tq_ref, tk_ref, tv_ref, G_ref, GT_ref,
                     b_ref, bT_ref, d_ref, out_ref, s_out_ref, *, chunk: int,
                     heads: int, group: int, dk: int, dv: int, taps: int):
  """One (slot, block of value heads) grid step.  A head's state is the
  ``[dk, dv]`` tile ``s_ref[0, h]``; its queries and keys are the columns
  ``[hk dk, (hk + 1) dk)`` of the block's ``[C, ..]`` rows, ``hk = h //
  group``, its values and outputs the columns ``[h dv, (h + 1) dv)``; the
  window's rows and the taps come in the same three column blocks.  The
  cumulative log decay and ``beta`` come position-major ``[C, heads]`` (a
  head's column scales rows) and head-major ``[heads, C]`` (its row scales
  columns); ``d_ref`` holds ``exp`` of a head's first and of its last
  cumulative log decay along the lanes, ``[2 heads, dv]``: what scales a
  whole tile.  A column of a key or query (``[dk, 1]``, what scales the
  state's rows) is made on the MXU: the identity times the rows,
  transposed on the way in."""
  b = pl.program_id(0)
  nv = nv_ref[b]
  f32 = jnp.float32
  C, K = chunk, taps
  fresh = reset_ref[b] != 0
  start = lambda h: jnp.where(fresh, jnp.zeros((), f32), s_ref[0, h])
  cols = lambda h, d: slice(h * d, (h + 1) * d)
  eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
         == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)).astype(f32)
  # [dk, n] = I x^T for x [n, dk]: exact (one addend a sum).
  transposed = lambda x: jax.lax.dot_general(
      eye, x, (((1,), (1,)), ((), ())), precision=_HI,
      preferred_element_type=f32)
  # The window's rows [K - 1, n], zeros for a slot that starts a request.
  behind = lambda w_ref: jnp.where(fresh, jnp.zeros((), f32),
                                   w_ref[0].astype(f32))

  def first(x_ref, w_ref, t_ref):
    """The convolution at the chunk's first position alone: ``[1, n]``."""
    w, t = behind(w_ref), t_ref[...]
    y = t[K - 1:K] * x_ref[0, 0:1, :].astype(f32)
    for j in range(K - 1):
      y = y + t[j:j + 1] * w[j:j + 1]
    return jax.nn.silu(y)

  def whole(x_ref, w_ref, t_ref):
    """The convolution at every position of the chunk: ``[C, n]``.  Row
    ``r`` of the input behind by ``s`` is row ``r - s`` of the chunk (a
    rotation down the sublanes) or, for ``r < s``, row ``K - 1 - s + r``
    of the window."""
    x, w, t = x_ref[0].astype(f32), behind(w_ref), t_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    y = t[K - 1:K] * x
    for s in range(1, K):
      back = pltpu.roll(x, s, 0)
      for r in range(s):
        at = K - 1 - s + r
        back = jnp.where(row == r, w[at:at + 1], back)
      y = y + t[K - 1 - s:K - s] * back
    return jax.nn.silu(y)

  @pl.when(nv == 0)
  def _idle():
    for h in range(heads):
      s_out_ref[0, h] = start(h)
    out_ref[...] = jnp.zeros_like(out_ref)

  @pl.when(nv == 1)
  def _decode():
    out_ref[...] = jnp.zeros_like(out_ref)
    q, k = first(q_ref, wq_ref, tq_ref), first(k_ref, wk_ref, tk_ref)
    v = first(v_ref, wv_ref, tv_ref)
    # A key head's row, normed, as a column: [dk, 8] from 8 equal rows.
    column = lambda x, hk, scale: transposed(jnp.broadcast_to(
        l2norm(x[:, cols(hk, dk)], scale), (8, dk)))[:, 0:1]
    qT = [column(q, hk, dk ** -0.5) for hk in range(heads // group)]
    kT = [column(k, hk, 1.0) for hk in range(heads // group)]
    for h in range(heads):
      hk = h // group
      o, S = one_position(
          start(h), kT[hk], qT[hk], v[:, cols(h, dv)],
          d_ref[0, 0, h:h + 1, :], b_ref[0, 0, 0:1, h:h + 1])
      s_out_ref[0, h] = S
      out_ref[0, 0:1, cols(h, dv)] = o.astype(out_ref.dtype)

  @pl.when(nv > 1)
  def _chunk():
    live = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < nv
    nt = lambda x, y: jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=f32)
    qs, ks = whole(q_ref, wq_ref, tq_ref), whole(k_ref, wk_ref, tk_ref)
    vs = whole(v_ref, wv_ref, tv_ref)
    for hk in range(heads // group):
      q = l2norm(qs[:, cols(hk, dk)], dk ** -0.5)
      k = l2norm(ks[:, cols(hk, dk)])
      kT, kk, qk = transposed(k), nt(k, k), nt(q, k)
      for h in range(hk * group, (hk + 1) * group):
        o, S = chunk_wy(
            start(h), q, k, kT, vs[:, cols(h, dv)],
            G_ref[0, 0, :, h:h + 1], GT_ref[0, 0, h:h + 1, :],
            b_ref[0, 0, :, h:h + 1], bT_ref[0, 0, h:h + 1, :], kk, qk,
            d_ref[0, 0, heads + h:heads + h + 1, :])
        s_out_ref[0, h] = S
        out_ref[0, :, cols(h, dv)] = jnp.where(live, o, 0.0).astype(
            out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_scan_pallas(state, window, x, taps, g, beta, num_valid, reset,
                    interpret: bool = False):
  """The kernel (module docstring); ``interpret`` runs it in Pallas
  interpreter mode on any backend.  Jitted, so that the layers of one
  step share one trace and one Mosaic lowering."""
  B, Hv, dk, dv = state.shape
  C, W = x.shape[1:]
  K = taps.shape[0]
  if not gdn_scan_fits(state.shape, W, x.dtype, C):
    raise ValueError(f"gdn_scan kernel does not fit state {state.shape}, "
                     f"x {x.shape} {x.dtype} (gdn_scan_fits)")
  Hk = key_heads(state.shape, W)
  hb = _head_block(Hv, Hk)
  group, hkb = Hv // Hk, hb // (Hv // Hk)
  f32 = jnp.float32
  # Dead positions are the recurrence's identity; the decay accumulates
  # down the chunk here, on [B, C, Hv] values.
  live = (jnp.arange(C)[None, :] < num_valid[:, None])[..., None]
  G = jnp.cumsum(jnp.where(live, g.astype(f32), 0.0), axis=1)
  beta = jnp.where(live, beta.astype(f32), 0.0)
  # [B, blocks, C, hb] and [B, blocks, hb, C]: a block's heads are the
  # whole of a minor dimension.
  by_block = lambda a: jnp.moveaxis(a.reshape(B, C, Hv // hb, hb), 2, 1)
  G, beta = by_block(G), by_block(beta)
  GT, betaT = jnp.swapaxes(G, 2, 3), jnp.swapaxes(beta, 2, 3)
  # exp of the first and of the last cumulative log decay, along dv lanes.
  decay = jnp.broadcast_to(
      jnp.exp(jnp.concatenate([GT[..., 0], GT[..., C - 1]], -1))[..., None],
      (B, Hv // hb, 2 * hb, dv))
  # The queries', the keys' and the values' column blocks of one operand:
  # the block index of each within its own block width.
  firsts = (0, Hk // hkb, 2 * Hk * dk // (hb * dv))
  widths = (hkb * dk, hkb * dk, hb * dv)
  slotwise = lambda rows: [
      pl.BlockSpec((1, rows, n), lambda b, j, nv, rs, at=at: (b, 0, at + j))
      for at, n in zip(firsts, widths)]
  shared = [pl.BlockSpec((K, n), lambda b, j, nv, rs, at=at: (0, at + j))
            for at, n in zip(firsts, widths)]
  scalars = lambda shape: pl.BlockSpec(
      (1, 1) + shape, lambda b, j, nv, rs: (b, j, 0, 0))
  st = pl.BlockSpec((1, hb, dk, dv), lambda b, j, nv, rs: (b, j, 0, 0))
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(B, Hv // hb),
      in_specs=[st, *slotwise(C), *slotwise(K - 1), *shared,
                scalars((C, hb)), scalars((hb, C)),
                scalars((C, hb)), scalars((hb, C)), scalars((2 * hb, dv))],
      out_specs=[pl.BlockSpec((1, C, hb * dv),
                              lambda b, j, nv, rs: (b, 0, j)), st],
  )
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
  taps = taps.astype(f32)
  out, new_state = pl.pallas_call(
      functools.partial(_gdn_scan_kernel, chunk=C, heads=hb, group=group,
                        dk=dk, dv=dv, taps=K),
      grid_spec=grid_spec,
      out_shape=[jax.ShapeDtypeStruct((B, C, Hv * dv), x.dtype),
                 jax.ShapeDtypeStruct(state.shape, f32)],
      # Operands count the two scalar-prefetch vectors: 2 is the state.
      input_output_aliases={2: 1},
      interpret=interpret,
      name=GDN_SCAN,
      **kwargs,
  )(num_valid.astype(jnp.int32), reset.astype(jnp.int32),
    state.astype(f32), x, x, x, window, window, window, taps, taps, taps,
    G, GT, beta, betaT, decay)
  return out, new_state, advance_window(window, x, num_valid, reset)


# --------------------------------------------------------------- dispatch --


def gdn_scan(state, window, x, taps, g, beta, num_valid=None, reset=None,
             impl: Optional[str] = None):
  """Advance every slot's state and window over its chunk (module
  docstring); returns ``(out, new_state, new_window)``.  ``num_valid=None``
  takes every position as live, ``reset=None`` none as new; ``impl=None``
  applies the dispatch rule to the shapes at hand, and takes the state as
  spread over chips whenever a multi-device mesh has been built (the
  serving engine resolves the impl from its own mesh and passes it)."""
  B, C = x.shape[:2]
  if num_valid is None:
    num_valid = jnp.full((B,), C, jnp.int32)
  if reset is None:
    reset = jnp.zeros((B,), bool)
  if impl is None:
    impl = resolve_gdn_scan_impl(state.shape, x.shape[-1], x.dtype, C,
                                 sharded=Env.get().mesh_built())
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  if impl == "reference":
    return gdn_scan_reference(state, window, x, taps, g, beta, num_valid,
                              reset)
  return gdn_scan_pallas(state, window, x, taps, g, beta, num_valid, reset,
                         interpret=impl == "interpret")
