"""The blocks more than one decoder is built from, below every decoder
file (each imports this module; it imports none of them and nothing of
``serving/``): the two norms, the SiLU-gated MLP, the parameter helpers,
rotary positions (plain and YaRN's), the full forward's grouped attention, a convolution
window's advance, a window ring's length, and multi-head latent attention
with its sizes (:class:`LatentAttention`, :class:`LatentDims`,
:class:`IndexerDims`).  Class names are flax scope and parameter-path
names: they do not change.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from easyparallellibrary_tpu.models.slot_core import missing_slot_cache
from easyparallellibrary_tpu.ops import Dense
from easyparallellibrary_tpu.ops.layers import HeldParams


def boxed(init, ndim: int):
  return nn.with_partitioning(init, (None,) * ndim)


def dense(cfg, features: int, name: str):
  return Dense(features, use_bias=False, parallel="none", dtype=cfg.dtype,
               param_dtype=cfg.param_dtype,
               kernel_init=nn.initializers.normal(stddev=0.02), name=name)


def uniform(bound: float):
  def init(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(dtype)
  return init


class RMSNorm(HeldParams, nn.Module):
  """``x * rsqrt(mean(x^2) + eps) * g`` in float32; the gain is a float32
  parameter whatever the weights' dtype.  ``rescale`` is a constant the
  result is multiplied by before it is rounded (models/dots3_note.py: the
  rescaled latents of its attention); 1 leaves the arithmetic as it is."""
  eps: float
  dtype: Any
  rescale: float = 1.0

  @nn.compact
  def __call__(self, x):
    g = self.param("scale", boxed(nn.initializers.ones_init(), 1),
                   (x.shape[-1],), jnp.float32)
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * g
    if self.rescale != 1.0:
      y = y * self.rescale
    return y.astype(self.dtype)


class LayerNorm(HeldParams, nn.Module):
  """``(x - mean) * rsqrt(var + eps) * g + b`` in float32, gain and bias
  float32 parameters (the indexer's key norm)."""
  eps: float
  dtype: Any

  @nn.compact
  def __call__(self, x):
    g = self.param("scale", boxed(nn.initializers.ones_init(), 1),
                   (x.shape[-1],), jnp.float32)
    b = self.param("bias", boxed(nn.initializers.zeros_init(), 1),
                   (x.shape[-1],), jnp.float32)
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, -1, keepdims=True)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + self.eps) * g + b
    return y.astype(self.dtype)


def clamp_gate_up(gate, up, limit: Optional[float]):
  """A gated MLP's two pre-activations held to ``limit`` before their
  product (``swiglu_limit``, models/gigachat.py): the gate at most
  ``limit``, the up projection within ``+-limit``; ``None`` leaves both."""
  if limit is None:
    return gate, up
  return jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)


class GatedMLP(nn.Module):
  """``down(silu(gate(h)) * up(h))``; ``cfg`` gives ``d_model`` and the
  dtypes, ``d_ff`` the width where a model has more than one
  (models/glm_moe.py: its dense layer and its shared expert), ``limit``
  what the two pre-activations are held to (:func:`clamp_gate_up`)."""
  cfg: Any
  d_ff: Optional[int] = None
  limit: Optional[float] = None

  @nn.compact
  def __call__(self, h):
    cfg = self.cfg
    d_ff = self.d_ff or cfg.d_ff
    gate, up = clamp_gate_up(dense(cfg, d_ff, "gate")(h),
                             dense(cfg, d_ff, "up")(h), self.limit)
    return dense(cfg, cfg.d_model, "down")(jax.nn.silu(gate) * up)


@dataclasses.dataclass(frozen=True)
class YarnDims:
  """YaRN's rescaling of rotary positions (Peng et al. 2023, as
  DeepSeek-V3's modelling code has it, a config's ``rope_scaling`` of
  ``type: yarn``): the slow pairs' frequencies divided by ``factor``, the
  fast ones kept, a linear ramp between the pairs that turn
  ``beta_fast`` and ``beta_slow`` times over ``original_max_position``.
  ``scale_softmax`` (``use_mla_scaling_factor``): the attention's softmax
  scale times ``mscale(factor, mscale_all_dim)`` squared."""
  factor: float
  original_max_position: int
  beta_fast: float = 32.0
  beta_slow: float = 1.0
  mscale: float = 1.0
  mscale_all_dim: float = 0.0
  scale_softmax: bool = True

  @staticmethod
  def get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0

  @property
  def amplitude(self) -> float:
    """What cos and sin are multiplied by."""
    return (self.get_mscale(self.factor, self.mscale)
            / self.get_mscale(self.factor, self.mscale_all_dim))

  @property
  def softmax_factor(self) -> float:
    if not (self.scale_softmax and self.mscale_all_dim):
      return 1.0
    return self.get_mscale(self.factor, self.mscale_all_dim) ** 2

  def frequencies(self, d: int, theta: float) -> np.ndarray:
    """The ``d / 2`` pair frequencies, float32."""
    pairs = np.arange(d // 2, dtype=np.float64)
    extra = theta ** (-2.0 * pairs / d)
    turn = lambda rotations: (d * np.log(
        self.original_max_position / (rotations * 2 * np.pi))
                              / (2 * np.log(theta)))
    low = max(np.floor(turn(self.beta_fast)), 0)
    high = min(np.ceil(turn(self.beta_slow)), d - 1)
    if low == high:
      high += 0.001
    keep = 1.0 - np.clip((pairs - low) / (high - low), 0.0, 1.0)
    return (extra / self.factor * (1.0 - keep) + extra * keep).astype(
        np.float32)


def rotary(x, positions, theta: float, yarn: Optional[YarnDims] = None):
  """Rotate-half rotary embedding over ALL of ``x``'s last axis: ``x``
  ``[B, S, H, d]``, ``positions`` int ``[B, S]``; pair ``i`` is ``(x[i],
  x[i + d/2])`` turned by ``position * theta^(-2i/d)``, or by
  ``yarn``'s frequency table (:class:`YarnDims`).  float32 inside."""
  d = x.shape[-1]
  if yarn is None:
    freq = jnp.exp(jnp.arange(d // 2, dtype=jnp.float32)
                   * (-2.0 * jnp.log(theta) / d))
  else:
    freq = jnp.asarray(yarn.frequencies(d, theta))
  ang = positions.astype(jnp.float32)[:, :, None, None] * freq
  cos, sin = jnp.cos(ang), jnp.sin(ang)
  if yarn is not None and yarn.amplitude != 1.0:
    cos, sin = cos * yarn.amplitude, sin * yarn.amplitude
  x32 = x.astype(jnp.float32)
  a, b = x32[..., :d // 2], x32[..., d // 2:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                         -1).astype(x.dtype)


def rotate_leading(x, positions, theta: float, width: int):
  """Rotary on the leading ``width`` of ``x`` ``[B, S, H, d]``'s last
  axis, the rest as it is."""
  return jnp.concatenate(
      [rotary(x[..., :width], positions, theta), x[..., width:]], -1)


def gqa_causal_attention(q, k, v, dtype, window: Optional[int] = None):
  """Dense causal attention of ``q`` [B, S, H, hd] over ``k``/``v`` [B, S,
  H_kv, hd], each K/V head shared by H / H_kv query heads: the full
  forward's attention (float32 softmax, as ``_dense_causal_attention``).
  Behind a ``window`` position ``t`` sees ``t - window < s <= t``
  (models/smallthinker.py)."""
  B, S, H, hd = q.shape
  Hkv = k.shape[2]
  q = q.reshape(B, S, Hkv, H // Hkv, hd)
  scale = 1.0 / jnp.sqrt(hd).astype(dtype)
  logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
  mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
  if window is not None:
    mask &= ~jnp.tril(jnp.ones((S, S), jnp.bool_), -window)
  logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(dtype), v)
  return out.reshape(B, S, H, hd)


def advance_window(full, num_valid, keep: int):
  """The convolution's carried inputs after a chunk: rows ``[num_valid,
  num_valid + keep)`` of ``full`` [B, keep + C, Di] (the old window
  followed by the chunk's inputs), per slot.  A select and a sum over the
  few rows, not a gather: exact, and no serial loop over the slots on a
  TPU.  ``num_valid = 0`` returns the old window bit for bit."""
  if num_valid is None:
    return full[:, full.shape[1] - keep:]
  rows = num_valid[:, None] + jnp.arange(keep)[None]          # [B, keep]
  pick = rows[:, :, None] == jnp.arange(full.shape[1])[None, None]
  return jnp.sum(jnp.where(pick[..., None], full[:, None],
                           jnp.zeros((), full.dtype)), axis=2)


def ring_length(window: int, chunk: int, tile: int = 128) -> int:
  """Rows of a window layer's ring for ``chunk``-wide steps: the window's
  reach behind a step's first query (``window - 1``) plus the chunk the
  step writes, up to whole ``tile``-row tiles (the attend's blocks and
  the write's tiles): 640 at window 513, chunk 32."""
  return -(-(window - 1 + chunk) // tile) * tile


@dataclasses.dataclass(frozen=True)
class IndexerDims:
  """The indexer of a latent attention that SELECTS the rows it reads
  (DeepSeek-V3.2's, models/dots3_note.py): ``num_heads`` index heads of
  ``head_dim``, the leading ``rope_dim`` of each rotated, one index key a
  position, the ``top_k`` best-scoring rows attended."""
  num_heads: int
  head_dim: int
  top_k: int
  rope_dim: int
  layer_norm_eps: float = 1e-6


@dataclasses.dataclass(frozen=True)
class LatentDims:
  """The sizes and options of ONE multi-head latent attention: what
  :class:`LatentAttention` is built from.  GLM-MoE's layers all share one
  (models/glm_moe.py ``glm_latent_dims``); a model whose layers differ
  (models/dots3_note.py) hands each layer its own.  ``q_rescale`` /
  ``kv_rescale`` multiply the two normed latents; ``gate`` adds a sigmoid
  gate on the heads' outputs, from a projection of the layer's input:
  ``True`` one value a head (models/dots3_note.py), ``"elementwise"`` one a
  value of every head (models/gigachat.py); ``yarn`` rescales the rotary
  positions and, where it says so, the softmax scale; ``window`` limits a
  query
  at ``t`` to the positions ``t - window < s <= t`` (slot mode then keeps
  the latent leaf as a ring); ``indexer`` limits it to the rows an indexer
  selects."""
  num_heads: int
  q_lora_rank: int
  kv_lora_rank: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  rope_theta: float
  q_rescale: float = 1.0
  kv_rescale: float = 1.0
  gate: Any = False
  window: Optional[int] = None
  indexer: Optional[IndexerDims] = None
  yarn: Optional[YarnDims] = None

  @property
  def scale(self) -> float:
    scale = float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
    return scale if self.yarn is None else scale * self.yarn.softmax_factor

  @property
  def latent_dim(self) -> int:
    return self.kv_lora_rank + self.qk_rope_head_dim


class LatentAttention(HeldParams, nn.Module):
  """Multi-head latent attention (models/glm_moe.py's module docstring
  holds the equations), shared by every model that has one: ``cfg`` gives
  ``d_model``, ``rms_norm_eps`` and the dtypes, ``dims`` the attention's
  own sizes and options.  The three lowerings are what the serving engine
  resolved (``kv_cache.step_lowerings``); ``None`` leaves each kernel's
  entry to apply its rule to the operands it is handed."""
  cfg: Any
  dims: LatentDims
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  dsa_index_impl: Optional[str] = None

  @nn.compact
  def __call__(self, h, positions=None, slot_cursors=None, num_valid=None,
               rows=None, part=None):
    cfg, dims = self.cfg, self.dims
    H, r = dims.num_heads, dims.kv_lora_rank
    dn, dr, dv = (dims.qk_nope_head_dim, dims.qk_rope_head_dim,
                  dims.v_head_dim)
    ix = dims.indexer
    held = []

    def w_kvb():
      if not held:
        held.append(jnp.asarray(self.param(
            "kv_b", boxed(nn.initializers.normal(stddev=0.02), 2),
            (r, H * (dn + dv)), cfg.param_dtype), cfg.dtype).reshape(
                r, H, dn + dv))
      return held[0]

    elementwise = dims.gate == "elementwise"

    def gated_out(out, gate):
      if elementwise:
        # carried as the projection left it; one sigmoid a value here
        gate = jax.nn.sigmoid(gate.astype(jnp.float32)).reshape(out.shape)
        out = (out.astype(jnp.float32) * gate).astype(cfg.dtype)
      elif gate is not None:
        out = (out.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
      return dense(cfg, cfg.d_model, "o")(
          out.reshape(*out.shape[:2], H * dv))

    # In slot mode the whole call is its three parts in turn
    # (models/slot_core.py:SplitLayer), ``h`` from the second on the carry.
    if part in (None, "pre"):
      B, S, _ = h.shape
      norm = lambda name, rescale=1.0: RMSNorm(
          cfg.rms_norm_eps, cfg.dtype, rescale, name=name)
      c_q = norm("q_norm", dims.q_rescale)(
          dense(cfg, dims.q_lora_rank, "q_a")(h))
      q = dense(cfg, H * (dn + dr), "q_b")(c_q).reshape(B, S, H, dn + dr)
      q_nope = q[..., :dn]
      q_rope = rotary(q[..., dn:], positions, dims.rope_theta, dims.yarn)
      kv = dense(cfg, r + dr, "kv_a")(h)
      c = norm("kv_norm", dims.kv_rescale)(kv[..., :r])
      k_r = rotary(kv[..., None, r:], positions, dims.rope_theta,
                   dims.yarn)                                # [B,S,1,dr]
      if ix is not None:
        # The indexer: index queries from the query latent, ONE index key a
        # position from the layer's input, a weight an index head.
        q_ix = rotate_leading(
            dense(cfg, ix.num_heads * ix.head_dim, "index_q")(c_q).reshape(
                B, S, ix.num_heads, ix.head_dim),
            positions, dims.rope_theta, ix.rope_dim)
        k_ix = rotate_leading(
            LayerNorm(ix.layer_norm_eps, cfg.dtype, name="index_k_norm")(
                dense(cfg, ix.head_dim, "index_k")(h))[:, :, None],
            positions, dims.rope_theta, ix.rope_dim)[:, :, 0]
        w_ix = dense(cfg, ix.num_heads, "index_w")(h).astype(jnp.float32)
      # From the layer's input, on the heads' outputs: one value a head,
      # or one a value of every head.
      if elementwise:
        gate = dense(cfg, H * dv, "gate")(h)
      else:
        gate = None if not dims.gate else jax.nn.sigmoid(
            dense(cfg, H, "gate")(h).astype(jnp.float32))
      if not self.decode:
        return gated_out(self._dense_attend(
            q_nope, q_rope, c, k_r, w_kvb(),
            None if ix is None else (q_ix, k_ix, w_ix), dims), gate)
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows); the window write and the attend take
      # their operands as [slots, C, ...], everything around them stays
      # flat.
      new = jnp.concatenate([c[:, :, None], k_r], -1)        # [T,1,1,r+dr]
      q_abs = jnp.concatenate(
          [jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb()[..., :dn]), q_rope],
          -1)[:, 0]                                          # [T,H,r+dr]
      index = () if ix is None else tuple(
          rows.to_slots(t[:, 0]) for t in (k_ix, q_ix, w_ix))
      # The tile kernels read a tile's query rows where they lie in the
      # flat batch, and write its result there: the selected and the
      # windowed attends wherever they run on a narrower batch, a plain
      # leaf's where tiling its chunk skips something besides
      # (``plain_tile_form``, from the leaf's shape, the heads and the
      # chunk).  The first grid takes them in [slots, C] order, which is
      # also what a lowering nobody resolved yet (``None``) is handed:
      # every lowering takes it.
      from easyparallellibrary_tpu.kernels.slot_attention import (
          plain_tile_form, tile_attn_out)
      narrower = rows.dst is not None
      flat = tile_attn_out(self.slot_attn_impl, narrower) == "flat"
      if flat and dims.window is None and ix is None:
        leaf = self.get_variable("cache", "cached_latent")
        flat = plain_tile_form(self.slot_attn_impl, narrower, leaf.shape,
                               leaf.dtype, rows.chunk, H, r)
      if flat:
        h = (gate, q_abs), (rows.to_slots(new[:, 0]), None, *index)
      else:
        h = (gate,), (rows.to_slots(new[:, 0]), rows.to_slots(q_abs),
                      *index)
      if part == "pre":
        return h
    if part in (None, "mix"):
      h = self._mix(*h, dims, slot_cursors, num_valid, rows)
      if part == "mix":
        return h
    (gate, *flat_o), o_lat = h
    # the attend's latent rows, flat [rows, H, r] as a kernel that works on
    # the flat batch left them or [slots, C, H, r], -> [rows, 1, H, dv]
    o_lat = flat_o[0] if flat_o else rows.to_flat(o_lat)
    return gated_out(jnp.einsum("bshr,rhd->bshd", o_lat[:, None],
                                w_kvb()[..., dn:]), gate)

  def _mix(self, rowwise, whole, dims, slot_cursors, num_valid, rows):
    """The per-slot work between the two position-wise parts, on
    ``[slots, C, ..]`` whatever rows those ran on: the latent (and index)
    window write, the index scores and their thresholds, the attend.
    ``rowwise`` ``(gate [T, 1, H] or None[, q_abs [T, H, r + dr]])``,
    ``whole`` ``(new, q_abs or None[, k_ix, q_ix, w_ix])``.  Returns
    ``((gate,), o_lat [slots, C, H, r])``, or, where the queries came
    row-wise, ``((gate, o_lat [T, H, r]), None)``: the attend read and wrote
    the flat batch where it lies, and its result is row-wise too."""
    from easyparallellibrary_tpu.kernels.kv_write import kv_write
    from easyparallellibrary_tpu.kernels.slot_attention import (
        slot_attention, slot_attention_selected, slot_attention_window)
    r, ix, scale = dims.kv_lora_rank, dims.indexer, dims.scale
    gate, *flat_q = rowwise
    new, q_abs, *index = whole
    starts = None
    if flat_q:
      (q_abs,) = flat_q
      starts = rows.dst.reshape(rows.slots, rows.chunk)[:, 0]
    latent = self.variable("cache", "cached_latent", missing_slot_cache)
    # Behind a window the leaf is a ring: position p at row p mod its
    # length.
    latent.value, _ = kv_write(latent.value, None, new, None, slot_cursors,
                               impl=self.kv_write_impl,
                               ring=dims.window is not None)
    if dims.window is not None:
      o_lat = slot_attention_window(
          q_abs, latent.value, slot_cursors, num_valid,
          impl=self.slot_attn_impl, window=dims.window, v_width=r,
          scale=scale, starts=starts, chunk=rows.chunk)
    elif ix is not None:
      from easyparallellibrary_tpu.kernels.dsa_index import (
          dsa_index, kth_largest)
      k_ix, q_ix, w_ix = index
      leaf = self.variable("cache", "cached_index", missing_slot_cache)
      leaf.value, _ = kv_write(leaf.value, None, k_ix, None, slot_cursors,
                               num_valid, impl=self.kv_write_impl)
      scores = dsa_index(q_ix, w_ix, leaf.value, slot_cursors, num_valid,
                         impl=self.dsa_index_impl)             # [slots,C,Lc]
      # Each live query's k-th largest score, on the flat batch: the
      # rows at or above it are the query's selection.
      k_each = jnp.clip(rows.positions[:, 0] + 1, 1, ix.top_k)
      threshold = rows.to_slots(
          kth_largest(rows.to_flat(scores), k_each)[:, None])[..., 0]
      o_lat = slot_attention_selected(
          q_abs, latent.value, scores, threshold, slot_cursors, num_valid,
          impl=self.slot_attn_impl, v_width=r, scale=scale, starts=starts)
    else:
      o_lat = slot_attention(q_abs, latent.value, None, slot_cursors,
                             num_valid, impl=self.slot_attn_impl, v_width=r,
                             scale=scale, starts=starts, chunk=rows.chunk)
    o_lat = o_lat.astype(self.cfg.dtype)
    return ((gate, o_lat), None) if flat_q else ((gate,), o_lat)

  def _dense_attend(self, q_nope, q_rope, c, k_r, w_kvb, index, dims):
    """The full forward's attend over its own sequence: ``[B, S, H, dv]``."""
    cfg = self.cfg
    B, S, H, dn = q_nope.shape
    kv_full = jnp.einsum("bsr,rhd->bshd", c, w_kvb)
    k = jnp.concatenate(
        [kv_full[..., :dn],
         jnp.broadcast_to(k_r, (B, S, H, k_r.shape[-1]))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k) * jnp.asarray(
        dims.scale, cfg.dtype)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if dims.window is not None:
      causal &= ~jnp.tril(jnp.ones((S, S), jnp.bool_), -dims.window)
    if index is not None:
      from easyparallellibrary_tpu.kernels.dsa_index import (
          MASKED, kth_largest)
      q_ix, k_ix, w_ix = index
      dots = jnp.einsum("bqhd,bkd->bqhk", q_ix, k_ix,
                        preferred_element_type=jnp.float32)
      index_scores = jnp.where(
          causal, jnp.sum(jax.nn.relu(dots) * w_ix[..., None], 2), MASKED)
      k_each = jnp.minimum(jnp.arange(S) + 1, dims.indexer.top_k)
      threshold = kth_largest(index_scores.reshape(B * S, S),
                              jnp.tile(k_each, B)).reshape(B, S, 1)
      causal = (causal & (index_scores >= threshold))[:, None]
    logits = jnp.where(causal, logits, jnp.asarray(-1e9, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype),
                      kv_full[..., dn:])
