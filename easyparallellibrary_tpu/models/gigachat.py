"""GigaChat 3.5 — three gated delta-rule linear-attention layers in four
beside one gated latent attention, four norms a layer, clamped MLPs, and
one chip's share of the routed experts.

``model_type: gigachat3_5`` (GigaChat3.5-432B-A28B, 40 layers of 7168):
layer ``l`` mixes by multi-head latent attention where ``l`` is in
``full_attention_layers`` (3, 7, .., 39) and by the gated delta rule
elsewhere; ``layernorm_type: pre_post`` puts a norm before AND after each
mixer and each MLP:

    h = x + N2(Mixer(N1(x)));   y = h + N4(FF(N3(h)))

The linear-attention layer (``GigaChat35GatedDeltaNet``; Yang, Kautz,
Hatamizadeh, "Gated Delta Networks", 2024, in the form Qwen3-Next's public
modelling code ships, whose shapes the config's ``linear_*`` keys give one
for one), for a position's normed input ``x``:

    [q, k, v, z] = x W_qkvz          (widths Hk dk, Hk dk, Hv dv, Hv dv)
    [b, a]       = x W_ba            (Hv, Hv: one a value head)
    (q, k, v)   <- silu(conv(concat(q, k, v)))   depthwise, causal, K taps
    q <- l2norm(q) / sqrt(dk),  k <- l2norm(k)   a head; key head h // (Hv/Hk)
    beta = sigmoid(b),   g = -exp(A_log) softplus(a + dt_bias)   float32
    S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q
    y = gated_norm(o, z) a head;   out = y W_o

``S`` is ``[dk, dv]`` float32 a value head (kernels/gdn_scan.py holds the
recurrence in its two forms, one position and a chunk).

What the config NAMES but does not define is elementwise and ASSUMED, each
from the published form named here (the other reading in brackets; the
configuration's file of the benchmark says the same under ``assumed``):

* ``gated_norm`` (``gated_rmsnorm_sigmoid_zero_centered``,
  ``linear_sigmoid_gate_scale`` 2): ``RMSNorm(o)`` over a head with gain
  ``1 + w``, times ``2 sigmoid(z)`` (Qwen3-Next's gated norm with a SiLU
  gate and gain ``w`` is the other reading);
* the model's norm (``ZeroCenteredGatedNorm``, ``layernorm_gating_weight``
  2): ``RMSNorm`` with gain ``2 sigmoid(w)``, which is 1 at ``w`` = 0 (gain
  ``1 + w``, Gemma's zero-centred norm, is the other);
* the attention's gate (``gated_attention``): Qiu et al. 2025's elementwise
  sigmoid gate on the heads' outputs from a projection ``[d_model, H dv]``
  of the layer's normed input, as Qwen3-Next ships it (one value a head,
  dots3-note's, is the other);
* ``swiglu_limit`` 10: the gate's pre-activation at most 10, the up
  projection within +-10, before the product, in the dense MLP, the shared
  expert and the routed experts (gpt-oss clamps so; a clamp of the product
  is the other);
* ``use_mla_scaling_factor``: DeepSeek-V3's YaRN ``mscale(factor,
  mscale_all_dim)`` squared on the softmax scale (no factor is the other);
* the router: sigmoid scores with a selection bias, DeepSeek-V3's
  ``noaux_tc``, which the ``n_group`` / ``topk_group`` /
  ``routed_scaling_factor`` / ``norm_topk_prob`` keys belong to.

The latent attention is models/blocks.py's :class:`LatentAttention`
(models/glm_moe.py's docstring holds its equations) with YaRN's
frequencies (theta 1e5, factor 8 over 32768) and the elementwise gate.
The feed-forward is a dense SiLU-gated MLP in the first ``first_k_dense``
layers and :class:`models.moe.DroplessMoE` elsewhere (``experts_held =
(first, count)``: one chip's share, as models/dots3_note.py).  A final
norm and an UNTIED head.  ``perfbench/reference/gigachat3_5.py`` holds the
same equations in plain float32 and the tests compare the two.  The two
multi-token-prediction layers of the published model take no part in
next-token logits and are not built.

Training mode (``decode=False``) is the full forward of whole sequences
from zero state: the chunked delta rule over the sequence
(``kernels.gdn_scan.gdn_sequence``), the latent attention expanded.  Slot
mode (``decode=True``, the serving engine) keeps, per layer KIND
(``serving/kv_cache.py``):

* ``gated_delta`` (a linear layer): ``conv_state`` ``[slots, K - 1, 2 Hk dk
  + Hv dv]`` (the convolution's last inputs) and ``delta_state`` float32
  ``[slots, Hv, dk, dv]``, neither of which grows with the context.  Both
  advance by exactly ``num_valid`` positions a slot (0 leaves them bit for
  bit), and a slot that starts a request (``reset``) starts from zero:
  the paged layout, prefix caching, speculation and the guarded retry
  refuse this model (``serving/_capabilities.py``);
* ``latent`` (a full layer): one latent leaf ``[slots, Lc, 1, 576]`` under
  the slot's cursor, as GLM's.

Precision: the residual stream and the matmuls in ``cfg.dtype``; norms,
rotary angles, the softmax, the gates' sigmoids, the convolution, ``g``,
``beta``, the delta rule with its state, and the router in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from easyparallellibrary_tpu.models.blocks import (
    GatedMLP, LatentAttention, LatentDims, YarnDims, boxed, dense, uniform)
from easyparallellibrary_tpu.models.layer_kinds import GATED_DELTA, LATENT
from easyparallellibrary_tpu.models.moe import DroplessMoE
from easyparallellibrary_tpu.models.slot_core import (
    SplitLayer, child_of, flat_ids, missing_slot_cache, slot_layers)
from easyparallellibrary_tpu.ops import Embedding
from easyparallellibrary_tpu.ops.layers import HeldParams


@dataclasses.dataclass(frozen=True)
class GigaChatConfig:
  vocab_size: int = 128256
  num_layers: int = 40
  full_attention_layers: Tuple[int, ...] = tuple(range(3, 40, 4))
  d_model: int = 7168
  d_ff: int = 18432                  # the leading dense layers' MLP
  moe_d_ff: int = 2048               # one expert's width
  # the latent attention of a full layer
  num_heads: int = 64
  q_lora_rank: int = 1536
  kv_lora_rank: int = 512
  qk_nope_head_dim: int = 128
  qk_rope_head_dim: int = 64
  v_head_dim: int = 128
  rope_theta: float = 1e5
  rope_factor: float = 8.0
  rope_original_max: int = 32768
  rope_beta_fast: float = 32.0
  rope_beta_slow: float = 1.0
  rope_mscale: float = 1.0
  rope_mscale_all_dim: float = 1.0
  rope_scale_softmax: bool = True    # use_mla_scaling_factor
  # the gated delta rule of a linear layer
  linear_num_key_heads: int = 32
  linear_num_value_heads: int = 64
  linear_key_head_dim: int = 128
  linear_value_head_dim: int = 128
  linear_conv_kernel_dim: int = 4
  linear_sigmoid_gate_scale: float = 2.0
  linear_attn_o_norm_eps: float = 1e-6
  layernorm_gating_weight: float = 2.0
  swiglu_limit: float = 10.0
  # experts
  n_routed_experts: int = 256        # the router's width
  experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
  n_shared_experts: int = 1
  num_experts_per_tok: int = 8
  first_k_dense: int = 3
  routed_scaling_factor: float = 2.5
  norm_topk_prob: bool = True
  route_norm_eps: float = 1e-20
  rms_norm_eps: float = 1e-6
  max_seq_len: int = 4096            # served context; the cache's length
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.bfloat16

  # No layer attends behind a window.
  sliding_window = 0

  @property
  def latent_dim(self) -> int:
    """Values a position of a full layer keeps: the compressed K/V and the
    shared rotary key."""
    return self.kv_lora_rank + self.qk_rope_head_dim

  @property
  def linear_conv_dim(self) -> int:
    """Channels of a linear layer's convolution: queries, keys, values."""
    return (2 * self.linear_num_key_heads * self.linear_key_head_dim
            + self.linear_num_value_heads * self.linear_value_head_dim)

  def layer_kinds(self) -> tuple:
    """Per layer, what it keeps in a slot: a full layer its latent leaf, a
    linear layer its convolution window and its matrix state."""
    return tuple(LATENT if i in self.full_attention_layers else GATED_DELTA
                 for i in range(self.num_layers))

  def latent_dims(self) -> LatentDims:
    """The one latent attention of the model."""
    return LatentDims(
        num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
        kv_lora_rank=self.kv_lora_rank,
        qk_nope_head_dim=self.qk_nope_head_dim,
        qk_rope_head_dim=self.qk_rope_head_dim, v_head_dim=self.v_head_dim,
        rope_theta=self.rope_theta, gate="elementwise",
        yarn=YarnDims(self.rope_factor, self.rope_original_max,
                      self.rope_beta_fast, self.rope_beta_slow,
                      self.rope_mscale, self.rope_mscale_all_dim,
                      self.rope_scale_softmax))

  # What :class:`models.moe.DroplessMoE` is told beyond the sizes: both of
  # an expert's pre-activations held to ``swiglu_limit``.
  @property
  def expert_gate(self):
    limit = self.swiglu_limit
    return lambda gate: jax.nn.silu(jnp.minimum(gate, limit))

  @property
  def expert_up(self):
    limit = self.swiglu_limit
    return lambda up: jnp.clip(up, -limit, limit)


class SigmoidGainNorm(HeldParams, nn.Module):
  """``x * rsqrt(mean(x^2) + eps) * (weight sigmoid(w))`` in float32: the
  model's ``ZeroCenteredGatedNorm`` as assumed (module docstring), whose
  gain is 1 at ``w`` = 0; ``w`` a float32 parameter."""
  eps: float
  weight: float
  dtype: Any

  @nn.compact
  def __call__(self, x):
    w = self.param("scale", boxed(nn.initializers.zeros_init(), 1),
                   (x.shape[-1],), jnp.float32)
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + self.eps) * (self.weight * jax.nn.sigmoid(w))
    return y.astype(self.dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
  """The inverse softplus of a step drawn log-uniform in [1e-3, 1e-1]."""
  dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
               * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
  return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
  """``log`` of a decay rate drawn uniform in (0, 16)."""
  return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3,
                                    16.0)).astype(dtype)


class GatedDeltaNet(HeldParams, nn.Module):
  """The gated delta-rule mixer (module docstring).  The convolution's
  taps are ``[K, 2 Hk dk + Hv dv]``, tap ``K - 1`` on the current token;
  ``A_log`` and ``dt_bias`` float32, one a value head; the gated norm's
  ``w`` float32 ``[dv]``, shared by the heads.  In slot mode the call is
  its three parts in turn (models/slot_core.py:SplitLayer): the
  projections on the flat batch; the convolution and the recurrence on
  ``[slots, C, ..]``, ONE call of ``kernels.gdn_scan.gdn_scan``, OUTSIDE a
  two-width step's conditionals (the state is 4.19 MB a slot: 537 MB a
  layer at 128 slots, which no conditional may copy); the gated norm and
  the output projection on the flat batch."""
  cfg: GigaChatConfig
  decode: bool = False
  gdn_scan_impl: Optional[str] = None

  @nn.compact
  def __call__(self, h, num_valid=None, reset=None, rows=None, part=None):
    cfg = self.cfg
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    K, W = cfg.linear_conv_kernel_dim, cfg.linear_conv_dim
    f32 = jnp.float32

    def taps():
      return jnp.asarray(self.param(
          "conv_w", boxed(uniform(K ** -0.5), 2), (K, W), cfg.param_dtype),
                         f32)

    def out(z, o):
      """The gated norm a head and the output projection: ``z``, ``o``
      ``[.., 1 or S, Hv dv]``."""
      w = self.param("norm", boxed(nn.initializers.zeros_init(), 1), (dv,),
                     f32)
      heads = lambda t: t.astype(f32).reshape(*t.shape[:-1], Hv, dv)
      o = heads(o)
      y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                            + cfg.linear_attn_o_norm_eps) * (1.0 + w)
      y = y * (cfg.linear_sigmoid_gate_scale * jax.nn.sigmoid(heads(z)))
      return dense(cfg, cfg.d_model, "o")(
          y.astype(cfg.dtype).reshape(*z.shape))

    if part in (None, "pre"):
      qkvz = dense(cfg, W + Hv * dv, "in_proj")(h)
      ba = dense(cfg, 2 * Hv, "ba")(h).astype(f32)
      qkv, z = qkvz[..., :W], qkvz[..., W:]
      a_log = self.param("A_log", boxed(_a_log_init, 1), (Hv,), f32)
      dt_bias = self.param("dt_bias", boxed(_dt_bias_init, 1), (Hv,), f32)
      beta = jax.nn.sigmoid(ba[..., :Hv])
      g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)
      if not self.decode:
        from easyparallellibrary_tpu.kernels.gdn_scan import (
            convolved, gdn_sequence)
        B, S, _ = h.shape
        full = jnp.concatenate([jnp.zeros((B, K - 1, W), qkv.dtype), qkv], 1)
        state_shape = (B, Hv, cfg.linear_key_head_dim, dv)
        return out(z, gdn_sequence(convolved(full, taps(), S), g, beta,
                                   state_shape))
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows): the convolution over a slot's window
      # and the recurrence over its state run as [slots, C, ..].
      h = (z[:, 0],), tuple(rows.to_slots(t[:, 0]) for t in (qkv, g, beta))
      if part == "pre":
        return h
    if part in (None, "mix"):
      from easyparallellibrary_tpu.kernels.gdn_scan import gdn_scan
      (z,), (qkv, g, beta) = h
      conv_var = self.variable("cache", "conv_state", missing_slot_cache)
      state_var = self.variable("cache", "delta_state", missing_slot_cache)
      # The convolution over the slot's window stands inside the scan's
      # contract: the kernel has the chunk's rows in VMEM anyway.
      o, state_var.value, conv_var.value = gdn_scan(
          state_var.value, conv_var.value, qkv, taps(), g, beta,
          num_valid=num_valid, reset=reset, impl=self.gdn_scan_impl)
      h = (z,), o
      if part == "mix":
        return h
    (z,), o = h
    return out(z[:, None], rows.to_flat(o)[:, None])


class GigaChatBlock(nn.Module):
  cfg: GigaChatConfig
  kind: str
  dense: bool
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  gdn_scan_impl: Optional[str] = None
  moe_gmm_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, positions, slot_cursors=None, num_valid=None,
               reset=None, rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: SigmoidGainNorm(
        cfg.rms_norm_eps, cfg.layernorm_gating_weight, cfg.dtype, name=name)
    # In three parts where the step asks (models/slot_core.py:SplitLayer).
    if self.kind == LATENT:
      latent = LatentAttention(
          cfg, cfg.latent_dims(), decode=self.decode,
          kv_write_impl=self.kv_write_impl,
          slot_attn_impl=self.slot_attn_impl, name="latent")
      mixer = lambda h: latent(h, positions, slot_cursors, num_valid, rows,
                               part)
    else:
      linear = GatedDeltaNet(cfg, decode=self.decode,
                             gdn_scan_impl=self.gdn_scan_impl, name="linear")
      mixer = lambda h: linear(h, num_valid, reset, rows, part)
    if part == "mix":
      return mixer(carry)
    mixed = mixer(carry if part == "post" else norm("norm_in")(x))
    if part == "pre":
      return mixed
    x = x + norm("norm_mix_out")(mixed)
    h = norm("norm_ff")(x)
    if self.dense:
      ff = GatedMLP(cfg, limit=cfg.swiglu_limit, name="mlp")(h)
    else:
      # Only live positions are routed (models/glm_moe.py).
      ff = DroplessMoE(cfg, moe_gmm_impl=self.moe_gmm_impl, name="moe")(
          h, None if rows is None else rows.live)
    return x + norm("norm_ff_out")(ff)


class GigaChat(nn.Module):
  """Decoder-only LM with :class:`models.glm_moe.GlmMoe`'s surface and
  :class:`models.jamba.Jamba`'s state arguments: ``__call__(ids) ->
  logits`` is the full forward from zero state; ``decode=True`` with
  ``slot_cursors`` is the serving engine's slot mode (module docstring):
  ``num_valid`` int32 ``[slots]`` says how many of the chunk's positions
  each slot feeds (what the attend reads, what the experts are handed, how
  far the recurrent state advances), ``reset`` bool ``[slots]`` which slots
  start from zero state."""

  cfg: GigaChatConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, reset=None,
               kv_write_impl=None, slot_attn_impl=None, gdn_scan_impl=None,
               moe_gmm_impl=None, rows=None):
    cfg = self.cfg
    if decode and slot_cursors is None:
      raise ValueError(
          "GigaChat decodes in slot mode only: pass slot_cursors= and a "
          "slot cache from serving.kv_cache.allocate_kv_cache (the serving "
          "engine does)")
    if slot_cursors is not None and not decode:
      raise ValueError("slot_cursors is a decode-mode argument (serving "
                       "engine); pass decode=True")
    B, S = ids.shape
    if decode:
      rows, ids = flat_ids(ids, slot_cursors, num_valid, rows)
      positions = rows.positions
    else:
      positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = Embedding(cfg.vocab_size, cfg.d_model, parallel="none",
                  param_dtype=cfg.param_dtype, name="embed")(ids).astype(
                      cfg.dtype)
    def layer(i, kind):
      block = child_of(lambda parent: GigaChatBlock(
          cfg, kind=kind, dense=i < cfg.first_k_dense, decode=decode,
          kv_write_impl=kv_write_impl, slot_attn_impl=slot_attn_impl,
          gdn_scan_impl=gdn_scan_impl, moe_gmm_impl=moe_gmm_impl,
          name=f"block_{i}", parent=parent))
      # Every layer's mixer owns a leaf no conditional of a two-width step
      # may copy: a latent leaf that grows with the context, or a matrix
      # state that does not and is larger than it.
      return SplitLayer(lambda mdl, rows, x, **part: block(mdl)(
          x, positions if rows is None else rows.positions, slot_cursors,
          num_valid, reset, rows, **part))
    layers = [layer(i, kind) for i, kind in enumerate(cfg.layer_kinds())]
    x = slot_layers(self, rows, x, layers)
    if decode:
      # The last norm and the head run on the rows that are read.
      x = rows.head_rows(x)
    x = SigmoidGainNorm(cfg.rms_norm_eps, cfg.layernorm_gating_weight,
                        cfg.dtype, name="norm_f")(x)
    if return_hidden:
      return x
    return dense(cfg, cfg.vocab_size, "lm_head")(x)
