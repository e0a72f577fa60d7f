"""LFM2-MoE — gated short convolutions beside grouped attention, routed
experts with no shared one.

``model_type: lfm2_moe`` (LFM2-8B-A1B): pre-RMSNorm residual layers, ``h =
x + Mixer(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``, no bias anywhere.
Layer ``l`` mixes by what ``layer_types[l]`` names:

* ``conv``: ``[B | C | u] = W_in x`` (three ``d_model``-wide thirds);
  ``z = B * u``; ``c_t = sum_j w_j z_{t - (L - 1) + j}`` over the ``L =
  conv_L_cache`` last products (depthwise, causal, tap ``L - 1`` on the
  current token, no bias); ``out = W_out (C * c)``.  All a request carries
  from one position to the next is the last ``L - 1`` products ``z``;
* ``full_attention``: ``q`` as ``num_heads`` heads, ``k`` and ``v`` as
  ``num_kv_heads``; an RMSNorm over each head of ``q`` and of ``k`` (one
  gain of ``head_dim`` each), rotate-half rotary on all of both at the
  token's index, causal softmax of ``q k^T / sqrt(head_dim)``, query head
  ``h`` on K/V head ``h // (num_heads / num_kv_heads)``.  Cached: ``k``
  AFTER its norm and rotary, and ``v``.

The feed-forward is a dense SiLU-gated MLP in the first
``num_dense_layers`` layers and routed experts without capacity and WITHOUT
a shared expert in every other (models/moe.py: :class:`DroplessMoE` with
``n_shared_experts`` 0; sigmoid scores, a bias that enters the choice
only, the chosen scores over their sum plus 1e-6).  One RMSNorm after the
last layer (the published code's ``embedding_norm``), logits over the TIED
embedding.  ``perfbench/reference/lfm2_moe.py`` holds the same equations in
plain float32 and the tests compare the two.

Serving: slot mode (``decode=True`` with ``slot_cursors``) keeps per slot,
through ``serving/kv_cache.py`` and :meth:`Lfm2MoeConfig.layer_kinds`,

* attention layers: ``cached_key`` / ``cached_value`` under a cursor,
  through ``models.slot_core.slot_cache_attend`` (``[slots, Lc, H_kv x hd]``,
  kept in rows, at the published 8 heads of 64);
* conv layers: ``conv_state`` ``[slots, L - 1, d_model]``, the layer's
  WHOLE state (kind :data:`CONV`).  Like the hybrid's recurrence it has no
  position axis: it advances by exactly ``num_valid`` positions a slot (0
  leaves it bit for bit; a chunk's positions beyond ``num_valid`` neither
  read into nor advance it), a slot that starts a request (``reset``)
  starts from zeros, and no cursor can roll it back: the paged layout,
  prefix caching, speculation and the guarded retry refuse this model
  (``serving/_capabilities.py``).

Precision: the residual stream, the matmuls and the convolution window in
``cfg.dtype`` (the window is two rows of products, not an accumulated
state); norms, rotary angles, the softmax, the router and the three-tap sum
in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp
from flax import linen as nn

from easyparallellibrary_tpu.models.blocks import (
    GatedMLP, RMSNorm, advance_window, boxed, dense, gqa_causal_attention,
    rotary, uniform)
from easyparallellibrary_tpu.models.layer_kinds import ATTENTION, CONV
from easyparallellibrary_tpu.models.moe import DroplessMoE
from easyparallellibrary_tpu.models.slot_core import (
    SplitLayer, child_of, flat_ids, missing_slot_cache, slot_cache_attend,
    slot_layers)
from easyparallellibrary_tpu.ops import Embedding
from easyparallellibrary_tpu.ops.layers import HeldParams

# ``layer_types`` of the published LFM2-8B-A1B: 18 conv, 6 attention.
PUBLISHED_LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
  vocab_size: int = 65536
  d_model: int = 2048
  d_ff: int = 7168                   # the leading dense layers' MLP
  moe_d_ff: int = 1792               # one expert's width
  num_heads: int = 32
  num_kv_heads: int = 8
  conv_L_cache: int = 3
  layer_types: tuple = PUBLISHED_LAYER_TYPES
  num_dense_layers: int = 2
  n_routed_experts: int = 32
  n_shared_experts: int = 0
  num_experts_per_tok: int = 4
  routed_scaling_factor: float = 1.0
  norm_topk_prob: bool = True
  route_norm_eps: float = 1e-6
  rope_theta: float = 1e6
  norm_eps: float = 1e-5
  max_seq_len: int = 4096            # served context; the cache's length
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.bfloat16

  @property
  def num_layers(self) -> int:
    return len(self.layer_types)

  @property
  def head_dim(self) -> int:
    return self.d_model // self.num_heads

  def layer_kinds(self) -> tuple:
    """Per layer, which state it keeps: :data:`CONV` for ``"conv"``,
    ``attention`` (a K/V pair) for ``"full_attention"``."""
    names = {"conv": CONV, "full_attention": ATTENTION}
    unknown = set(self.layer_types) - set(names)
    if unknown:
      raise ValueError(f"layer_types may hold 'conv' and 'full_attention'; "
                       f"got {sorted(unknown)}")
    return tuple(names[t] for t in self.layer_types)


class ShortConv(HeldParams, nn.Module):
  """The gated short convolution (module docstring).  ``in_proj``'s
  columns are ``B | C | u``; the taps are ``[L, d_model]``, tap ``L - 1``
  on the current token."""
  cfg: Lfm2MoeConfig
  decode: bool = False

  @nn.compact
  def __call__(self, h, num_valid=None, reset=None, rows=None):
    cfg = self.cfg
    D = h.shape[-1]
    L = cfg.conv_L_cache
    bcu = dense(cfg, 3 * D, "in_proj")(h)
    gate_b, gate_c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    z = gate_b * u
    conv_w = self.param("conv_w", boxed(uniform(L ** -0.5), 2), (L, D),
                        cfg.param_dtype)
    if self.decode:
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows): the convolution over a slot's window
      # runs as [slots, C, D], everything around it stays flat.
      z = rows.to_slots(z[:, 0])
      state = self.variable("cache", "conv_state", missing_slot_cache)
      window = state.value
      if reset is not None:
        window = jnp.where(reset[:, None, None],
                           jnp.zeros((), window.dtype), window)
    else:
      window = jnp.zeros((z.shape[0], L - 1, D), z.dtype)
    C = z.shape[1]
    full = jnp.concatenate([window.astype(z.dtype), z], axis=1)
    w32 = jnp.asarray(conv_w, jnp.float32)
    conv = sum(full[:, j:j + C].astype(jnp.float32) * w32[j]
               for j in range(L)).astype(cfg.dtype)
    if self.decode:
      state.value = advance_window(full, num_valid, L - 1)
      conv = rows.to_flat(conv)[:, None]
    return dense(cfg, D, "out_proj")(gate_c * conv)


class NormedAttention(nn.Module):
  """Grouped attention whose queries and keys are normalised a head and
  then rotated (module docstring)."""
  cfg: Lfm2MoeConfig
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None

  @nn.compact
  def __call__(self, h, positions=None, slot_cursors=None, num_valid=None,
               rows=None, part=None):
    cfg = self.cfg
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    out_proj = lambda: dense(cfg, cfg.d_model, "o")
    # In slot mode the whole call is its three parts in turn
    # (models/slot_core.py:SplitLayer), ``h`` from the second on the carry.
    if part in (None, "pre"):
      B, S, _ = h.shape
      norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
      q = dense(cfg, H * hd, "q")(h).reshape(B, S, H, hd)
      k = dense(cfg, Hkv * hd, "k")(h).reshape(B, S, Hkv, hd)
      v = dense(cfg, Hkv * hd, "v")(h).reshape(B, S, Hkv, hd)
      q = rotary(norm("q_norm")(q), positions, cfg.rope_theta)
      k = rotary(norm("k_norm")(k), positions, cfg.rope_theta)
      if not self.decode:
        return out_proj()(gqa_causal_attention(q, k, v, cfg.dtype).reshape(
            B, S, H * hd))
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows); the window write and the attend take
      # their operands as [slots, C, ...].
      h = (), tuple(rows.to_slots(t[:, 0]) for t in (q, k, v))
      if part == "pre":
        return h
    if part in (None, "mix"):
      ck = self.variable("cache", "cached_key", missing_slot_cache)
      cv = self.variable("cache", "cached_value", missing_slot_cache)
      out, ck.value, cv.value = slot_cache_attend(
          *h[1], ck.value, cv.value, slot_cursors, cfg.dtype,
          write_impl=self.kv_write_impl, attn_impl=self.slot_attn_impl,
          num_valid=num_valid)
      h = (), out
      if part == "mix":
        return h
    return out_proj()(rows.to_flat(h[1]).reshape(-1, 1, H * hd))


class Lfm2MoeBlock(nn.Module):
  cfg: Lfm2MoeConfig
  kind: str
  dense: bool
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  moe_gmm_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, positions, slot_cursors=None, num_valid=None,
               reset=None, rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
    if self.kind == ATTENTION:
      # In three parts where the step asks (models/slot_core.py:SplitLayer).
      attn = NormedAttention(
          cfg, decode=self.decode, kv_write_impl=self.kv_write_impl,
          slot_attn_impl=self.slot_attn_impl, name="attn")
      if part == "mix":
        return attn(carry, positions, slot_cursors, num_valid, rows, part)
      mixed = attn(carry if part == "post" else norm("norm_in")(x),
                   positions, slot_cursors, num_valid, rows, part)
      if part == "pre":
        return mixed
    else:
      mixed = ShortConv(cfg, decode=self.decode, name="conv")(
          norm("norm_in")(x), num_valid, reset, rows)
    x = x + mixed
    h = norm("norm_ff")(x)
    if self.dense:
      return x + GatedMLP(cfg, name="mlp")(h)
    # Only live positions are routed: a chunk's tail beyond ``num_valid``,
    # an idle slot's positions and the flat batch's padding rows reach no
    # expert.
    return x + DroplessMoE(cfg, moe_gmm_impl=self.moe_gmm_impl,
                           name="moe")(
                               h, None if rows is None else rows.live)


class Lfm2Moe(nn.Module):
  """Decoder-only LM.  ``__call__(ids) -> logits`` is the full forward
  from an empty window; ``decode=True`` with ``slot_cursors`` is the
  serving engine's slot mode (module docstring): token ``i`` of slot ``b``
  sits at position ``slot_cursors[b] + i`` (what rotary turns by),
  ``num_valid`` int32 ``[slots]`` says how many of the chunk's positions
  each slot feeds (``None``: all) — what the attend reads, what the
  experts are handed and how far a convolution window advances —
  ``reset`` bool ``[slots]`` which slots start from an empty window.  In
  slot mode the position-wise layers run on the token-flat batch ``rows``
  describes (models/slot_core.py:SlotRows; every position of every slot when
  none is handed in) and the logits are those of the rows it names."""

  cfg: Lfm2MoeConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, reset=None,
               kv_write_impl=None, slot_attn_impl=None, moe_gmm_impl=None,
               rows=None):
    cfg = self.cfg
    if decode and slot_cursors is None:
      raise ValueError(
          "Lfm2Moe decodes in slot mode only: pass slot_cursors= and a "
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
    tok = Embedding(cfg.vocab_size, cfg.d_model, parallel="none",
                    param_dtype=cfg.param_dtype, name="embed")
    x = tok(ids).astype(cfg.dtype)
    def layer(i, kind):
      block = child_of(lambda parent: Lfm2MoeBlock(
          cfg, kind, dense=i < cfg.num_dense_layers, decode=decode,
          kv_write_impl=kv_write_impl, slot_attn_impl=slot_attn_impl,
          moe_gmm_impl=moe_gmm_impl, name=f"block_{i}", parent=parent))
      # In slot mode a layer takes each row's position from the map of
      # the rows it is handed (``slot_layers``).
      call = lambda mdl, rows, x, **part: block(mdl)(
          x, positions if rows is None else rows.positions, slot_cursors,
          num_valid, reset, rows, **part)
      # An attention layer's K/V window stays outside a two-width step's
      # conditionals; a convolution's few inputs stand inside.
      return SplitLayer(call) if kind == ATTENTION else call
    layers = [layer(i, kind) for i, kind in enumerate(cfg.layer_kinds())]
    x = slot_layers(self, rows, x, layers)
    if decode:
      # The last norm and the head run on the rows that are read.
      x = rows.head_rows(x)
    x = RMSNorm(cfg.norm_eps, cfg.dtype, name="norm_f")(x)
    if return_hidden:
      return x
    return tok.attend(x)
