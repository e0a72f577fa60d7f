"""SmallThinker — grouped attention behind a window in three layers of
four beside full layers with NO positions, experts routed from the layer's
INPUT.

``model_type: smallthinker`` (SmallThinker-21BA3B-Instruct, 52 layers):
pre-RMSNorm residual layers, no bias anywhere.  With ``x`` the layer's
input:

* ``r = x W_r``: the router's logits, read from the layer's INPUT, before
  the attention and before any norm ("router placed before attention");
* ``a = RMSNorm_1(x)``; ``q = a W_q`` as ``num_heads`` heads of
  ``head_dim`` (28 x 128 = 3584, NOT ``d_model``: the head size is the
  model's own), ``k``, ``v`` as ``num_kv_heads`` (4), query head ``h`` on
  K/V head ``h // 7``.  ``rope_layout[l]`` says whether the layer rotates
  ``q`` and ``k`` (rotate-half over all of ``head_dim``, theta 1.5e6) or
  gives them NO positions; ``window_layout[l]`` whether position ``t`` sees
  ``s`` with ``t - sliding_window < s <= t`` (its own among them) or every
  ``s <= t``.  The published layouts are one: ``[0, 1, 1, 1] x 13``, a full
  layer without positions, then three window layers with rotary;
* ``h = x + concat(heads) W_o``; ``m = RMSNorm_2(h)``;
* ``y = sum_i w_i W_down,i (relu(m W_gate,i) * (m W_up,i))`` over the
  ``top6`` of ``r``, ``w = softmax(r[chosen])`` in float32 (models/moe.py
  :class:`DroplessMoE` told its routing rule, :func:`softmax_topk_route`,
  its gate, ReLU, and its router's input, ``x``); the layer gives ``h +
  y``.  No shared expert, no dense layer.

One RMSNorm after the last layer and an UNTIED head.
``perfbench/reference/smallthinker.py`` holds the same equations in plain
float32 and the tests compare the two.

Slot mode (``decode=True``, the serving engine) keeps, per layer KIND
(``serving/kv_cache.py``), TWO kinds of K/V in one cache:

* ``attention`` (a full layer): ``cached_key`` / ``cached_value`` under the
  slot's cursor, ``[slots, Lc, H_kv x hd]`` kept in rows, through
  ``models.slot_core.slot_cache_attend`` (``kv_write``, ``slot_attn``);
* ``window_kv`` (a window layer): the same pair as a RING of ``R``
  rows whatever the served context, position ``p`` at row ``p mod R``,
  ``R`` = window - 1 + chunk up to the attend's 128-row tile
  (:meth:`SmallThinkerConfig.ring_length`: 4,224 at window 4096, chunk
  32), written by ``kv_write(..., ring=True)`` and read by
  ``slot_attn_kvwin`` (kernels/slot_attention.py), each resolved by its own
  rule (``kv_win_write_impl`` / ``kv_win_attn_impl``).

A ring overwrites what a cursor moved back would need again: the paged
layout, prefix caching, speculation and the guarded retry refuse this
model (``serving/_capabilities.py:check_kv_window``).

Precision: the residual stream and the matmuls in ``cfg.dtype``; norms,
rotary angles, the softmaxes and the router's logits in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from easyparallellibrary_tpu.models.blocks import (
    RMSNorm, dense, gqa_causal_attention, ring_length, rotary)
from easyparallellibrary_tpu.models.layer_kinds import ATTENTION, WINDOW_KV
from easyparallellibrary_tpu.models.moe import DroplessMoE, softmax_topk_route
from easyparallellibrary_tpu.models.slot_core import (
    SplitLayer, child_of, flat_ids, missing_slot_cache, slot_cache_attend,
    slot_layers)
from easyparallellibrary_tpu.ops import Embedding

# ``sliding_window_layout`` and ``rope_layout`` of the published model.
PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
  vocab_size: int = 151936
  d_model: int = 2560
  num_heads: int = 28
  num_kv_heads: int = 4
  head_dim: int = 128                # the model's own: not d_model / heads
  moe_d_ff: int = 768                # one expert's width
  n_routed_experts: int = 64
  n_shared_experts: int = 0
  num_experts_per_tok: int = 6
  sliding_window: int = 4096
  window_layout: Tuple[int, ...] = PUBLISHED_LAYOUT   # 1: behind the window
  rope_layout: Tuple[int, ...] = PUBLISHED_LAYOUT     # 1: rotary; 0: none
  rope_theta: float = 1.5e6
  norm_eps: float = 1e-6
  max_seq_len: int = 16384           # served context; a full leaf's length
  ring_tile: int = 128               # the window rings' row tile
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.bfloat16

  def __post_init__(self):
    if len(self.window_layout) != len(self.rope_layout):
      raise ValueError(
          f"window_layout ({len(self.window_layout)} layers) and rope_layout "
          f"({len(self.rope_layout)}) name different depths")
    if self.num_heads % self.num_kv_heads:
      raise ValueError(f"{self.num_heads} query heads do not share "
                       f"{self.num_kv_heads} K/V heads in whole groups")

  @property
  def num_layers(self) -> int:
    return len(self.window_layout)

  # What :class:`models.moe.DroplessMoE` is told beyond the sizes: the
  # routing rule (no bias in the tree) and the gate's activation.
  expert_route = staticmethod(softmax_topk_route)
  expert_gate = staticmethod(jax.nn.relu)

  def layer_kinds(self) -> tuple:
    """Per layer, what it keeps in a slot: a window layer its K/V pair as
    a ring, a full layer the pair under the cursor."""
    return tuple(WINDOW_KV if w else ATTENTION for w in self.window_layout)

  def ring_length(self, chunk: int) -> int:
    return ring_length(self.sliding_window, chunk, self.ring_tile)


class GroupedAttention(nn.Module):
  """The layer's attention (module docstring): ``window`` positions or
  all (``None``), rotary or none."""
  cfg: SmallThinkerConfig
  window: Optional[int]
  rope: bool
  decode: bool = False
  write_impl: Optional[str] = None     # this layer KIND's resolved pair
  attn_impl: Optional[str] = None

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
      q = dense(cfg, H * hd, "q")(h).reshape(B, S, H, hd)
      k = dense(cfg, Hkv * hd, "k")(h).reshape(B, S, Hkv, hd)
      v = dense(cfg, Hkv * hd, "v")(h).reshape(B, S, Hkv, hd)
      if self.rope:
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k, positions, cfg.rope_theta)
      if not self.decode:
        return out_proj()(gqa_causal_attention(
            q, k, v, cfg.dtype, self.window).reshape(B, S, H * hd))
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows); the window write and the attend take
      # their operands as [slots, C, ...].
      h = (), tuple(rows.to_slots(t[:, 0]) for t in (q, k, v))
      if part == "pre":
        return h
    if part in (None, "mix"):
      q, k, v = h[1]
      ck = self.variable("cache", "cached_key", missing_slot_cache)
      cv = self.variable("cache", "cached_value", missing_slot_cache)
      if self.window is None:
        out, ck.value, cv.value = slot_cache_attend(
            q, k, v, ck.value, cv.value, slot_cursors, cfg.dtype,
            write_impl=self.write_impl, attn_impl=self.attn_impl,
            num_valid=num_valid)
      else:
        # The pair is a ring: position p at row p mod its length.
        from easyparallellibrary_tpu.kernels.kv_write import kv_write
        from easyparallellibrary_tpu.kernels.slot_attention import (
            slot_attention_kv_window)
        ck.value, cv.value = kv_write(
            ck.value, cv.value, k, v, slot_cursors, num_valid,
            impl=self.write_impl, ring=True)
        out = slot_attention_kv_window(
            q, ck.value, cv.value, slot_cursors, num_valid,
            impl=self.attn_impl, window=self.window).astype(cfg.dtype)
      h = (), out
      if part == "mix":
        return h
    return out_proj()(rows.to_flat(h[1]).reshape(-1, 1, H * hd))


class SmallThinkerBlock(nn.Module):
  cfg: SmallThinkerConfig
  window: Optional[int]
  rope: bool
  decode: bool = False
  write_impl: Optional[str] = None
  attn_impl: Optional[str] = None
  moe_gmm_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, positions, slot_cursors=None, num_valid=None,
               rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype, name=name)
    # In three parts where the step asks (models/slot_core.py:SplitLayer).
    attn = GroupedAttention(
        cfg, self.window, self.rope, decode=self.decode,
        write_impl=self.write_impl, attn_impl=self.attn_impl, name="attn")
    if part == "mix":
      return attn(carry, positions, slot_cursors, num_valid, rows, part)
    mixed = attn(carry if part == "post" else norm("norm_in")(x),
                 positions, slot_cursors, num_valid, rows, part)
    if part == "pre":
      return mixed
    h = x + mixed
    # The router reads the layer's INPUT ``x``, un-normed, not the stream
    # the experts read; only live positions are routed (models/glm_moe.py).
    return h + DroplessMoE(cfg, moe_gmm_impl=self.moe_gmm_impl, name="moe")(
        norm("norm_ff")(h), None if rows is None else rows.live, router_in=x)


class SmallThinker(nn.Module):
  """Decoder-only LM with :class:`models.lfm2_moe.Lfm2Moe`'s surface:
  ``__call__(ids) -> logits`` is the full forward (the window as a mask);
  ``decode=True`` with ``slot_cursors`` is the serving engine's slot mode
  (module docstring): token ``i`` of slot ``b`` sits at position
  ``slot_cursors[b] + i`` (what rotary turns by, where the layer has any),
  ``num_valid`` int32 ``[slots]`` says how many of the chunk's positions
  each slot feeds.  ``kv_win_write_impl`` / ``kv_win_attn_impl`` are the
  window layers' resolved lowerings, beside the full layers'
  ``kv_write_impl`` / ``slot_attn_impl``."""

  cfg: SmallThinkerConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, kv_write_impl=None,
               slot_attn_impl=None, moe_gmm_impl=None,
               kv_win_write_impl=None, kv_win_attn_impl=None, rows=None):
    cfg = self.cfg
    if decode and slot_cursors is None:
      raise ValueError(
          "SmallThinker decodes in slot mode only: pass slot_cursors= and a "
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
    def layer(i, windowed, rope):
      write, attn = ((kv_win_write_impl, kv_win_attn_impl) if windowed
                     else (kv_write_impl, slot_attn_impl))
      block = child_of(lambda parent: SmallThinkerBlock(
          cfg, cfg.sliding_window if windowed else None, bool(rope),
          decode=decode, write_impl=write, attn_impl=attn,
          moe_gmm_impl=moe_gmm_impl, name=f"block_{i}", parent=parent))
      # In slot mode a layer takes each row's position from the map of
      # the rows it is handed (``slot_layers``); every layer's K/V write
      # and attend stay outside a two-width step's conditionals.
      return SplitLayer(lambda mdl, rows, x, **part: block(mdl)(
          x, positions if rows is None else rows.positions, slot_cursors,
          num_valid, rows, **part))
    layers = [layer(i, w, r) for i, (w, r) in enumerate(
        zip(cfg.window_layout, cfg.rope_layout))]
    x = slot_layers(self, rows, x, layers)
    if decode:
      # The last norm and the head run on the rows that are read.
      x = rows.head_rows(x)
    x = RMSNorm(cfg.norm_eps, cfg.dtype, name="norm_f")(x)
    if return_hidden:
      return x
    return dense(cfg, cfg.vocab_size, "lm_head")(x)
