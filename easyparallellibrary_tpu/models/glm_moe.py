"""GLM-MoE — a sparse-expert decoder with multi-head latent attention.

``model_type: glm4_moe_lite`` (GLM-4.7-Flash; the DeepSeek-V3 block at
another size): pre-RMSNorm residual layers, every mixer multi-head latent
attention (MLA) with rotary positions on a 64-wide slice of each query
head and on ONE key slice all heads share; the feed-forward a dense
SiLU-gated MLP in the first ``first_k_dense`` layers and routed experts
without capacity beside a shared one in every other (models/moe.py:
:class:`DroplessMoE`); a final RMSNorm and an UNTIED head.
``perfbench/reference/glm4_moe_lite.py`` holds the same equations in plain
float32 and the tests compare the two.  The multi-token-prediction module
of the published checkpoint takes no part in next-token logits and is not
built.

MLA, per position with hidden state ``x``: ``c_q = RMSNorm(W_qa x)``;
``[q_nope | q_rope] = W_qb c_q`` per head; ``[c_kv | k_r] = W_kva x``;
``c = RMSNorm(c_kv)``; rotary on ``q_rope`` and ``k_r``; ``[k_nope | v] =
W_kvb c`` per head; ``score = (q_nope . k_nope + q_rope . k_r) /
sqrt(nope + rope)``, causal softmax, ``W_o`` over the heads' ``sum p v``.

Two forms of the same attention:

* the full forward (``decode=False``) EXPANDS ``k_nope`` and ``v`` for the
  whole sequence, as the reference does;
* slot mode (``decode=True``, the serving engine) keeps per position only
  the LATENT ``[c | rot(k_r)]`` — ``kv_lora_rank + qk_rope_head_dim``
  values, one cache leaf a layer ``[slots, Lc, 1, 576]`` (kind
  :data:`LATENT` in ``serving/kv_cache.py``) where expanded keys and
  values would take ``heads x (256 + 256)`` — and attends in the ABSORBED
  form: ``q' = [q_nope W_kvb^K | q_rope]``, ``score = q' . latent``,
  ``o = (sum p latent[:rank]) W_kvb^V``.  The heads become query rows
  against one head whose values are the leading ``kv_lora_rank`` columns
  of its keys (``kernels/kv_write.py`` and ``kernels/slot_attention.py``
  in their one-leaf form).  Equal to the expanded form up to rounding:
  matrix products re-associated.

A latent row, like a K/V row, is addressed under a cursor, so a partly
valid chunk is harmless; but nothing here can restore a cache the paged
layout, prefix caching or speculation would need (no paged pool of latent
rows is built): ``serving/_capabilities.py`` refuses them.

Precision: the residual stream and the matmuls in ``cfg.dtype``; norms,
rotary angles, the softmax and the router (scores, choice, weights) in
float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from easyparallellibrary_tpu.models.gpt import (
    SplitLayer, _missing_slot_cache, child_of, flat_ids, slot_layers)
from easyparallellibrary_tpu.models.jamba import (
    GatedMLP, RMSNorm, _boxed, _dense)
from easyparallellibrary_tpu.models.moe import DroplessMoE
from easyparallellibrary_tpu.ops import Embedding
from easyparallellibrary_tpu.ops.layers import HeldParams

# What a layer keeps per slot (serving/kv_cache.py reads
# ``cfg.layer_kinds()``): one latent leaf, no K/V pair.
LATENT = "latent"


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
  vocab_size: int = 154880
  num_layers: int = 47
  d_model: int = 2048
  d_ff: int = 10240                  # the leading dense layers' MLP
  moe_d_ff: int = 1536               # one expert's width
  num_heads: int = 20
  q_lora_rank: int = 768
  kv_lora_rank: int = 512
  qk_nope_head_dim: int = 192
  qk_rope_head_dim: int = 64
  v_head_dim: int = 256
  n_routed_experts: int = 64
  n_shared_experts: int = 1
  num_experts_per_tok: int = 4
  first_k_dense: int = 1
  routed_scaling_factor: float = 1.8
  norm_topk_prob: bool = True
  route_norm_eps: float = 1e-20      # added to the chosen scores' sum
  rope_theta: float = 1e6
  rms_norm_eps: float = 1e-5
  max_seq_len: int = 4096            # served context; the cache's length
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.bfloat16

  @property
  def latent_dim(self) -> int:
    """Values a position keeps: the compressed K/V and the shared rotary
    key."""
    return self.kv_lora_rank + self.qk_rope_head_dim

  @property
  def qk_head_dim(self) -> int:
    return self.qk_nope_head_dim + self.qk_rope_head_dim

  def layer_kinds(self) -> tuple:
    """Every layer keeps one latent leaf."""
    return (LATENT,) * self.num_layers


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
  (:func:`glm_latent_dims`); a model whose layers differ
  (models/dots3_note.py) hands each layer its own.  ``q_rescale`` /
  ``kv_rescale`` multiply the two normed latents; ``gate`` adds a sigmoid
  gate, one value a head, on the heads' outputs; ``window`` limits a query
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
  gate: bool = False
  window: Optional[int] = None
  indexer: Optional[IndexerDims] = None

  @property
  def scale(self) -> float:
    return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

  @property
  def latent_dim(self) -> int:
    return self.kv_lora_rank + self.qk_rope_head_dim


def glm_latent_dims(cfg) -> LatentDims:
  """The one latent attention of a :class:`GlmMoeConfig`."""
  return LatentDims(
      num_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
      kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
      qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
      rope_theta=cfg.rope_theta)


def rotary(x, positions, theta: float):
  """Rotate-half rotary embedding over ALL of ``x``'s last axis: ``x``
  ``[B, S, H, d]``, ``positions`` int ``[B, S]``; pair ``i`` is ``(x[i],
  x[i + d/2])`` turned by ``position * theta^(-2i/d)``.  float32 inside."""
  d = x.shape[-1]
  freq = jnp.exp(jnp.arange(d // 2, dtype=jnp.float32)
                 * (-2.0 * jnp.log(theta) / d))
  ang = positions.astype(jnp.float32)[:, :, None, None] * freq
  cos, sin = jnp.cos(ang), jnp.sin(ang)
  x32 = x.astype(jnp.float32)
  a, b = x32[..., :d // 2], x32[..., d // 2:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                         -1).astype(x.dtype)


class LayerNorm(HeldParams, nn.Module):
  """``(x - mean) * rsqrt(var + eps) * g + b`` in float32, gain and bias
  float32 parameters (the indexer's key norm)."""
  eps: float
  dtype: Any

  @nn.compact
  def __call__(self, x):
    g = self.param("scale", _boxed(nn.initializers.ones_init(), 1),
                   (x.shape[-1],), jnp.float32)
    b = self.param("bias", _boxed(nn.initializers.zeros_init(), 1),
                   (x.shape[-1],), jnp.float32)
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, -1, keepdims=True)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + self.eps) * g + b
    return y.astype(self.dtype)


def _rotate_leading(x, positions, theta: float, width: int):
  """Rotary on the leading ``width`` of ``x`` ``[B, S, H, d]``'s last
  axis, the rest as it is."""
  return jnp.concatenate(
      [rotary(x[..., :width], positions, theta), x[..., width:]], -1)


class LatentAttention(HeldParams, nn.Module):
  """Multi-head latent attention (module docstring), shared by every model
  that has one: ``cfg`` gives ``d_model``, ``rms_norm_eps`` and the dtypes,
  ``dims`` the attention's own sizes and options (``None``: ``cfg`` is a
  :class:`GlmMoeConfig` and says them itself)."""
  cfg: Any
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  dims: Optional[LatentDims] = None
  dsa_index_impl: Optional[str] = None

  @nn.compact
  def __call__(self, h, positions=None, slot_cursors=None, num_valid=None,
               rows=None, part=None):
    cfg = self.cfg
    dims = self.dims if self.dims is not None else glm_latent_dims(cfg)
    H, r = dims.num_heads, dims.kv_lora_rank
    dn, dr, dv = (dims.qk_nope_head_dim, dims.qk_rope_head_dim,
                  dims.v_head_dim)
    ix = dims.indexer
    held = []

    def w_kvb():
      if not held:
        held.append(jnp.asarray(self.param(
            "kv_b", _boxed(nn.initializers.normal(stddev=0.02), 2),
            (r, H * (dn + dv)), cfg.param_dtype), cfg.dtype).reshape(
                r, H, dn + dv))
      return held[0]

    def gated_out(out, gate):
      if gate is not None:
        out = (out.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
      return _dense(cfg, cfg.d_model, "o")(
          out.reshape(*out.shape[:2], H * dv))

    # In slot mode the whole call is its three parts in turn
    # (models/gpt.py:SplitLayer), ``h`` from the second on the carry.
    if part in (None, "pre"):
      B, S, _ = h.shape
      norm = lambda name, rescale=1.0: RMSNorm(
          cfg.rms_norm_eps, cfg.dtype, rescale, name=name)
      c_q = norm("q_norm", dims.q_rescale)(
          _dense(cfg, dims.q_lora_rank, "q_a")(h))
      q = _dense(cfg, H * (dn + dr), "q_b")(c_q).reshape(B, S, H, dn + dr)
      q_nope = q[..., :dn]
      q_rope = rotary(q[..., dn:], positions, dims.rope_theta)
      kv = _dense(cfg, r + dr, "kv_a")(h)
      c = norm("kv_norm", dims.kv_rescale)(kv[..., :r])
      k_r = rotary(kv[..., None, r:], positions, dims.rope_theta)  # [B,S,1,dr]
      if ix is not None:
        # The indexer: index queries from the query latent, ONE index key a
        # position from the layer's input, a weight an index head.
        q_ix = _rotate_leading(
            _dense(cfg, ix.num_heads * ix.head_dim, "index_q")(c_q).reshape(
                B, S, ix.num_heads, ix.head_dim),
            positions, dims.rope_theta, ix.rope_dim)
        k_ix = _rotate_leading(
            LayerNorm(ix.layer_norm_eps, cfg.dtype, name="index_k_norm")(
                _dense(cfg, ix.head_dim, "index_k")(h))[:, :, None],
            positions, dims.rope_theta, ix.rope_dim)[:, :, 0]
        w_ix = _dense(cfg, ix.num_heads, "index_w")(h).astype(jnp.float32)
      # One value a head, from the layer's input, on the heads' outputs.
      gate = None if not dims.gate else jax.nn.sigmoid(
          _dense(cfg, H, "gate")(h).astype(jnp.float32))
      if not self.decode:
        return gated_out(self._dense_attend(
            q_nope, q_rope, c, k_r, w_kvb(),
            None if ix is None else (q_ix, k_ix, w_ix), dims), gate)
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/gpt.py:SlotRows); the window write and the attend take
      # their operands as [slots, C, ...], everything around them stays
      # flat.
      new = jnp.concatenate([c[:, :, None], k_r], -1)        # [T,1,1,r+dr]
      q_abs = jnp.concatenate(
          [jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb()[..., :dn]), q_rope],
          -1)[:, 0]                                          # [T,H,r+dr]
      index = () if ix is None else tuple(
          rows.to_slots(t[:, 0]) for t in (k_ix, q_ix, w_ix))
      # The selected and the windowed kernels read a tile's query rows
      # where they lie in the flat batch; every other attend takes them
      # in [slots, C] order.
      if (dims.window is not None or ix is not None) and (
          self.slot_attn_impl != "reference" and rows.dst is not None):
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
    (gate,), o_lat = h
    # the attend's latent rows [slots, C, H, r] -> [rows, 1, H, dv]
    return gated_out(jnp.einsum("bshr,rhd->bshd",
                                rows.to_flat(o_lat)[:, None],
                                w_kvb()[..., dn:]), gate)

  def _mix(self, rowwise, whole, dims, slot_cursors, num_valid, rows):
    """The per-slot work between the two position-wise parts, on
    ``[slots, C, ..]`` whatever rows those ran on: the latent (and index)
    window write, the index scores and their thresholds, the attend.
    ``rowwise`` ``(gate [T, 1, H] or None[, q_abs [T, H, r + dr]])``,
    ``whole`` ``(new, q_abs or None[, k_ix, q_ix, w_ix])``.  Returns
    ``((gate,), o_lat [slots, C, H, r])``."""
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
    latent = self.variable("cache", "cached_latent", _missing_slot_cache)
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
      leaf = self.variable("cache", "cached_index", _missing_slot_cache)
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
                             scale=scale)
    return (gate,), o_lat.astype(self.cfg.dtype)

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


class GlmMoeBlock(nn.Module):
  cfg: GlmMoeConfig
  dense: bool
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  moe_gmm_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, positions, slot_cursors=None, num_valid=None,
               rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    # In three parts where the step asks (models/gpt.py:SplitLayer).
    latent = LatentAttention(
        cfg, decode=self.decode, kv_write_impl=self.kv_write_impl,
        slot_attn_impl=self.slot_attn_impl, name="latent")
    if part == "mix":
      return latent(carry, positions, slot_cursors, num_valid, rows, part)
    mixed = latent(carry if part == "post" else norm("norm_in")(x),
                   positions, slot_cursors, num_valid, rows, part)
    if part == "pre":
      return mixed
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


class GlmMoe(nn.Module):
  """Decoder-only LM.  ``__call__(ids) -> logits`` is the full forward
  (expanded attention); ``decode=True`` with ``slot_cursors`` is the
  serving engine's slot mode (module docstring): token ``i`` of slot ``b``
  sits at position ``slot_cursors[b] + i``, ``num_valid`` int32
  ``[slots]`` says how many of the chunk's positions each slot feeds
  (``None``: all) — what the attend reads and what the experts are
  handed.  In slot mode the position-wise layers run on the token-flat
  batch ``rows`` describes (models/gpt.py:SlotRows; every position of
  every slot when none is handed in) and the logits are those of the
  rows it names."""

  cfg: GlmMoeConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, kv_write_impl=None,
               slot_attn_impl=None, moe_gmm_impl=None, rows=None):
    cfg = self.cfg
    if decode and slot_cursors is None:
      raise ValueError(
          "GlmMoe decodes in slot mode only: pass slot_cursors= and a slot "
          "cache from serving.kv_cache.allocate_kv_cache (the serving "
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
    def layer(i):
      block = child_of(lambda parent: GlmMoeBlock(
          cfg, dense=i < cfg.first_k_dense, decode=decode,
          kv_write_impl=kv_write_impl, slot_attn_impl=slot_attn_impl,
          moe_gmm_impl=moe_gmm_impl, name=f"block_{i}", parent=parent))
      # In slot mode a layer takes each row's position from the map of
      # the rows it is handed (``slot_layers``); its latent leaf stays
      # outside a two-width step's conditionals.
      return SplitLayer(lambda mdl, rows, x, **part: block(mdl)(
          x, positions if rows is None else rows.positions, slot_cursors,
          num_valid, rows, **part))
    layers = [layer(i) for i in range(cfg.num_layers)]
    x = slot_layers(self, rows, x, layers)
    if decode:
      # The last norm and the head run on the rows that are read.
      x = rows.head_rows(x)
    x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
    if return_hidden:
      return x
    return _dense(cfg, cfg.vocab_size, "lm_head")(x)
