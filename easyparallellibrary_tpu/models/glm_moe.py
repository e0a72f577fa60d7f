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

Two forms of the same attention (models/blocks.py ``LatentAttention``,
which models/dots3_note.py builds at other sizes):

* the full forward (``decode=False``) EXPANDS ``k_nope`` and ``v`` for the
  whole sequence, as the reference does;
* slot mode (``decode=True``, the serving engine) keeps per position only
  the LATENT ``[c | rot(k_r)]`` — ``kv_lora_rank + qk_rope_head_dim``
  values, one cache leaf a layer ``[slots, Lc, 1, 576]`` (kind
  ``latent``, models/layer_kinds.py) where expanded keys and
  values would take ``heads x (256 + 256)`` — and attends in the ABSORBED
  form: ``q' = [q_nope W_kvb^K | q_rope]``, ``score = q' . latent``,
  ``o = (sum p latent[:rank]) W_kvb^V``.  The heads become query rows
  against one head whose values are the leading ``kv_lora_rank`` columns
  of its keys (``kernels/kv_write.py`` and ``kernels/slot_attention.py``
  in their one-leaf form).  Equal to the expanded form up to rounding:
  matrix products re-associated.

``model_type: glm_moe_dsa`` (GLM-5) is the same block with DeepSeek-V3.2's
indexer in EVERY layer (``index_topk`` > 0: ``LatentDims(indexer=...)``,
layer kind ``sparse_latent``, an index leaf beside the latent one; a query
attends the ``index_topk`` rows of largest index score, all of them while
its position is below that) and, where the config says so
(``experts_held``), a share of the routed experts.  ``expert_axis`` names
the mesh axis a serving engine divided that share over: the expert layers
then exchange rows between the chips (models/moe.py ``exchanged_experts``).
``perfbench/reference/glm_moe_dsa.py`` holds those equations.

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
from typing import Any, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from easyparallellibrary_tpu.models.blocks import (
    GatedMLP, IndexerDims, LatentAttention, LatentDims, RMSNorm, dense)
from easyparallellibrary_tpu.models.layer_kinds import (
    FULL, LATENT, SPARSE_LATENT)
from easyparallellibrary_tpu.models.moe import DroplessMoE
from easyparallellibrary_tpu.models.slot_core import (
    SplitLayer, child_of, flat_ids, slot_layers)
from easyparallellibrary_tpu.ops import Embedding


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
  # ``index_topk`` > 0 (``model_type: glm_moe_dsa``, GLM-5): every layer
  # selects the rows it attends through DeepSeek-V3.2's indexer.
  index_topk: int = 0
  index_n_heads: int = 32
  index_head_dim: int = 128
  n_routed_experts: int = 64         # the router's width
  experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
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

  # No layer attends behind a window (what ``serving/window_rows``
  # counts rows up to: none).
  sliding_window = 0

  def layer_kinds(self) -> tuple:
    """Every layer keeps one latent leaf, and with an indexer its index
    leaf beside it."""
    kind = SPARSE_LATENT if self.index_topk else LATENT
    return (kind,) * self.num_layers

  def latent_dims(self, layer_type: str = FULL) -> LatentDims:
    """The one latent attention of the model (the cache asks a selecting
    layer's by its layer type)."""
    if layer_type != FULL:
      raise ValueError(f"layer type {layer_type!r}: every layer is {FULL}")
    return glm_latent_dims(self)


def glm_latent_dims(cfg) -> LatentDims:
  """The one latent attention of a :class:`GlmMoeConfig`."""
  return LatentDims(
      num_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
      kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
      qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
      rope_theta=cfg.rope_theta,
      indexer=None if not cfg.index_topk else IndexerDims(
          cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
          cfg.qk_rope_head_dim))


class GlmMoeBlock(nn.Module):
  cfg: GlmMoeConfig
  dense: bool
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  moe_gmm_impl: Optional[str] = None
  dsa_index_impl: Optional[str] = None
  expert_axis: Optional[str] = None

  @nn.compact
  def __call__(self, x, positions, slot_cursors=None, num_valid=None,
               rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    # In three parts where the step asks (models/slot_core.py:SplitLayer).
    latent = LatentAttention(
        cfg, glm_latent_dims(cfg), decode=self.decode,
        kv_write_impl=self.kv_write_impl,
        slot_attn_impl=self.slot_attn_impl,
        dsa_index_impl=self.dsa_index_impl, name="latent")
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
                           expert_axis=self.expert_axis, name="moe")(
                               h, None if rows is None else rows.live)


class GlmMoe(nn.Module):
  """Decoder-only LM.  ``__call__(ids) -> logits`` is the full forward
  (expanded attention); ``decode=True`` with ``slot_cursors`` is the
  serving engine's slot mode (module docstring): token ``i`` of slot ``b``
  sits at position ``slot_cursors[b] + i``, ``num_valid`` int32
  ``[slots]`` says how many of the chunk's positions each slot feeds
  (``None``: all) — what the attend reads and what the experts are
  handed.  In slot mode the position-wise layers run on the token-flat
  batch ``rows`` describes (models/slot_core.py:SlotRows; every position of
  every slot when none is handed in) and the logits are those of the
  rows it names.  ``expert_axis`` names the mesh axis the held experts
  are divided over when the call stands inside a ``shard_map`` over it
  (a serving engine on such a mesh; models/moe.py ``exchanged_experts``)."""

  cfg: GlmMoeConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, kv_write_impl=None,
               slot_attn_impl=None, moe_gmm_impl=None, dsa_index_impl=None,
               expert_axis=None, rows=None):
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
          moe_gmm_impl=moe_gmm_impl, dsa_index_impl=dsa_index_impl,
          expert_axis=expert_axis, name=f"block_{i}", parent=parent))
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
    return dense(cfg, cfg.vocab_size, "lm_head")(x)
