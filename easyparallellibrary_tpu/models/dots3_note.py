"""dots3-note — latent attention that selects its rows in the full layers,
a second latent attention of other widths behind a window in the others,
a headwise output gate, and one chip's share of the routed experts.

``model_type: dots3_note`` (dots3-note-prev, 46 layers, 288B-A17B with its
towers): pre-RMSNorm residual layers; ``layer_types[l]`` says which of two
multi-head latent attentions layer ``l`` mixes by, both
models/blocks.py's :class:`LatentAttention` at sizes of their own
(:meth:`Dots3NoteConfig.latent_dims`):

* a FULL layer (``full_attention``): 128 heads of 128 | 64 rotary | 128
  value on a 512-wide latent, theta 8e7, and an INDEXER (DeepSeek-V3.2's):
  64 index heads of 128 from the query latent, one 128-wide index key a
  position (LayerNorm, rotary on its leading 64), a weight a head; query
  ``t`` scores every ``s <= t`` by ``sum_j w_j(t) ReLU(qI_j(t) . kI(s))``
  and attends the ``index_topk`` best ONLY;
* a WINDOW layer (``sliding_attention``): 64 heads of 192 | 64 | 128 on a
  1024-wide latent, theta 5e4, no indexer; ``s`` is visible iff ``0 <= t -
  s < sliding_window`` (the window counts the query's own position);
* both: the two normed latents rescaled by ``sqrt(d_model / rank)``
  (``apply_mla_qkv_lora_rescale``), and a headwise gate ``sigmoid(W_g u)``,
  one value a head from the layer's normed input, on the heads' outputs
  before ``W_o``.

The feed-forward is a dense SiLU-gated MLP in the first ``first_k_dense``
layers and routed experts without capacity beside a shared one in every
other (models/moe.py :class:`DroplessMoE`, the ``noaux_tc`` router with
one group).  ``experts_held = (first, count)`` tells every expert layer
which of the router's experts this chip holds, one of several a layer is
divided over: the router keeps its published width and its experts per
token, and the layer computes its own experts' part of the sum.  A final
RMSNorm and an UNTIED head.  ``perfbench/reference/dots3_note.py`` holds
the same equations in plain float32 and the tests compare the two.  The
vision and audio towers and the prediction module of the published model
take no part in next-token logits from token ids and are not built.

Slot mode (``decode=True``, the serving engine) keeps, per layer KIND
(``serving/kv_cache.py``):

* ``sparse_latent`` (a full layer): the latent leaf ``[slots, Lc, 1,
  576]`` as GLM's, and the indexer's keys ``[slots, Lc, 128]`` (kept in
  rows: one lane tile), both under the slot's cursor.  A step scores its
  live queries against the index leaf (``kernels/dsa_index.py``), finds
  each query's threshold (``kth_largest``) and attends the selected rows
  in the absorbed form (``slot_attn_sel``);
* ``window_latent`` (a window layer): ONE leaf ``[slots, R, 1, 1088]``
  that does not grow with the served context, a RING: position ``p`` at
  row ``p mod R``, ``R`` = window + chunk up to the attend's 128-row tile
  (:func:`ring_length`), written by ``kv_write(..., ring=True)`` and read
  by ``slot_attn_win``.

Nothing here can restore a cache the paged layout, prefix caching,
speculation or the guarded retry would need: ``serving/_capabilities.py``
refuses them.

Precision: the residual stream and the matmuls in ``cfg.dtype``; norms,
the LayerNorm, rotary angles, the softmax, the index scores, the gates'
sigmoids and the router in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from easyparallellibrary_tpu.models.blocks import (
    GatedMLP, IndexerDims, LatentAttention, LatentDims, RMSNorm, dense,
    ring_length)
from easyparallellibrary_tpu.models.layer_kinds import (
    FULL, SLIDING, SPARSE_LATENT, WINDOW_LATENT)
from easyparallellibrary_tpu.models.moe import DroplessMoE
from easyparallellibrary_tpu.models.slot_core import (
    SplitLayer, child_of, flat_ids, slot_layers)
from easyparallellibrary_tpu.ops import Embedding


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
  vocab_size: int = 152064
  layer_types: Tuple[str, ...] = (FULL, FULL, SLIDING, SLIDING, SLIDING)
  d_model: int = 5120
  d_ff: int = 13824                  # the leading dense layers' MLP
  moe_d_ff: int = 1536               # one expert's width
  # full layers
  num_heads: int = 128
  q_lora_rank: int = 1024
  kv_lora_rank: int = 512
  qk_nope_head_dim: int = 128
  qk_rope_head_dim: int = 64
  v_head_dim: int = 128
  rope_theta: float = 8e7
  index_n_heads: int = 64
  index_head_dim: int = 128
  index_topk: int = 2048
  # window layers
  sliding_window: int = 513
  swa_num_heads: int = 64
  swa_q_lora_rank: int = 1024
  swa_kv_lora_rank: int = 1024
  swa_qk_nope_head_dim: int = 192
  swa_qk_rope_head_dim: int = 64
  swa_v_head_dim: int = 128
  swa_rope_theta: float = 5e4
  # experts
  n_routed_experts: int = 256        # the router's width
  experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
  n_shared_experts: int = 1
  num_experts_per_tok: int = 8
  first_k_dense: int = 1
  routed_scaling_factor: float = 1.0
  norm_topk_prob: bool = True
  route_norm_eps: float = 1e-20
  rms_norm_eps: float = 1e-5
  max_seq_len: int = 4096            # served context; the cache's length
  ring_tile: int = 128               # the window rings' row tile
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.bfloat16

  @property
  def num_layers(self) -> int:
    return len(self.layer_types)

  def latent_dims(self, layer_type: str) -> LatentDims:
    """The latent attention of a full or of a window layer."""
    rescale = lambda rank: float(self.d_model / rank) ** 0.5
    if layer_type == FULL:
      return LatentDims(
          num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
          kv_lora_rank=self.kv_lora_rank,
          qk_nope_head_dim=self.qk_nope_head_dim,
          qk_rope_head_dim=self.qk_rope_head_dim,
          v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
          q_rescale=rescale(self.q_lora_rank),
          kv_rescale=rescale(self.kv_lora_rank), gate=True,
          indexer=IndexerDims(self.index_n_heads, self.index_head_dim,
                              self.index_topk, self.qk_rope_head_dim))
    if layer_type == SLIDING:
      return LatentDims(
          num_heads=self.swa_num_heads, q_lora_rank=self.swa_q_lora_rank,
          kv_lora_rank=self.swa_kv_lora_rank,
          qk_nope_head_dim=self.swa_qk_nope_head_dim,
          qk_rope_head_dim=self.swa_qk_rope_head_dim,
          v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
          q_rescale=rescale(self.swa_q_lora_rank),
          kv_rescale=rescale(self.swa_kv_lora_rank), gate=True,
          window=self.sliding_window)
    raise ValueError(f"layer type {layer_type!r}: {FULL} or {SLIDING}")

  def layer_kinds(self) -> tuple:
    """Per layer, what it keeps in a slot: a full layer its latent and
    index leaves, a window layer its ring."""
    kinds = {FULL: SPARSE_LATENT, SLIDING: WINDOW_LATENT}
    return tuple(kinds[t] for t in self.layer_types)

  def ring_length(self, chunk: int) -> int:
    return ring_length(self.sliding_window, chunk, self.ring_tile)


class Dots3NoteBlock(nn.Module):
  cfg: Dots3NoteConfig
  layer_type: str
  dense: bool
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  moe_gmm_impl: Optional[str] = None
  dsa_index_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, positions, slot_cursors=None, num_valid=None,
               rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    # In three parts where the step asks (models/slot_core.py:SplitLayer).
    latent = LatentAttention(
        cfg, cfg.latent_dims(self.layer_type), decode=self.decode,
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
    # Only live positions are routed (models/glm_moe.py).
    return x + DroplessMoE(cfg, moe_gmm_impl=self.moe_gmm_impl,
                           name="moe")(
                               h, None if rows is None else rows.live)


class Dots3Note(nn.Module):
  """Decoder-only LM with :class:`models.glm_moe.GlmMoe`'s surface:
  ``__call__(ids) -> logits`` is the full forward (expanded attention, the
  selection and the window as masks); ``decode=True`` with
  ``slot_cursors`` is the serving engine's slot mode (module docstring)."""

  cfg: Dots3NoteConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, kv_write_impl=None,
               slot_attn_impl=None, moe_gmm_impl=None, dsa_index_impl=None,
               rows=None):
    cfg = self.cfg
    if decode and slot_cursors is None:
      raise ValueError(
          "Dots3Note decodes in slot mode only: pass slot_cursors= and a "
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
    def layer(i, layer_type):
      block = child_of(lambda parent: Dots3NoteBlock(
          cfg, layer_type=layer_type, dense=i < cfg.first_k_dense,
          decode=decode, kv_write_impl=kv_write_impl,
          slot_attn_impl=slot_attn_impl, moe_gmm_impl=moe_gmm_impl,
          dsa_index_impl=dsa_index_impl, name=f"block_{i}", parent=parent))
      # In slot mode a layer takes each row's position from the map of
      # the rows it is handed (``slot_layers``); its latent, index and
      # ring leaves stay outside a two-width step's conditionals.
      return SplitLayer(lambda mdl, rows, x, **part: block(mdl)(
          x, positions if rows is None else rows.positions, slot_cursors,
          num_valid, rows, **part))
    layers = [layer(i, t) for i, t in enumerate(cfg.layer_types)]
    x = slot_layers(self, rows, x, layers)
    if decode:
      # The last norm and the head run on the rows that are read.
      x = rows.head_rows(x)
    x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
    if return_hidden:
      return x
    return dense(cfg, cfg.vocab_size, "lm_head")(x)
