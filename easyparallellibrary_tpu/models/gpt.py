"""GPT — the flagship decoder-only transformer family.

The reference keeps its model zoo in the external FastNN repo
(/root/reference/README.md:18); this framework bundles the models because
the reference's benchmark matrix (configs 2/4/5) needs them.  The model is
written TPU-first:

  * bf16 compute / fp32 params by default (MXU-friendly),
  * every weight carries GSPMD partitioning metadata: Megatron-style
    tensor parallelism over the ``model`` axis (QKV/MLP-in column, proj/
    MLP-out row, vocab-sharded embedding + tied head),
  * activation sharding constraints over ``(data, seq)`` so sequence/
    context parallelism composes,
  * optional `jax.checkpoint` per block (gradient checkpointing),
  * optional MoE blocks (expert parallelism) — see models/moe.py,
  * blocks can be stacked + scanned for pipeline parallelism.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants
# ``slot_step_logits`` is re-exported for ONE caller that may not be edited
# here: perfbench/selection_witness.py:93 imports it from this module
# (ROADMAP D17); everything else imports models/slot_core.py.
from easyparallellibrary_tpu.models.slot_core import (
    flat_ids, missing_slot_cache, paged_cache_attend, slot_cache_attend,
    slot_layers, slot_step_logits)  # noqa: F401
from easyparallellibrary_tpu.ops import Dense, Embedding
from easyparallellibrary_tpu.ops.layers import HeldParams, LayerNorm
from easyparallellibrary_tpu.ops.losses import (
    distributed_sparse_softmax_cross_entropy_with_logits,
)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
  vocab_size: int = 32768
  num_layers: int = 12
  num_heads: int = 16
  d_model: int = 1024
  d_ff: int = 4096
  max_seq_len: int = 1024
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.float32
  tensor_parallel: bool = False      # shard weights over the model axis
  remat: bool = False                # jax.checkpoint every block
  # nothing | dots | dots_flash | everything.  dots_flash = dots + saved
  # flash-kernel outputs: the policy to pair with attn_impl="pallas_flash"
  # under remat (plain dots re-runs the flash forward in the backward;
  # measured 0.336 vs 0.487 MFU at bench shape).
  remat_policy: str = "nothing"
  tie_embeddings: bool = True
  z_loss: float = 0.0
  dropout_rate: float = 0.0
  # MoE (expert parallelism): every `moe_every`-th block uses experts
  # (moe_every=1 -> every block, =2 -> blocks 1,3,5..., as in Switch).
  num_experts: int = 0
  moe_every: int = 2
  capacity_factor: float = 1.25
  moe_aux_weight: float = 0.01
  moe_top_k: int = 1
  # "einsum" (GSPMD chooses collectives) | "a2a" (explicit all_to_all
  # dispatch/combine over the expert axis — the reference's M6-style EP
  # dataflow; see models/moe.py).
  moe_impl: str = "einsum"
  # Sequence parallelism: constrain activations over the seq axis.
  seq_parallel: bool = False
  attn_impl: str = "xla"             # xla | pallas_flash | ring
  # Pipeline parallelism: blocks grouped into stages over the stage axis.
  pipeline_stages: int = 1
  num_micro_batch: int = 1
  pipeline_schedule: str = ""   # "" = from Config pipeline.strategy
  pipeline_debug_sequential: bool = False  # ground-truth path for tests
  # Interleaved pipeline (reference config pipeline.num_stages_per_device):
  # blocks split into K chained passes, so each device holds K
  # non-adjacent block chunks.  On the vmapped engines this is the
  # circular WEIGHT DISTRIBUTION only; on the shard_map engine
  # (pipeline.engine="smap") K > 1 upgrades the schedule to true
  # Megatron-interleaved 1F1B (parallel/pipeline_interleaved.py) with
  # the ramp shrunk to 2(S-1) + (K-1)S one-chunk ticks.
  pipeline_interleave: int = 1
  # Explicit per-chunk block counts (len == stages*interleave), e.g. from
  # the auto-parallel planner; overrides the default even/ceil layout.
  stage_plan: Optional[tuple] = None
  # Chunked cross-entropy: compute tied-head logits + CE over sequence
  # chunks of this many tokens inside a rematerialized scan, so the
  # [B, S, vocab] logits tensor never materializes (peak-memory win at
  # large vocab; ~3% extra FLOPs from the logit-matmul recompute).
  # 0 = off.  Requires tie_embeddings and no pipeline.
  loss_chunk: int = 0


def _act_spec(cfg: GPTConfig, ndim: int = 3) -> P:
  seq = constants.SEQ_AXIS if cfg.seq_parallel else None
  if ndim == 3:
    return P(constants.DATA_AXIS, seq, None)
  return P(constants.DATA_AXIS, seq)


from easyparallellibrary_tpu.utils.sharding import constrain as _constrain  # noqa: E402


def _dense_causal_attention(q, k, v, dtype):
  """Reference XLA attention: bf16 matmuls, fp32 softmax, causal mask.
  Shared by the training path and the KV-cache prefill so the two can
  never drift apart numerically."""
  S = q.shape[1]
  scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(dtype)
  logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
  mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
  logits = jnp.where(mask[None, None], logits,
                     jnp.asarray(-1e9, logits.dtype))
  probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
  return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dtype), v)


class CausalSelfAttention(nn.Module):
  cfg: GPTConfig
  decode: bool = False
  # Resolved lowerings of the slot cache's window write and of the
  # attend over it (kernels/kv_write.py, kernels/slot_attention.py);
  # None = resolve from the shapes when traced.
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, slot_cursors=None, paged_info=None,
               num_valid=None, rows=None):
    cfg = self.cfg
    B, S, D = x.shape
    H = cfg.num_heads
    head_dim = D // H
    col = "column" if cfg.tensor_parallel else "none"
    row = "row" if cfg.tensor_parallel else "none"

    qkv = Dense(3 * D, parallel=col, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="qkv")(x)
    if rows is not None:
      # Slot mode: x is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py ``SlotRows``); the window write and the
      # attend own per-slot
      # state and take their operands as [slots, C, H, hd], each gathered
      # as its own block of whole rows (the three column blocks of the
      # fused projection, cut before any reshape to heads).
      q, k, v = (
          _constrain(
              rows.to_slots(qkv[:, 0, i * D:(i + 1) * D]).reshape(
                  rows.slots, rows.chunk, H, head_dim),
              P(constants.DATA_AXIS, None, constants.MODEL_AXIS, None))
          for i in range(3))
    elif (cfg.attn_impl == "pallas_flash" and paged_info is None
          and not self.decode):
      # The flash kernels read q, k and v where the projection wrote them
      # (kernels/flash_attention.py:flash_attention_qkv): nothing to cut.
      q = k = v = None
    else:
      qkv = qkv.reshape(B, S, 3, H, head_dim)
      # Heads ride the model axis (column-parallel QKV already produced
      # the sharded feature dim; this re-expresses it on the head dim).
      qkv = _constrain(qkv, P(constants.DATA_AXIS, None, None,
                              constants.MODEL_AXIS, None))
      q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    if paged_info is not None:
      # Flat-token paged decode (serving/engine.py paged mode): x is
      # [T, 1, D] — one token per batch row — and attention routes
      # through the slot block tables instead of a contiguous cache.
      ck = self.variable("cache", "cached_key", missing_slot_cache)
      cv = self.variable("cache", "cached_value", missing_slot_cache)
      out, ck.value, cv.value = paged_cache_attend(
          q[:, 0], k[:, 0], v[:, 0], ck.value, cv.value, paged_info,
          cfg.dtype)
      out = out[:, None]
    elif self.decode:
      out = self._decode_attend(q, k, v, slot_cursors, num_valid)
      if rows is not None:
        out = rows.to_flat(out)[:, None]
    elif cfg.attn_impl == "ring":
      from easyparallellibrary_tpu.sequence.ring_attention import (
          ring_attention)
      out = ring_attention(q, k, v, causal=True)
    elif cfg.attn_impl == "ulysses":
      from easyparallellibrary_tpu.sequence.ulysses import ulysses_attention
      out = ulysses_attention(q, k, v, causal=True)
    elif cfg.attn_impl == "pallas_flash":
      from easyparallellibrary_tpu.kernels.flash_attention import (
          flash_attention_qkv)
      out = flash_attention_qkv(qkv, H, causal=True)
    elif cfg.attn_impl == "xla":
      out = _dense_causal_attention(q, k, v, cfg.dtype)
    else:
      # A typo'd impl silently falling back to dense attention would
      # mislabel any benchmark run on top of it.
      raise ValueError(
          f"attn_impl must be 'xla', 'pallas_flash', 'ring' or "
          f"'ulysses'; got {cfg.attn_impl!r}")

    out = out.reshape(B, S, D)
    out = Dense(D, parallel=row, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="proj")(out)
    return _constrain(out, _act_spec(cfg))

  def _decode_attend(self, q, k, v, slot_cursors=None, num_valid=None):
    """KV-cached attention (VERDICT round-1 item 10).

    Two cache layouts share :func:`slot_cache_attend` as their math:

    * Legacy (``slot_cursors=None``) — one whole request per call, cache
      ``[B, max_seq_len, H, hd]`` with one scalar cursor for the whole
      batch.  Prefill (S > 1): normal causal attention; the prompt's K/V
      land in the cache.  Step (S == 1): append this token's K/V at the
      cursor and attend over the valid prefix — O(1) forwards per token
      instead of the full-forward-per-token fallback.
    * Slot mode (``slot_cursors`` = int32 ``[B]`` vector) — the serving
      engine's layout: B is a SLOT index (requests at different decode
      depths coexist in one batch), the cache is slot-indexed and
      preallocated externally (serving/kv_cache.py; this module never
      allocates it), and every call is one fused chunk step — prefill
      chunks and single decode tokens distinguished purely by how many
      of the C token positions each slot's cursor math treats as live.
    """
    cfg = self.cfg
    B, S, H, hd = q.shape
    L = cfg.max_seq_len

    if slot_cursors is not None:
      ck = self.variable("cache", "cached_key", missing_slot_cache)
      cv = self.variable("cache", "cached_value", missing_slot_cache)
      out, ck.value, cv.value = slot_cache_attend(
          q, k, v, ck.value, cv.value, slot_cursors, cfg.dtype,
          write_impl=self.kv_write_impl, attn_impl=self.slot_attn_impl,
          num_valid=num_valid)
      return out

    ck = self.variable("cache", "cached_key",
                       lambda: jnp.zeros((B, L, H, hd), cfg.dtype))
    cv = self.variable("cache", "cached_value",
                       lambda: jnp.zeros((B, L, H, hd), cfg.dtype))
    ci = self.variable("cache", "cache_index",
                       lambda: jnp.zeros((), jnp.int32))

    if S > 1:  # prefill
      ck.value = jax.lax.dynamic_update_slice(
          ck.value, k.astype(cfg.dtype), (0, 0, 0, 0))
      cv.value = jax.lax.dynamic_update_slice(
          cv.value, v.astype(cfg.dtype), (0, 0, 0, 0))
      ci.value = jnp.int32(S)
      return _dense_causal_attention(q, k, v, cfg.dtype)

    # One-token step == slot attention with a batch-uniform cursor.
    cursors = jnp.broadcast_to(ci.value, (B,))
    out, ck.value, cv.value = slot_cache_attend(
        q, k, v, ck.value, cv.value, cursors, cfg.dtype,
        write_impl=self.kv_write_impl, attn_impl=self.slot_attn_impl)
    ci.value = ci.value + 1
    return out


class MLP(nn.Module):
  cfg: GPTConfig

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    col = "column" if cfg.tensor_parallel else "none"
    row = "row" if cfg.tensor_parallel else "none"
    h = Dense(cfg.d_ff, parallel=col, dtype=cfg.dtype,
              param_dtype=cfg.param_dtype, name="wi")(x)
    h = nn.gelu(h)
    h = Dense(cfg.d_model, parallel=row, dtype=cfg.dtype,
              param_dtype=cfg.param_dtype, name="wo")(h)
    return _constrain(h, _act_spec(cfg))


class Block(nn.Module):
  cfg: GPTConfig
  use_moe: bool = False
  deterministic: bool = True
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, slot_cursors=None, paged_info=None,
               num_valid=None, rows=None):
    cfg = self.cfg
    drop = nn.Dropout(rate=cfg.dropout_rate,
                      deterministic=self.deterministic
                      or cfg.dropout_rate == 0.0)
    y = LayerNorm(dtype=cfg.dtype, name="ln1")(x)
    x = x + drop(CausalSelfAttention(cfg, decode=self.decode,
                                     kv_write_impl=self.kv_write_impl,
                                     slot_attn_impl=self.slot_attn_impl,
                                     name="attn")(y, slot_cursors,
                                                  paged_info, num_valid,
                                                  rows))
    y = LayerNorm(dtype=cfg.dtype, name="ln2")(x)
    if self.use_moe:
      from easyparallellibrary_tpu.models.moe import MoEMLP
      x = x + drop(MoEMLP(cfg, top_k=cfg.moe_top_k, impl=cfg.moe_impl,
                          name="moe")(y))
    else:
      x = x + drop(MLP(cfg, name="mlp")(y))
    return _constrain(x, _act_spec(cfg))


class StageBlocks(nn.Module):
  """One pipeline stage = a contiguous chunk of transformer blocks.

  Stage *structure* must be homogeneous so stages can be stacked and
  vmapped over the stage axis; with MoE, the expert pattern repeats per
  stage.  Heterogeneous (uneven) models pass ``n_active`` — a per-stage
  block count (traced scalar under the stage vmap): blocks at index
  ``i >= n_active`` are computed but masked to identity, so a stage can
  own fewer blocks than the allocated maximum.  This is the TPU answer to
  the reference's arbitrary per-stage taskgraphs
  (epl/parallel/graph_editor.py:423-443): SPMD needs one program for all
  stages, so heterogeneity is data (the mask), not structure.
  """

  cfg: GPTConfig
  blocks_per_stage: int
  deterministic: bool = True

  @nn.compact
  def __call__(self, x, n_active=None):
    cfg = self.cfg
    for i in range(self.blocks_per_stage):
      use_moe = cfg.num_experts > 0 and \
          (i % cfg.moe_every == cfg.moe_every - 1)
      y = Block(cfg, use_moe=use_moe, deterministic=self.deterministic,
                name=f"block_{i}")(x)
      if n_active is None:
        x = y
      else:
        x = jnp.where(i < n_active, y, x)
    return x


def stage_layout(num_layers: int, num_chunks: int,
                 stage_plan: Optional[tuple] = None):
  """Distribute blocks over pipeline chunks.

  Returns ``(blocks_per_chunk, n_active)``: even models get
  ``(L/chunks, None)``; uneven models allocate ``ceil(L/chunks)`` block
  slots per chunk with ``n_active[c]`` real blocks in chunk ``c`` (the
  first ``L % chunks`` chunks carry the extra block) — masked-identity
  slots make the stacked trunk homogeneous (see StageBlocks).

  ``stage_plan`` (e.g. from the auto-parallel planner) pins the per-chunk
  counts explicitly.
  """
  if stage_plan is not None:
    counts = tuple(int(c) for c in stage_plan)
    if len(counts) != num_chunks or sum(counts) != num_layers \
        or min(counts) < 1:
      raise ValueError(
          f"stage_plan {counts} must hold {num_chunks} positive counts "
          f"summing to num_layers={num_layers}")
    slots = max(counts)
    if all(c == slots for c in counts):
      return slots, None
    return slots, counts
  if num_layers % num_chunks == 0:
    return num_layers // num_chunks, None
  base, rem = divmod(num_layers, num_chunks)
  counts = tuple(base + 1 if c < rem else base for c in range(num_chunks))
  return base + 1, counts


def _remat_policy(name: str):
  if name == "dots":
    return jax.checkpoint_policies.checkpoint_dots
  if name == "dots_flash":
    # `dots` plus the flash-attention kernel outputs (tagged in
    # kernels/flash_attention.py) — the pairing that makes
    # attn_impl="pallas_flash" profitable under remat: dot outputs and
    # the flash (out, lse) are saved, so the backward recomputes only
    # elementwise work and the flash forward kernel is never re-run.
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.checkpoint_dots,
        jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"))
  if name == "everything":
    return jax.checkpoint_policies.nothing_saveable
  return None


def _engine_is_smap(cfg: GPTConfig) -> bool:
  """True when the active Config dispatches the shard_map pipeline engine
  for this (pipelined) model.  Safe before epl.init (returns False)."""
  if cfg.pipeline_stages <= 1:
    return False
  try:
    from easyparallellibrary_tpu.env import Env
    return Env.get().config.pipeline.engine == "smap"
  except Exception:
    return False


def _tied_embedding(cfg: GPTConfig, name=None) -> Embedding:
  """Token-embedding construction shared by the forward pass, the chunked
  tied-head CE, and the 1F1B emit head — one site so the tied table's
  sharding/init can never silently diverge between them.

  Under the smap pipeline engine (without TP) the table is boxed
  stage-vocab-sharded, so `create_sharded_train_state` commits it at
  [V/S, D] per stage group — the stage-resident boundary layout the
  engine's in-specs expect, now also the table's *resident* layout
  (params + adam moments shrink S-fold)."""
  if cfg.tensor_parallel:
    parallel = "vocab"
  elif _engine_is_smap(cfg):
    parallel = "stage_vocab"
  else:
    parallel = "none"
  return Embedding(cfg.vocab_size, cfg.d_model, parallel=parallel,
                   param_dtype=cfg.param_dtype, name=name)


def _lm_head(cfg: GPTConfig, name=None) -> "Dense":
  """Untied LM head, shared by the forward pass and the pipeline emit
  heads.  Mirrors :func:`_tied_embedding`'s engine awareness: under the
  smap engine (without TP) the kernel is committed stage-vocab-sharded
  ([D, V/S] per stage group) so the head is genuinely stage-resident,
  not just resharded per call."""
  if cfg.tensor_parallel:
    parallel = "column"
  elif _engine_is_smap(cfg):
    parallel = "stage_column"
  else:
    parallel = "none"
  return Dense(cfg.vocab_size, parallel=parallel, use_bias=False,
               dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)


class GPT(HeldParams, nn.Module):
  """Decoder-only LM.  `__call__(ids) -> logits`; `loss(params-free)` via
  :func:`gpt_loss`."""

  cfg: GPTConfig

  @nn.compact
  def __call__(self, ids, deterministic: bool = True,
               decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, paged_info=None, kv_write_impl=None,
               slot_attn_impl=None, num_valid=None, rows=None):
    from easyparallellibrary_tpu.runtime.amp import resolve_model_dtypes
    cfg = resolve_model_dtypes(self.cfg)
    B, S = ids.shape
    if decode and cfg.pipeline_stages > 1:
      raise ValueError("KV-cache decode is single-program; run generation "
                       "on a non-pipelined config (pipeline_stages=1)")
    if (slot_cursors is not None or paged_info is not None) and not decode:
      raise ValueError("slot_cursors/paged_info are decode-mode arguments "
                       "(serving engine); pass decode=True")
    tok = _tied_embedding(cfg, name="wte")
    pos_init = nn.initializers.normal(stddev=0.02)
    pos = self.param("wpe", nn.with_partitioning(pos_init, (None, None)), (cfg.max_seq_len, cfg.d_model),
                     cfg.param_dtype)
    if paged_info is not None:
      # Paged flat-token mode (serving paged engine): ids is [T, 1] —
      # one token per batch row — and absolute positions come from the
      # step plan's per-token position vector.  Out-of-range positions
      # (padding rows, draft-rollout overshoot) clip; their outputs are
      # never consumed.
      pos_ids = jnp.clip(paged_info.positions, 0,
                         cfg.max_seq_len - 1)[:, None]        # [T, 1]
      pos_slice = jnp.take(jnp.asarray(pos), pos_ids, axis=0)  # [T, 1, D]
      x = tok(ids).astype(cfg.dtype) + pos_slice.astype(cfg.dtype)
    elif slot_cursors is not None:
      # Slot mode (serving): the step's token-flat batch, ids [T, 1]
      # (models/slot_core.py ``SlotRows``; every position of every slot when
      # no map is handed in).  Absolute positions come straight from the
      # per-slot cursor vector — no pos_index variable; the engine owns cursor
      # advancement.  Past-capacity positions of garbage rows clip into
      # range (their outputs are never consumed).
      rows, ids = flat_ids(ids, slot_cursors, num_valid, rows)
      pos_ids = jnp.clip(rows.positions, 0, cfg.max_seq_len - 1)
      pos_slice = jnp.take(jnp.asarray(pos), pos_ids, axis=0)  # [T, 1, D]
      x = tok(ids).astype(cfg.dtype) + pos_slice.astype(cfg.dtype)
    elif decode:
      # Absolute positions while stepping: the cursor mirrors the
      # attention caches' index (prefill pins it to S).
      pi = self.variable("cache", "pos_index",
                         lambda: jnp.zeros((), jnp.int32))
      if S > 1:  # prefill
        offset = jnp.int32(0)
        pi.value = jnp.int32(S)
      else:
        offset = pi.value
        pi.value = pi.value + 1
      pos_slice = jax.lax.dynamic_slice(
          jnp.asarray(pos), (offset, 0), (S, cfg.d_model))
      x = tok(ids).astype(cfg.dtype) + pos_slice[None].astype(cfg.dtype)
    else:
      pos_slice = jnp.asarray(pos)[:S]
      x = tok(ids).astype(cfg.dtype) + pos_slice[None].astype(cfg.dtype)
    x = _constrain(x, _act_spec(cfg))

    if cfg.pipeline_stages > 1:
      from easyparallellibrary_tpu.parallel.pipeline import Pipeline
      from easyparallellibrary_tpu.strategies.scheduler import get_scheduler
      K = max(1, cfg.pipeline_interleave)
      chunks = cfg.pipeline_stages * K
      blocks_per_chunk, n_active = stage_layout(cfg.num_layers, chunks,
                                                cfg.stage_plan)
      if n_active is not None and cfg.num_experts > 0:
        raise ValueError(
            f"num_layers={cfg.num_layers} must divide evenly into "
            f"{chunks} stages when MoE is enabled (sown aux losses "
            f"cannot be masked per stage)")
      from easyparallellibrary_tpu.env import Env
      sched = get_scheduler(cfg.pipeline_schedule
                            or Env.get().config.pipeline.strategy)
      for k in range(K):
        extra = None
        if n_active is not None:
          # Pass k owns the contiguous chunks k*S .. k*S+S-1, so stage s
          # holds chunk k*S+s in pass k — i.e. every S-th chunk across
          # the K passes (the circular weight distribution).
          extra = (tuple(n_active[k * cfg.pipeline_stages:
                                  (k + 1) * cfg.pipeline_stages]),)
        x = Pipeline(
            stage_module_cls=StageBlocks,
            stage_kwargs=dict(
                cfg=cfg,
                blocks_per_stage=blocks_per_chunk,
                deterministic=deterministic),
            num_stages=cfg.pipeline_stages,
            num_micro_batch=cfg.num_micro_batch,
            sequential=cfg.pipeline_debug_sequential,
            remat_stage=sched.remat_stage or cfg.remat,
            seq_parallel=cfg.seq_parallel,
            stage_extra=extra,
            name="pipeline" if K == 1 else f"pipeline_{k}")(x)
    else:
      block_cls = Block
      if cfg.remat:
        block_cls = nn.checkpoint(
            Block, policy=_remat_policy(cfg.remat_policy),
            prevent_cse=False)
      # Whole layers, so all of them stand in ONE conditional of a
      # two-width step with their K/V pairs (``slot_layers``): a
      # conditional a layer costs a GPT-2 step more than the narrow width
      # saves it, and the compiler writes these pairs in place there
      # (tests/test_kv_write.py compiles the cell's 24 layers for a v5e).
      def layer(i):
        use_moe = cfg.num_experts > 0 and \
          (i % cfg.moe_every == cfg.moe_every - 1)
        return lambda mdl, rows, x: block_cls(
            cfg, use_moe=use_moe, deterministic=deterministic,
            decode=decode, kv_write_impl=kv_write_impl,
            slot_attn_impl=slot_attn_impl, name=f"block_{i}", parent=mdl)(
                x, slot_cursors, paged_info, num_valid, rows)
      x = slot_layers(self, rows, x,
                      [layer(i) for i in range(cfg.num_layers)])

    if rows is not None:
      # The head and the last norm run on the rows that are read: the
      # one a slot samples from where the caller named it.
      x = rows.head_rows(x)
    x = LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
    if return_hidden:
      return x
    if cfg.tie_embeddings:
      logits = tok.attend(x)
    else:
      logits = _lm_head(cfg, name="lm_head")(x)
    return logits


def _chunked_tied_ce(model: GPT, params, hidden, targets):
  """Tied-head CE over sequence chunks inside a rematerialized scan: the
  [B, S, vocab] logits tensor never materializes — only one
  [B, chunk, vocab] block is live at a time (forward AND backward; the
  chunk's logit matmul is recomputed in the backward).  The round-1
  NOTES bottleneck (vocab-32k LM head) attacked at its memory root."""
  cfg = model.cfg
  C = cfg.loss_chunk
  B, S = targets.shape
  if S % C != 0:
    raise ValueError(f"loss_chunk={C} must divide sequence length {S}")
  emb = _tied_embedding(cfg)
  wte = nn.meta.unbox(params)["wte"]

  def chunk_loss(h, t):
    logits = emb.apply({"params": wte}, h, method=Embedding.attend)
    loss = distributed_sparse_softmax_cross_entropy_with_logits(
        t, logits, z_loss=cfg.z_loss)
    return jnp.sum(loss)

  chunk_loss = jax.checkpoint(chunk_loss, prevent_cse=False)
  n = S // C
  hs = jnp.moveaxis(hidden.reshape(B, n, C, -1), 1, 0)    # [n, B, C, D]
  ts = jnp.moveaxis(targets.reshape(B, n, C), 1, 0)       # [n, B, C]

  def body(acc, ht):
    h, t = ht
    return acc + chunk_loss(h, t), None

  total, _ = jax.lax.scan(body, jnp.float32(0), (hs, ts))
  return total / (B * S)


def gpt_loss(model: GPT, params, batch, rng=None):
  """Next-token cross entropy; batch = {"ids": [B, S+1] int32}.

  With MoE enabled, the sown load-balancing losses are collected from the
  ``losses`` collection and added with weight ``moe_aux_weight``.  With
  ``cfg.loss_chunk > 0`` (tied embeddings, no pipeline), the LM head and
  CE run chunked over the sequence (see :func:`_chunked_tied_ce`).
  """
  cfg = model.cfg
  ids = batch["ids"]
  inputs, targets = ids[:, :-1], ids[:, 1:]
  train = cfg.dropout_rate > 0 and rng is not None
  rngs = {"dropout": rng} if train else None
  chunked = cfg.loss_chunk > 0
  if chunked and (not cfg.tie_embeddings or cfg.pipeline_stages > 1):
    # Match the config-layer precedent: never silently ignore a knob the
    # user set expecting a memory win.
    raise ValueError(
        "loss_chunk requires tie_embeddings=True and pipeline_stages<=1 "
        f"(got tie_embeddings={cfg.tie_embeddings}, "
        f"pipeline_stages={cfg.pipeline_stages})")
  kw = dict(deterministic=not train, rngs=rngs, return_hidden=chunked)
  if cfg.num_experts > 0:
    out, state = model.apply({"params": params}, inputs,
                             mutable=["losses"], **kw)
    aux_leaves = jax.tree_util.tree_leaves(state.get("losses", {}))
    aux = sum(jnp.sum(l) for l in aux_leaves) if aux_leaves else 0.0
  else:
    out = model.apply({"params": params}, inputs, **kw)
    aux = 0.0
  if chunked:
    mean_loss = _chunked_tied_ce(model, params, out, targets)
  else:
    loss = distributed_sparse_softmax_cross_entropy_with_logits(
        targets, out, z_loss=cfg.z_loss)
    mean_loss = jnp.mean(loss)
  total = mean_loss + cfg.moe_aux_weight * aux
  metrics = {}
  if cfg.num_experts > 0:
    metrics["moe_aux_loss"] = aux
  return total, metrics


def make_gpt_1f1b_grad_fn(model: GPT):
  """1F1B gradient function for a pipelined GPT.

  Maps the GPT parameter tree onto the generic 1F1B engine
  (parallel/schedule_1f1b.py): embedding = feed, stacked transformer
  stages = stage, final-LN + LM head + CE = emit.  The embedding/head
  live outside the stacked trunk — the heterogeneous-boundary layout the
  reference expresses as arbitrary per-stage taskgraphs
  (epl/parallel/graph_editor.py:423-443).

  Returns `grad_fn(params, batch, rng, loss_scale=None) -> ((loss, aux),
  grads)` with grads matching the (boxed) params structure, drop-in for a
  train step; `loss_scale` seeds the backward for AMP (see
  schedule_1f1b.one_f_one_b).
  """
  from easyparallellibrary_tpu.parallel.schedule_1f1b import (
      one_f_one_b, split_micro_batches)
  from easyparallellibrary_tpu.runtime.amp import resolve_model_dtypes

  cfg = resolve_model_dtypes(model.cfg)
  if cfg.pipeline_stages <= 1:
    raise ValueError("1F1B needs pipeline_stages > 1")
  if cfg.pipeline_interleave > 1:
    # Deliberately unsupported ON THIS ENGINE: in the lockstep SPMD
    # wavefront every tick costs a full device-share of compute (masked
    # chunks execute anyway), so a K-way chunk-interleaved chain has
    # ramp 2(S*K-1) chunk-ticks ~= 2(S - 1/K) device-ticks — never
    # better than plain 1F1B's 2(S-1).  The per-rank smap engine CAN
    # express the Megatron win (see strategies/scheduler.py).
    raise ValueError(
        "1F1B with pipeline_interleave > 1 is not supported on the "
        "lockstep vmapped engine (chunk interleaving cannot beat plain "
        "1F1B here — see strategies/scheduler.py); use "
        "pipeline.engine='smap' for true Megatron-interleaved 1F1B, "
        "interleave=1, or PreferForward for circular weight placement")
  S, M = cfg.pipeline_stages, cfg.num_micro_batch
  blocks_per_stage, n_active = stage_layout(cfg.num_layers, S,
                                            cfg.stage_plan)
  if cfg.num_experts > 0 and n_active is not None:
    # Same guard as GPT.__call__: masked identity slots would still sow
    # MoE aux losses (matters when params bypass GPT.init, e.g. restored
    # checkpoints).
    raise ValueError(
        f"num_layers={cfg.num_layers} must divide evenly into {S} stages "
        f"when MoE is enabled (sown aux losses cannot be masked per stage)")

  emb = _tied_embedding(cfg)
  ln_f = LayerNorm(dtype=cfg.dtype)
  head = None
  if not cfg.tie_embeddings:
    head = _lm_head(cfg)

  def build(train: bool):
    stage_mod = StageBlocks(cfg, blocks_per_stage=blocks_per_stage,
                            deterministic=not train)

    def feed_fn(fp, mb, rng):
      ids = mb["inputs"]
      x = emb.apply({"params": fp["wte"]}, ids).astype(cfg.dtype)
      x = x + fp["wpe"][None, :ids.shape[1]].astype(cfg.dtype)
      return _constrain(x, _act_spec(cfg))

    def stage_fn(p_row, x, rng, *extra):
      rngs = {"dropout": rng} if (train and rng is not None) else None
      if cfg.num_experts > 0:
        y, state = stage_mod.apply({"params": p_row}, x, *extra, rngs=rngs,
                                   mutable=["losses"])
        leaves = jax.tree_util.tree_leaves(state.get("losses", {}))
        aux = sum(jnp.sum(l) for l in leaves) if leaves else jnp.float32(0)
      else:
        y = stage_mod.apply({"params": p_row}, x, *extra, rngs=rngs)
        aux = jnp.float32(0)
      return y, aux

    def emit_fn(ep, y, mb, rng):
      h = ln_f.apply({"params": ep["ln_f"]}, y)
      if cfg.tie_embeddings:
        logits = emb.apply({"params": ep["wte"]}, h,
                           method=Embedding.attend)
      else:
        logits = head.apply({"params": ep["lm_head"]}, h)
      loss = distributed_sparse_softmax_cross_entropy_with_logits(
          mb["targets"], logits, z_loss=cfg.z_loss)
      return jnp.mean(loss), {}

    return one_f_one_b(feed_fn, stage_fn, emit_fn, S, M,
                       stage_aux_weight=(cfg.moe_aux_weight
                                         if cfg.num_experts > 0 else 0.0),
                       seq_parallel=cfg.seq_parallel,
                       stage_extra=(None if n_active is None
                                    else (jnp.asarray(n_active),)))

  def grad_fn(params, batch, rng, loss_scale=None):
    train = cfg.dropout_rate > 0 and rng is not None
    engine = build(train)
    un = nn.meta.unbox(params)
    fp = {"wte": un["wte"], "wpe": un["wpe"]}
    sp = un["pipeline"]["stages"]["stacked"]
    if cfg.tie_embeddings:
      ep = {"ln_f": un["ln_f"], "wte": un["wte"]}
    else:
      ep = {"ln_f": un["ln_f"], "lm_head": un["lm_head"]}
    ids = batch["ids"]
    mbs = split_micro_batches(
        {"inputs": ids[:, :-1], "targets": ids[:, 1:]}, M)
    (loss, aux), (gf, gs, ge) = engine(fp, sp, ep, mbs, rng,
                                       loss_scale=loss_scale)

    g = {"wpe": gf["wpe"], "ln_f": ge["ln_f"],
         "pipeline": {"stages": {"stacked": gs}}}
    if cfg.tie_embeddings:
      g["wte"] = jax.tree_util.tree_map(jnp.add, gf["wte"], ge["wte"])
    else:
      g["wte"] = gf["wte"]
      g["lm_head"] = ge["lm_head"]
    grads = jax.tree_util.tree_map(
        lambda box, gg: box.replace_boxed(gg)
        if isinstance(box, nn.meta.AxisMetadata) else gg,
        params, g,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata))
    metrics = {}
    if cfg.num_experts > 0:
      metrics["moe_aux_loss"] = aux.get("stage_aux_loss", jnp.float32(0))
    return (loss, metrics), grads

  return grad_fn


def make_gpt_smap_grad_fn(model: GPT, mesh=None, schedule: str = "1f1b"):
  """Asynchronous shard_map pipeline gradient function for GPT.

  The per-device-program twin of :func:`make_gpt_1f1b_grad_fn`, built on
  ``parallel.pipeline_smap``: stage boundaries are explicit ppermutes,
  bubble ticks and masked uneven-stage slots genuinely skip compute
  (real ``lax.cond`` branches — impossible in the vmapped engines where
  cond lowers to select), and the tied embedding/LM head are
  **stage-resident**: the [V, D] table is vocab-sharded over the stage
  axis ([V/S, D] per stage group — vs fully replicated in the other two
  engines), with the lookup and softmax-CE computed collectively.
  Reference analog: boundary layers placed on the first/last stage via
  arbitrary per-stage taskgraphs (epl/parallel/graph_editor.py:423-443);
  this distributes their memory AND compute across all stage groups.

  Accepts the same (boxed) parameter tree as the other pipeline paths,
  so checkpoints move freely between engines.  ``schedule``: "1f1b"
  (default — manual wavefront, residual-ring memory bound, dead ramp
  sub-ticks skipped; also the engine's best memory point by XLA's
  memory plan) or "gpipe" (autodiff order; worst temp bytes of the
  four engines).  Returns
  ``grad_fn(params, batch, rng) -> ((loss, metrics), grads)``.

  Tensor parallelism composes: the shard_map is manual over
  ``stage``/``data`` only, so TP weights keep their model-axis GSPMD
  shardings inside the stage program and XLA inserts the row-parallel
  psums as in the non-pipelined path (requires an unpadded vocab:
  ``vocab_size`` divisible by the model axis).  Untied embeddings
  compose: the LM head kernel is stage-vocab-sharded ([D, V/S] per
  stage) just like the tied table.

  Megatron-interleaved 1F1B (``pipeline_interleave`` K > 1): the K
  chained pipeline passes become K virtual chunks per device and the
  table-driven schedule of ``parallel.pipeline_interleaved`` shrinks the
  ramp from 2(S-1) ticks of K-chunk work to 2(S-1) + (K-1)S ticks of
  one-chunk work (schedule="1f1b" upgrades automatically when K > 1).

  Sequence parallelism composes (round 5): ``attn_impl="ring"/"ulysses"``
  with an active seq axis makes the engine manual over ``seq`` and runs
  stage compute branch-UNIFORMLY (select, not cond) so the attention's
  seq collectives execute every tick — XLA gives per-replica-group
  rendezvous only to all-reduce, so gated collective-permutes /
  all-to-alls would deadlock.  ``moe_impl="a2a"`` composes the same way
  (the nested expert shard_map's whole-mesh channels are safe once no
  device can branch around them).  The real-branch ramp FLOP skip is
  traded away exactly for these two compositions; everywhere else the
  engine keeps real branches.

  Remaining constraints (each raises):
  ``vocab_size % pipeline_stages == 0``, interleave needs the 1F1B-order
  schedule, ``ring_impl="einsum"`` cannot enter the seq-manual region.
  """
  from easyparallellibrary_tpu.env import Env
  from easyparallellibrary_tpu.parallel.pipeline_smap import (
      check_seq_token_count, check_unpadded_vocab, engine_meta_specs,
      make_engine_tree_fns, make_smap_1f1b_grad_fn,
      make_smap_gpipe_grad_fn, rebox_grads, run_smap_engine,
      seq_engine_axes, seq_manual_mode, sharded_softmax_ce,
      stage_stacked_specs, token_offset_slice, vocab_partial_embed,
      zero1_grad_layout)
  from easyparallellibrary_tpu.parallel.schedule_1f1b import (
      split_micro_batches)
  from easyparallellibrary_tpu.runtime.amp import resolve_model_dtypes

  cfg = resolve_model_dtypes(model.cfg)
  S, M = cfg.pipeline_stages, cfg.num_micro_batch
  K = max(1, cfg.pipeline_interleave)
  if S <= 1:
    raise ValueError("smap pipeline needs pipeline_stages > 1")
  if schedule == "1f1b" and K > 1:
    schedule = "interleaved"
  if schedule == "interleaved" and K < 2:
    raise ValueError("schedule='interleaved' needs pipeline_interleave "
                     ">= 2 (K virtual chunks per device)")
  if schedule == "gpipe" and K > 1:
    raise ValueError(
        "pipeline_interleave > 1 on the smap engine requires the "
        "interleaved-1F1B schedule (pipeline.strategy PreferBackward*); "
        "GPipe order does not interleave chunks")
  # Sequence parallelism composes by making the engine manual over the
  # seq axis too: the attention's seq collectives (ring ppermutes /
  # Ulysses all-to-alls) then ride the AMBIENT region — no nested
  # shard_map, whose lowered channels span all devices (the round-4
  # deadlock).  Because XLA gives per-replica-group rendezvous only to
  # all-reduce (collective-permute/all-to-all are single whole-mesh
  # channels), the engines additionally run stage compute
  # branch-UNIFORMLY in this mode (pipeline_smap.uniform_stage_compute):
  # the collectives execute every tick on every device, restoring the
  # vmapped engines' uniform-work semantics for exactly this
  # composition.  Tokens shard over seq like batch elements over data:
  # micro-batches arrive seq-split, wpe is sliced at the device's
  # global token offset, the emit CE pmeans its local-token mean over
  # seq, and the engines pmean grads over seq
  # (pipeline_smap.grad_mean_axes).  Shared helpers with the BERT
  # wiring (seq_manual_mode & co) so the guards cannot drift.
  seq_size, seq_manual = seq_manual_mode(cfg.attn_impl, cfg.num_heads)
  a2a_moe = False
  if cfg.num_experts > 0:
    if cfg.moe_impl == "a2a":
      # The a2a MoE's nested shard_map compiles inside the engine's
      # partial-manual region, and its whole-mesh collective channels
      # are safe ONLY when no device can skip them: the engine runs
      # stage compute branch-uniformly for this composition (same
      # trade as sequence parallelism — uniform_stage_compute).
      try:
        a2a_moe = Env.get().cluster.axis_size(constants.EXPERT_AXIS) > 1
      except Exception:
        a2a_moe = False
    if cfg.num_layers % (S * K) != 0:
      raise ValueError(
          f"num_layers={cfg.num_layers} must divide evenly into "
          f"{S * K} stages/chunks when MoE is enabled (matches the "
          f"model's own constraint, GPT.__call__)")
  if cfg.vocab_size % S:
    raise ValueError(f"vocab_size {cfg.vocab_size} must divide into "
                     f"{S} stage-resident shards")
  if schedule not in ("gpipe", "1f1b", "interleaved"):
    raise ValueError(f"schedule must be gpipe|1f1b|interleaved, "
                     f"got {schedule!r}")
  blocks_per_stage, n_active = stage_layout(cfg.num_layers, S * K,
                                            cfg.stage_plan)
  n_active_arr = None if n_active is None else jnp.asarray(n_active)
  if mesh is None:
    mesh = Env.get().cluster.mesh
  if cfg.tensor_parallel:
    check_unpadded_vocab(cfg.vocab_size, mesh)

  ln_f = LayerNorm(dtype=cfg.dtype)
  policy = _remat_policy(cfg.remat_policy)

  def feed_fn(p, mb, rng):
    ids = mb["inputs"]
    x = jax.lax.psum(vocab_partial_embed(p["wte"]["embedding"], ids),
                     constants.STAGE_AXIS)
    pe = token_offset_slice(p["wpe"], ids.shape[1], seq_manual)
    return x.astype(cfg.dtype) + pe[None].astype(cfg.dtype)

  def stage_fn(p, x, rng, chunk=None):
    """One stage's blocks -> (y, aux_scalar).  `chunk` (interleaved
    only) is the LOCAL chunk index; the params tree then carries the K
    passes stacked on axis 1 of each stacked leaf ([1, K, ...] per
    device) and the block row is dynamically selected — the dynamic
    index transposes to the right gradient rows automatically.  MoE
    blocks follow the same local-index pattern as StageBlocks and
    return their sown load-balancing losses through `aux` (the engines
    weight it by stage_aux_weight = cfg.moe_aux_weight)."""
    s_idx = jax.lax.axis_index(constants.STAGE_AXIS)
    row = p["pipeline"]["stages"]["stacked"]
    train = cfg.dropout_rate > 0 and rng is not None
    if chunk is None:
      sel = lambda l: l[0]
      v_idx = s_idx            # layer-order chunk id == stage id
    else:
      sel = lambda l: jax.lax.dynamic_index_in_dim(l[0], chunk, 0,
                                                   keepdims=False)
      v_idx = chunk * S + s_idx  # virtual stage = layer-order chunk id
    aux = jnp.float32(0)
    for i in range(blocks_per_stage):
      bp = jax.tree_util.tree_map(sel, row[f"block_{i}"])
      use_moe = cfg.num_experts > 0 and \
          (i % cfg.moe_every == cfg.moe_every - 1)
      blk = Block(cfg, use_moe=use_moe, deterministic=not train)

      def apply_blk(xx, bp=bp, blk=blk, i=i, use_moe=use_moe):
        rngs = ({"dropout": jax.random.fold_in(rng, i)}
                if train else None)
        if use_moe:
          yy, state = blk.apply({"params": bp}, xx, rngs=rngs,
                                mutable=["losses"])
          leaves = jax.tree_util.tree_leaves(state.get("losses", {}))
          a = (sum(jnp.sum(l) for l in leaves) if leaves
               else jnp.float32(0))
          return yy, jnp.asarray(a, jnp.float32)
        return blk.apply({"params": bp}, xx, rngs=rngs), jnp.float32(0)

      if cfg.remat:
        apply_blk = jax.checkpoint(apply_blk, policy=policy,
                                   prevent_cse=False)
      if n_active_arr is None:
        x, a_i = apply_blk(x)
      elif seq_manual or a2a_moe:
        # Ring / a2a collectives inside the block: collective-permute
        # and all-to-all channels span the mesh, so masked slots must
        # stay branch-uniform (select) — see
        # pipeline_smap.uniform_stage_compute.  (The a2a arm is
        # defense-in-depth: GPT.__call__ already rejects MoE with
        # uneven stage plans.)
        live = i < n_active_arr[v_idx]
        x_run, a_run = apply_blk(x)
        x = jnp.where(live, x_run, x)
        a_i = jnp.where(live, a_run, 0.0)
      else:
        # Real branch under shard_map: a masked slot costs nothing.
        x, a_i = jax.lax.cond(
            i < n_active_arr[v_idx], apply_blk,
            lambda xx: (xx, jnp.float32(0)), x)
      aux = aux + a_i
    return x, aux

  def emit_fn(p, y, mb, valid, rng):
    h = ln_f.apply({"params": p["ln_f"]}, y)
    if cfg.tie_embeddings:
      w = p["wte"]["embedding"]                    # [V/S, D] local slice
      Vs = w.shape[0]

      def slab(hh):
        # Mirrors Embedding.attend (x @ table.T in activation dtype) on
        # the local vocab shard; rematerialized so the [mb, s, V/S] slab
        # is never a saved residual.
        return jnp.matmul(hh, w.T.astype(hh.dtype))
    else:
      w = p["lm_head"]["kernel"]                   # [D, V/S] local slice
      Vs = w.shape[1]

      def slab(hh):
        return jnp.matmul(hh, w.astype(hh.dtype))

    ll = jax.lax.cond(
        valid, jax.checkpoint(slab),
        lambda hh: jnp.zeros(hh.shape[:-1] + (Vs,), hh.dtype), h)
    loss = sharded_softmax_ce(ll, mb["targets"], z_loss=cfg.z_loss)
    m = jnp.mean(loss)
    if seq_manual:
      # Local-token mean -> true micro-batch mean.  Unconditional seq
      # collective, every tick; seq peers share the engine's predicates
      # (same stage index) so this is branch-uniform.  Its pmean
      # transpose also keeps the engines' seed/S calibration exact (the
      # 1/n cancels the n-peer seeding); only grads need the extra
      # pmean over seq, applied in the engines' reduction.
      m = jax.lax.pmean(m, constants.SEQ_AXIS)
    return m

  engine_cache = {}
  # Shared K-pass stacking convention (pipeline_smap.make_engine_tree_fns
  # — one helper set with the BERT wiring so the layouts cannot drift).
  to_engine_tree, from_engine_grads = make_engine_tree_fns(K)

  # ZeRO-1 (config zero.level="v1"): the engine's grad reduction becomes
  # a reduce-scatter to the data-axis owner (pipeline_smap._reduce_grads)
  # — grads leave the engine data-sharded and pre-aligned with the
  # optimizer-state shards that create_sharded_train_state(zero_level=
  # "v1") builds, so the update applies shard-locally and GSPMD
  # all-gathers the params: the reference's reduce-to-owner + broadcast
  # choreography (epl/runtime/zero.py:129-190) riding the pipeline
  # engine's own reduction.
  zero1_dp = 0
  if Env.get().config.zero.level == constants.ZERO_V1:
    zero1_dp = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        constants.DATA_AXIS, 1)
    if zero1_dp <= 1:
      zero1_dp = 0


  def grad_fn(params, batch, rng, loss_scale=None):
    check_seq_token_count(batch["ids"].shape[1] - 1, seq_size,
                          seq_manual)
    un = to_engine_tree(nn.meta.unbox(params))
    if "fn" not in engine_cache:
      # Manual (stage/data) projection only: model-axis TP shardings ride
      # the argument arrays through the auto axes (partial-manual
      # shard_map — see pipeline_smap module docstring).
      specs = stage_stacked_specs(un)
      specs["wte"]["embedding"] = P(constants.STAGE_AXIS, None)
      if not cfg.tie_embeddings:
        specs["lm_head"]["kernel"] = P(None, constants.STAGE_AXIS)
      manual, bspec = seq_engine_axes(seq_manual)
      uniform = (seq_manual or a2a_moe) or None
      aux_w = cfg.moe_aux_weight if cfg.num_experts > 0 else 0.0
      zero1 = None
      if zero1_dp:
        dims, gspecs = zero1_grad_layout(
            un, engine_meta_specs(params, K), specs, zero1_dp)
        zero1 = (dims, gspecs, zero1_dp)
      if schedule == "interleaved":
        from easyparallellibrary_tpu.parallel.pipeline_interleaved import (
            make_smap_interleaved_grad_fn)
        engine_cache["fn"] = make_smap_interleaved_grad_fn(
            feed_fn, stage_fn, emit_fn, S, K, M, mesh, specs,
            batch_spec=bspec, manual_axes=manual, stage_aux_weight=aux_w,
            uniform_compute=uniform, zero1=zero1)
      else:
        build = (make_smap_1f1b_grad_fn if schedule == "1f1b"
                 else make_smap_gpipe_grad_fn)
        engine_cache["fn"] = build(
            feed_fn, stage_fn, emit_fn, S, M, mesh, specs,
            batch_spec=bspec, manual_axes=manual, stage_aux_weight=aux_w,
            uniform_compute=uniform, zero1=zero1)
    ids = batch["ids"]
    mbs = split_micro_batches(
        {"inputs": ids[:, :-1], "targets": ids[:, 1:]}, M)
    (loss, metrics), g = run_smap_engine(
        engine_cache["fn"], schedule, un, mbs, rng, loss_scale)
    grads = rebox_grads(params, from_engine_grads(g))
    metrics = dict(metrics)
    aux_metric = metrics.pop("stage_aux_loss", None)
    if cfg.num_experts > 0 and aux_metric is not None:
      metrics["moe_aux_loss"] = aux_metric
    return (loss, metrics), grads

  return grad_fn


def auto_parallel_gpt(cfg: GPTConfig, config=None) -> GPT:
  """Auto-parallel model build: plan pipeline stages automatically.

  When ``auto.auto_parallel`` is on and ``pipeline.num_stages > 1``, the
  stage layout comes from :class:`parallel.planner.AutoStageGenerator`
  over per-block FLOP weights and lands in ``GPTConfig.stage_plan``.
  This is the build-time trigger the reference fires from its graph hooks
  (epl/parallel/hooks.py:129-135 → planner → partition); here the planner
  output flows directly into model construction.  With auto off (or
  stages already pinned) the config passes through unchanged.

  Only transformer blocks are planned: embedding and LM head execute
  outside the stacked trunk (before/after the Pipeline; feed/emit in the
  1F1B engine), and the lockstep SPMD trunk's per-tick cost is
  ``max(counts)`` block slots on *every* stage — so weighting the
  boundary stages by vocab size would buy nothing and cost extra masked
  slots.  The planner balances the blocks' own weights, which for a
  uniform model reproduces the optimal ceil split (uneven counts exactly
  when ``num_layers % chunks != 0``).
  """
  import dataclasses as _dc
  from easyparallellibrary_tpu.env import Env
  from easyparallellibrary_tpu.parallel.planner import AutoStageGenerator

  conf = config if config is not None else Env.get().config
  N = conf.pipeline.num_stages
  if not conf.auto.auto_parallel or N <= 1 or cfg.pipeline_stages > 1:
    return GPT(cfg)

  K = max(1, cfg.pipeline_interleave)
  chunks = N * K
  L = cfg.num_layers
  if L < chunks:
    raise ValueError(
        f"auto-parallel needs num_layers >= stages*interleave "
        f"({L} < {chunks}); reduce pipeline.num_stages")
  # GPT trunk blocks are structurally uniform (MoE top-1 activates the
  # same matmul count as dense), so the planner balances unit weights;
  # plug per-block costs here if blocks ever become heterogeneous.
  names = [f"block_{i}" for i in range(L)]
  gen = AutoStageGenerator(num_stages=chunks)
  stages = gen.search(names)
  counts = tuple(len(s) for s in stages)
  if len(counts) != chunks or min(counts) < 1:
    raise ValueError(
        f"auto stage search produced an invalid plan {counts} for "
        f"{chunks} chunks over {L} blocks")
  mb = conf.pipeline.num_micro_batch
  cfg2 = _dc.replace(
      cfg, pipeline_stages=N, stage_plan=counts,
      num_micro_batch=mb if mb > 1 else max(cfg.num_micro_batch, 1))
  return GPT(cfg2)


# Once-per-process latch for the engine advisory below: the recommendation
# is identical for every trace/step, so repeating it per trace is noise.
_SMAP_ADVICE_LOGGED = [False]

# Same once-gating for generate()'s pipeline fallback: the reason is
# identical for every call, and generation loops call generate() often.
_PP_GENERATE_FALLBACK_LOGGED = [False]


def _smap_preconditions_ok(cfg: GPTConfig, conf, sched) -> bool:
  """True iff ``pipeline.engine='smap'`` would accept this exact config —
  the advisory in :func:`make_gpt_train_step` must never recommend an
  engine that would raise on the user's model (the full constraint list
  of :func:`make_gpt_smap_grad_fn`, not just vocab divisibility)."""
  S = cfg.pipeline_stages
  K = max(1, cfg.pipeline_interleave)
  if cfg.vocab_size % S:
    return False
  if K > 1 and not sched.remat_stage:
    return False  # interleave requires the 1F1B-order schedules
  if cfg.num_experts > 0 and cfg.num_layers % (S * K):
    return False
  from easyparallellibrary_tpu.env import Env
  env = Env.get()
  sizes = {}
  if env.cluster is not None and env.cluster._mesh is not None:
    mesh = env.cluster._mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
  model_size = sizes.get(constants.MODEL_AXIS, 1)
  if cfg.tensor_parallel and model_size > 1 and cfg.vocab_size % model_size:
    return False  # stage-resident CE needs an unpadded vocab table
  seq = sizes.get(constants.SEQ_AXIS, 1)
  if seq > 1 and cfg.attn_impl == "ring" and \
      conf.sequence.ring_impl not in ("flash", "dense"):
    return False  # einsum ring cannot enter the seq-manual region
  if seq > 1 and cfg.attn_impl == "ulysses" and cfg.num_heads % seq:
    return False
  return True


def make_gpt_train_step(model: GPT, config=None):
  """Config-driven train step for GPT, engine- and schedule-aware.

  ``pipeline.engine`` selects the pipeline engine (reference analog: the
  scheduler registry dispatch, epl/strategies/scheduler.py:120-131):

    * ""/"vmap" — the lockstep SPMD engines; ``PreferBackward``/
      ``PreferBackwardOptimizer`` pick the true-1F1B wavefront
      (reference scheduler.py:53-116 orders backward-k before
      forward-k+1 — here the interleave is explicit in one scan),
      ``PreferForward`` the GPipe autodiff path.
    * "smap" — the per-device shard_map engine
      (:func:`make_gpt_smap_grad_fn`); the schedule policy still picks
      the order within it (PreferBackward* → "1f1b", PreferForward →
      "gpipe").

  Non-pipelined configs use the standard autodiff path
  (`build_train_step` over :func:`gpt_loss`) regardless of engine.
  """
  from easyparallellibrary_tpu.env import Env
  from easyparallellibrary_tpu.runtime.trainer import build_train_step
  from easyparallellibrary_tpu.strategies.scheduler import get_scheduler

  cfg = model.cfg
  conf = config if config is not None else Env.get().config
  sched = None
  use_1f1b = False
  groups = None
  if cfg.pipeline_stages > 1 and not cfg.pipeline_debug_sequential:
    sched = get_scheduler(cfg.pipeline_schedule or conf.pipeline.strategy)
    # PreferBackwardOptimizer's grouped apply (reference interleaves the
    # optimizer with the backward, scheduler.py:86-116): default to one
    # group per stage when the config doesn't pin a count.
    if sched.grouped_apply and conf.optimizer.num_apply_group <= 1:
      groups = cfg.pipeline_stages
    if conf.pipeline.engine == "smap":
      schedule = "1f1b" if sched.remat_stage else "gpipe"
      return build_train_step(
          grad_fn=make_gpt_smap_grad_fn(model, schedule=schedule),
          config=conf, num_apply_group=groups)
    from easyparallellibrary_tpu.utils.logging import get_logger
    if not _SMAP_ADVICE_LOGGED[0] and \
        _smap_preconditions_ok(cfg, conf, sched):
      # Advise 'smap' ONCE per process, and only when this config
      # satisfies the engine's FULL constraint set — a recommendation
      # the engine would reject is worse than none.
      _SMAP_ADVICE_LOGGED[0] = True
      get_logger().info(
          "pipeline.engine=%r runs the lockstep vmapped engine; the "
          "per-device shard_map engine (pipeline.engine='smap') "
          "measured lower compiled FLOPs, smaller temps and "
          "stage-resident argument bytes at every attested composition.",
          conf.pipeline.engine)
    use_1f1b = sched.remat_stage  # PreferBackward / PreferBackwardOptimizer
    if use_1f1b and cfg.pipeline_interleave > 1:
      get_logger().warning(
          "pipeline.strategy=%s requests 1F1B but pipeline_interleave=%d "
          "is only interleaved on the shard_map engine "
          "(pipeline.engine='smap'); falling back to the GPipe autodiff "
          "path (M live activations per stage).",
          sched.name, cfg.pipeline_interleave)
      use_1f1b = False

  if not use_1f1b:
    return build_train_step(lambda p, b, r: gpt_loss(model, p, b, r),
                            config=conf)

  # build_train_step owns AMP loss scaling (the engine seeds its backward
  # with the scale), overflow skipping, and grouped apply.
  return build_train_step(grad_fn=make_gpt_1f1b_grad_fn(model),
                          config=conf, num_apply_group=groups)


def sample_logits(logits, rng, temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0):
  """Sample token ids from ``[..., vocab]`` logits.

  ``temperature<=0`` is greedy; ``top_k>0`` restricts to the k highest
  logits; ``top_p<1`` restricts to the smallest set whose probability
  mass reaches p (nucleus sampling; the top token always survives).
  Filters compose (top-k first, then top-p over the survivors), all with
  static shapes, so this is jit/fori_loop-safe and usable on sharded
  logits.
  """
  # Validate here (not only in generate): top_p=0 would otherwise mask
  # EVERY logit to -1e30 and categorical would sample uniformly over the
  # whole vocabulary — garbage tokens with no error.
  if not 0.0 < top_p <= 1.0:
    raise ValueError(f"top_p must be in (0, 1]: {top_p}")
  if top_k < 0:
    raise ValueError(f"top_k must be >= 0: {top_k}")
  if temperature <= 0:
    return jnp.argmax(logits, axis=-1)
  logits = logits / temperature
  neg = jnp.asarray(-1e30, logits.dtype)
  if top_k and top_k < logits.shape[-1]:
    kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
    logits = jnp.where(logits < kth, neg, logits)
  if top_p < 1.0:
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep entries whose PRECEDING mass is < p (so the first token that
    # crosses p is still kept, and the top token always survives).
    keep_sorted = (cum - probs) < top_p
    # Threshold = smallest kept logit; everything below is cut.
    thresh = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                     axis=-1, keepdims=True)
    logits = jnp.where(logits < thresh.astype(logits.dtype), neg, logits)
  return jax.random.categorical(rng, logits, axis=-1)


def generate(model: GPT, params, prompt_ids, max_new_tokens: int,
             temperature: float = 0.0, rng=None, use_cache: bool = True,
             top_k: int = 0, top_p: float = 1.0):
  """Autoregressive decoding; returns [B, prompt + max_new_tokens].

  With ``use_cache`` (default), each layer keeps a K/V cache: one prefill
  over the prompt, then O(1) forwards per generated token (VERDICT
  round-1 item 10).  ``use_cache=False`` (or a pipelined config) falls
  back to re-running the full forward per token — the simple path the
  cached one is tested against.  ``temperature=0`` is greedy;
  ``top_k``/``top_p`` restrict sampling (see :func:`sample_logits`).
  """
  B, plen = prompt_ids.shape
  if plen == 0:
    raise ValueError("generate() needs a non-empty prompt (at least a BOS "
                     "token); an empty prompt would condition the first "
                     "token on uninitialized padding")
  if not 0.0 < top_p <= 1.0:
    raise ValueError(f"top_p must be in (0, 1]: {top_p}")
  if top_k < 0:
    raise ValueError(f"top_k must be >= 0: {top_k}")
  total = plen + max_new_tokens
  if total > model.cfg.max_seq_len:
    raise ValueError(f"prompt + new tokens ({total}) exceeds "
                     f"max_seq_len {model.cfg.max_seq_len}")
  ids = jnp.zeros((B, total), jnp.int32).at[:, :plen].set(prompt_ids)
  rng = rng if rng is not None else jax.random.PRNGKey(0)

  def pick(next_logits, t):
    return sample_logits(next_logits, jax.random.fold_in(rng, t),
                         temperature, top_k, top_p)

  if max_new_tokens <= 0:
    return ids

  if use_cache and model.cfg.pipeline_stages > 1 and \
      not _PP_GENERATE_FALLBACK_LOGGED[0]:
    # The silent O(S)-per-token cliff, surfaced (once per process — same
    # latch pattern as the smap advisory): KV-cache decode is a single
    # program (GPT.__call__ rejects decode=True under pipelining), so a
    # pipelined config re-runs the FULL forward for every generated
    # token.
    _PP_GENERATE_FALLBACK_LOGGED[0] = True
    from easyparallellibrary_tpu.utils.logging import get_logger
    get_logger().warning(
        "generate(use_cache=True) on a pipelined config "
        "(pipeline_stages=%d) falls back to full-forward-per-token: "
        "KV-cache decode is single-program and cannot span pipeline "
        "stages.  Restore the checkpoint into a pipeline_stages=1 config "
        "(runtime.saver.restore_params) for O(1)-per-token decoding or "
        "the serving engine (docs/serving.md).  (Logged once per "
        "process.)", model.cfg.pipeline_stages)

  if use_cache and model.cfg.pipeline_stages <= 1:
    # Prefill: one full forward over the prompt populates the caches.
    logits, vars = model.apply({"params": params}, prompt_ids,
                               decode=True, mutable=["cache"])
    nxt = pick(logits[:, plen - 1], plen)
    ids = jax.lax.dynamic_update_slice_in_dim(
        ids, nxt[:, None].astype(jnp.int32), plen, axis=1)

    def body(t, carry):
      ids, cache = carry
      tok = jax.lax.dynamic_slice_in_dim(ids, t - 1, 1, axis=1)
      logits, vars = model.apply({"params": params, "cache": cache}, tok,
                                 decode=True, mutable=["cache"])
      nxt = pick(logits[:, 0], t)
      ids = jax.lax.dynamic_update_slice_in_dim(
          ids, nxt[:, None].astype(jnp.int32), t, axis=1)
      return ids, vars["cache"]

    ids, _ = jax.lax.fori_loop(plen + 1, total, body,
                               (ids, vars["cache"]))
    return ids

  def body(t, ids):
    logits = model.apply({"params": params}, ids)
    next_logits = jax.lax.dynamic_slice_in_dim(
        logits, t - 1, 1, axis=1)[:, 0]            # [B, vocab]
    nxt = pick(next_logits, t)
    return jax.lax.dynamic_update_slice_in_dim(
        ids, nxt[:, None].astype(jnp.int32), t, axis=1)

  return jax.lax.fori_loop(plen, total, body, ids)


def gpt_flops_per_token(cfg: GPTConfig, seq_len: Optional[int] = None) -> float:
  """Training FLOPs/token (fwd+bwd ≈ 3x fwd): 6*N_dense + attention term."""
  S = seq_len or cfg.max_seq_len
  D, F, L, V = cfg.d_model, cfg.d_ff, cfg.num_layers, cfg.vocab_size
  attn_part = 4 * D * D               # qkv + proj
  ffn_part = 2 * D * F                # mlp in + out
  n_matmul = L * (attn_part + ffn_part) + D * V   # + lm head
  if cfg.num_experts > 0 and cfg.moe_top_k > 1:
    # Top-k>1 routes each token through k experts: the FFN matmuls of
    # the MoE blocks (every moe_every-th) run k times per token.
    n_moe_blocks = len([i for i in range(L)
                        if (i + 1) % max(cfg.moe_every, 1) == 0])
    n_matmul += n_moe_blocks * ffn_part * (cfg.moe_top_k - 1)
  attn = L * 2 * D * S                # qk^T and attn*v per token
  return 6.0 * n_matmul + 6.0 * attn
