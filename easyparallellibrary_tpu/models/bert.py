"""BERT — bidirectional encoder family (the reference's config 2: BERT-Large
2-stage pipeline with 4 micro-batches, the reference's pipeline tutorial
model, /root/reference/docs/en/tutorials/pipe.md:33-48).

Shares the TPU-first machinery with GPT: tensor-parallel ops layers,
stage-stacked pipeline over the ``stage`` axis, bf16 compute.  Trains with
a masked-LM objective through the tied embedding.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.ops import Dense, Embedding
from easyparallellibrary_tpu.ops.layers import LayerNorm
from easyparallellibrary_tpu.ops.losses import (
    distributed_sparse_softmax_cross_entropy_with_logits,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
  vocab_size: int = 30528          # multiple of 64 for sharding
  num_layers: int = 12
  num_heads: int = 12
  d_model: int = 768
  d_ff: int = 3072
  max_seq_len: int = 512
  type_vocab_size: int = 2
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.float32
  tensor_parallel: bool = False
  remat: bool = False
  # xla | pallas_flash | ring | ulysses (all non-causal).  ring/ulysses
  # give the encoder family the same long-context scaling as GPT
  # (sequence sharded over the seq axis; bidirectional rings have no
  # zigzag — the causal-balance trick is moot without a mask).
  attn_impl: str = "xla"
  seq_parallel: bool = False         # shard activations over seq
  pipeline_stages: int = 1
  num_micro_batch: int = 1
  pipeline_schedule: str = ""   # "" = from Config pipeline.strategy
  # Megatron-interleaved virtual chunks per device (K): the K pipeline
  # passes become pipeline_0..pipeline_{K-1} param trees; the smap
  # engine upgrades 1f1b to the interleaved schedule (same convention
  # as GPTConfig.pipeline_interleave).
  pipeline_interleave: int = 1
  pipeline_debug_sequential: bool = False


def bert_large_config(**kw):
  base = dict(num_layers=24, num_heads=16, d_model=1024, d_ff=4096)
  base.update(kw)
  return BertConfig(**base)


from easyparallellibrary_tpu.utils.sharding import constrain as _constrain  # noqa: E402


def _act_spec(cfg: BertConfig) -> P:
  seq = constants.SEQ_AXIS if cfg.seq_parallel else None
  return P(constants.DATA_AXIS, seq, None)


class EncoderBlock(nn.Module):
  cfg: BertConfig

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    B, S, D = x.shape
    H = cfg.num_heads
    col = "column" if cfg.tensor_parallel else "none"
    row = "row" if cfg.tensor_parallel else "none"

    y = LayerNorm(dtype=cfg.dtype, name="ln1")(x)
    qkv = Dense(3 * D, parallel=col, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="qkv")(y)
    if cfg.attn_impl == "pallas_flash":
      # Bidirectional flash (causal=False) — same kernel as GPT's path,
      # reading q, k and v where the projection wrote them; removes the
      # [B, H, S, S] score temps at BERT's S=512 default.
      from easyparallellibrary_tpu.kernels.flash_attention import (
          flash_attention_qkv)
      attn = flash_attention_qkv(qkv, H, causal=False)
    elif cfg.attn_impl in ("ring", "ulysses", "xla"):
      qkv = qkv.reshape(B, S, 3, H, D // H)
      qkv = _constrain(qkv, P(constants.DATA_AXIS, None, None,
                              constants.MODEL_AXIS, None))
      q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
      if cfg.attn_impl == "ring":
        # Bidirectional ring — the encoder family's long-context path
        # (sequence sharded over the seq axis; composes with the smap
        # pipeline engines exactly like GPT's).
        from easyparallellibrary_tpu.sequence.ring_attention import (
            ring_attention)
        attn = ring_attention(q, k, v, causal=False)
      elif cfg.attn_impl == "ulysses":
        from easyparallellibrary_tpu.sequence.ulysses import (
            ulysses_attention)
        attn = ulysses_attention(q, k, v, causal=False)
      else:
        scale = 1.0 / jnp.sqrt(D // H).astype(cfg.dtype)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               -1).astype(cfg.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
      attn = attn.reshape(B, S, D)
    else:
      # A typo'd impl silently falling back to dense attention would
      # mislabel any benchmark run on top of it (same guard as GPT).
      raise ValueError(f"attn_impl must be 'xla', 'pallas_flash', "
                       f"'ring' or 'ulysses'; got {cfg.attn_impl!r}")
    x = x + Dense(D, parallel=row, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype, name="proj")(attn)

    y = LayerNorm(dtype=cfg.dtype, name="ln2")(x)
    h = nn.gelu(Dense(cfg.d_ff, parallel=col, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="wi")(y))
    x = x + Dense(D, parallel=row, dtype=cfg.dtype,
                  param_dtype=cfg.param_dtype, name="wo")(h)
    return _constrain(x, _act_spec(cfg))


class BertStage(nn.Module):
  cfg: BertConfig
  blocks_per_stage: int

  @nn.compact
  def __call__(self, x):
    for i in range(self.blocks_per_stage):
      x = EncoderBlock(self.cfg, name=f"block_{i}")(x)
    return x


class Bert(nn.Module):
  cfg: BertConfig

  @nn.compact
  def __call__(self, ids, type_ids=None):
    from easyparallellibrary_tpu.runtime.amp import resolve_model_dtypes
    cfg = resolve_model_dtypes(self.cfg)
    B, S = ids.shape
    tok = Embedding(cfg.vocab_size, cfg.d_model,
                    parallel="vocab" if cfg.tensor_parallel else "none",
                    param_dtype=cfg.param_dtype, name="wte")
    pos = self.param(
        "wpe", nn.with_partitioning(nn.initializers.normal(0.02),
                                    (None, None)),
        (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
    seg = Embedding(cfg.type_vocab_size, cfg.d_model, parallel="none",
                    param_dtype=cfg.param_dtype, name="wse")
    if type_ids is None:
      type_ids = jnp.zeros_like(ids)
    x = (tok(ids).astype(cfg.dtype) + pos[None, :S].astype(cfg.dtype)
         + seg(type_ids).astype(cfg.dtype))
    x = LayerNorm(dtype=cfg.dtype, name="ln_emb")(x)
    x = _constrain(x, _act_spec(cfg))

    if cfg.pipeline_stages > 1:
      from easyparallellibrary_tpu.parallel.pipeline import Pipeline
      from easyparallellibrary_tpu.strategies.scheduler import get_scheduler
      K = max(1, cfg.pipeline_interleave)
      chunks = cfg.pipeline_stages * K
      if cfg.num_layers % chunks != 0:
        raise ValueError(
            "num_layers must be divisible by pipeline_stages "
            "* pipeline_interleave")
      from easyparallellibrary_tpu.env import Env
      sched = get_scheduler(cfg.pipeline_schedule
                            or Env.get().config.pipeline.strategy)
      for k in range(K):
        # Pass k owns contiguous chunks k*S .. k*S+S-1: stage s holds
        # chunk k*S+s in pass k — every S-th chunk across the K passes
        # (the circular weight distribution; same layout as GPT).
        x = Pipeline(
            stage_module_cls=BertStage,
            stage_kwargs=dict(
                cfg=cfg,
                blocks_per_stage=cfg.num_layers // chunks),
            num_stages=cfg.pipeline_stages,
            num_micro_batch=cfg.num_micro_batch,
            sequential=cfg.pipeline_debug_sequential,
            remat_stage=sched.remat_stage or cfg.remat,
            seq_parallel=cfg.seq_parallel,
            name="pipeline" if K == 1 else f"pipeline_{k}")(x)
    else:
      block_cls = EncoderBlock
      if cfg.remat:
        block_cls = nn.checkpoint(EncoderBlock, prevent_cse=False)
      for i in range(cfg.num_layers):
        x = block_cls(cfg, name=f"block_{i}")(x)

    x = LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
    return tok.attend(x)   # MLM logits via tied embedding


class BertForQuestionAnswering(nn.Module):
  """SQuAD-style span prediction head (the reference's pipeline tutorial
  fine-tunes BERT on SQuAD, docs/en/tutorials/pipe.md:46-59)."""

  cfg: BertConfig

  @nn.compact
  def __call__(self, ids, type_ids=None):
    cfg = self.cfg
    x = BertEncoderTrunk(cfg, name="bert")(ids, type_ids)
    span = Dense(2, parallel="none", dtype=jnp.float32,
                 param_dtype=cfg.param_dtype, name="qa_outputs")(x)
    start_logits, end_logits = span[..., 0], span[..., 1]
    return start_logits, end_logits


class BertEncoderTrunk(nn.Module):
  """Bert without the MLM head (shared trunk for task heads)."""

  cfg: BertConfig

  @nn.compact
  def __call__(self, ids, type_ids=None):
    from easyparallellibrary_tpu.runtime.amp import resolve_model_dtypes
    cfg = resolve_model_dtypes(self.cfg)
    B, S = ids.shape
    tok = Embedding(cfg.vocab_size, cfg.d_model,
                    parallel="vocab" if cfg.tensor_parallel else "none",
                    param_dtype=cfg.param_dtype, name="wte")
    pos = self.param(
        "wpe", nn.with_partitioning(nn.initializers.normal(0.02),
                                    (None, None)),
        (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
    seg = Embedding(cfg.type_vocab_size, cfg.d_model, parallel="none",
                    param_dtype=cfg.param_dtype, name="wse")
    if type_ids is None:
      type_ids = jnp.zeros_like(ids)
    x = (tok(ids).astype(cfg.dtype) + pos[None, :S].astype(cfg.dtype)
         + seg(type_ids).astype(cfg.dtype))
    x = LayerNorm(dtype=cfg.dtype, name="ln_emb")(x)
    x = _constrain(x, _act_spec(cfg))
    block_cls = EncoderBlock
    if cfg.remat:
      block_cls = nn.checkpoint(EncoderBlock, prevent_cse=False)
    for i in range(cfg.num_layers):
      x = block_cls(cfg, name=f"block_{i}")(x)
    return LayerNorm(dtype=cfg.dtype, name="ln_f")(x)


def bert_qa_loss(model: BertForQuestionAnswering, params, batch, rng=None):
  """Span loss; batch = {"ids", "start_positions", "end_positions"}."""
  start_logits, end_logits = model.apply({"params": params}, batch["ids"])
  loss = (
      distributed_sparse_softmax_cross_entropy_with_logits(
          batch["start_positions"], start_logits)
      + distributed_sparse_softmax_cross_entropy_with_logits(
          batch["end_positions"], end_logits))
  return jnp.mean(loss) / 2, {}


def bert_mlm_loss(model: Bert, params, batch, rng=None):
  """Masked-LM loss; batch = {"ids": [B,S], "labels": [B,S],
  "mask": [B,S] float (1 where a token is masked/predicted)}."""
  logits = model.apply({"params": params}, batch["ids"])
  loss = distributed_sparse_softmax_cross_entropy_with_logits(
      batch["labels"], logits)
  mask = batch["mask"].astype(jnp.float32)
  total = jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
  return total, {}


def make_bert_smap_grad_fn(model: Bert, mesh=None, schedule: str = "1f1b"):
  """Per-device shard_map pipeline gradient function for BERT.

  The GPT smap wiring (models/gpt.py:make_gpt_smap_grad_fn) applied to
  the encoder family — proof the engines are framework infrastructure,
  not a GPT special case (BASELINE row 2 is the reference's pipeline
  tutorial model, /root/reference/docs/en/tutorials/pipe.md:33-48):

    feed  = stage-vocab-sharded token lookup (psum) + position/segment
            embeddings + embedding LayerNorm,
    stage = L/S EncoderBlocks per device (non-causal attention; TP
            composes through the auto model axis),
    emit  = final LayerNorm + tied-table MLM logits slab + sharded CE,
            normalized by THIS micro-batch's mask count.

  Per-micro-batch loss semantics: each micro-batch's masked loss is the
  ratio-of-sums across ALL its shards (data rows and, under sequence
  parallelism, token shards — ragged per-shard mask counts are exact);
  the engine then averages the M per-micro-batch ratios, which equals
  `bert_mlm_loss`'s whole-batch ratio when mask counts are equal across
  micro-batches (the standard fixed-count MLM masking).

  ``pipeline_interleave`` K > 1 upgrades ``schedule="1f1b"`` to the
  Megatron-interleaved table-driven engine, exactly as the GPT wiring
  does (the K-pass stacking itself is the SHARED
  ``pipeline_smap.make_engine_tree_fns`` — one helper set, no drift).

  Constraints (each raises): pipeline_stages > 1,
  vocab_size % pipeline_stages == 0,
  num_layers % (pipeline_stages * pipeline_interleave) == 0,
  unpadded vocab under TP, interleave needs the 1F1B-order schedule.
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
  # Sequence parallelism composes exactly as in the GPT wiring (shared
  # helpers, parallel/pipeline_smap.py): the engine goes manual over
  # seq, runs stage compute branch-uniformly, tokens shard over seq,
  # and the masked-LM emit ratio psums its numerator/denominator over
  # the token shards (ratio-of-sums — the same per-micro-batch
  # semantics and div0 clamp as the unsharded path even with ragged
  # per-shard mask counts).
  seq_size, seq_manual = seq_manual_mode(cfg.attn_impl, cfg.num_heads)
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
  if cfg.vocab_size % S:
    raise ValueError(f"vocab_size {cfg.vocab_size} must divide into "
                     f"{S} stage-resident shards")
  if cfg.num_layers % (S * K):
    raise ValueError("num_layers must be divisible by pipeline_stages "
                     "* pipeline_interleave (the model's own constraint)")
  if schedule not in ("gpipe", "1f1b", "interleaved"):
    raise ValueError(f"schedule must be gpipe|1f1b|interleaved, "
                     f"got {schedule!r}")
  blocks_per_stage = cfg.num_layers // (S * K)
  if mesh is None:
    mesh = Env.get().cluster.mesh
  if cfg.tensor_parallel:
    check_unpadded_vocab(cfg.vocab_size, mesh)

  ln_emb = LayerNorm(dtype=cfg.dtype)
  ln_f = LayerNorm(dtype=cfg.dtype)

  def feed_fn(p, mb, rng):
    ids = mb["ids"]
    type_ids = mb.get("type_ids", jnp.zeros_like(ids))
    x = jax.lax.psum(vocab_partial_embed(p["wte"]["embedding"], ids),
                     constants.STAGE_AXIS).astype(cfg.dtype)
    pe = token_offset_slice(p["wpe"], ids.shape[1], seq_manual)
    x = x + pe[None].astype(cfg.dtype)
    x = x + jnp.take(p["wse"]["embedding"], type_ids,
                     axis=0).astype(cfg.dtype)
    return ln_emb.apply({"params": p["ln_emb"]}, x)

  def stage_fn(p, x, rng, chunk=None):
    """One stage's blocks.  `chunk` (interleaved only) is the LOCAL
    chunk index; stacked leaves then arrive [1, K, ...] per device and
    the chunk's rows are dynamically selected (same convention as the
    GPT wiring — the dynamic index transposes to the right gradient
    rows automatically)."""
    row = p["pipeline"]["stages"]["stacked"]
    if chunk is None:
      sel = lambda l: l[0]
    else:
      sel = lambda l: jax.lax.dynamic_index_in_dim(l[0], chunk, 0,
                                                   keepdims=False)
    for i in range(blocks_per_stage):
      bp = jax.tree_util.tree_map(sel, row[f"block_{i}"])
      blk = EncoderBlock(cfg)

      def apply_blk(xx, bp=bp, blk=blk):
        return blk.apply({"params": bp}, xx)

      if cfg.remat:
        apply_blk = jax.checkpoint(apply_blk, prevent_cse=False)
      x = apply_blk(x)
    return x, jnp.float32(0)

  def emit_fn(p, y, mb, valid, rng):
    h = ln_f.apply({"params": p["ln_f"]}, y)
    w = p["wte"]["embedding"]                      # [V/S, D] local slice

    def slab(hh):
      return jnp.matmul(hh, w.T.astype(hh.dtype))

    ll = jax.lax.cond(
        valid, jax.checkpoint(slab),
        lambda hh: jnp.zeros(hh.shape[:-1] + (w.shape[0],), hh.dtype), h)
    ce = sharded_softmax_ce(ll, mb["labels"])
    mask = mb["mask"].astype(jnp.float32)
    num = jnp.sum(ce * mask)
    den = jnp.sum(mask)
    # Ratio-of-sums across ALL shards of the micro-batch (data rows +,
    # under seq-manual, token shards): PSUM both sides so the ratio and
    # its div0 clamp see the true micro-batch totals — per-shard ratios
    # would weight shards equally regardless of their mask counts, and
    # a pmean'd denominator would silently engage the clamp on sparse
    # masks (review finding: 2x/4x loss shrink).  Gradient calibration:
    # the psum transposes overcount by the shard count, and the
    # engines' final grad pmean over exactly those axes
    # (grad_mean_axes) divides it back out — the same cancellation as
    # the GPT emit's pmean form.
    red = ((constants.DATA_AXIS, constants.SEQ_AXIS) if seq_manual
           else (constants.DATA_AXIS,))
    num = jax.lax.psum(num, red)
    den = jax.lax.psum(den, red)
    return num / jnp.maximum(den, 1.0)

  engine_cache = {}
  # Shared K-pass stacking convention with the GPT wiring.
  to_engine_tree, from_engine_grads = make_engine_tree_fns(K)

  # ZeRO-1 (config zero.level="v1"): engine grad reduction becomes the
  # owner reduce-scatter, exactly as in the GPT wiring.
  zero1_dp = 0
  if Env.get().config.zero.level == constants.ZERO_V1:
    zero1_dp = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        constants.DATA_AXIS, 1)
    if zero1_dp <= 1:
      zero1_dp = 0

  def grad_fn(params, batch, rng, loss_scale=None):
    check_seq_token_count(batch["ids"].shape[1], seq_size, seq_manual)
    un = to_engine_tree(nn.meta.unbox(params))
    if "fn" not in engine_cache:
      specs = stage_stacked_specs(un)
      specs["wte"]["embedding"] = P(constants.STAGE_AXIS, None)
      manual, bspec = seq_engine_axes(seq_manual)
      uniform = seq_manual or None
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
            batch_spec=bspec, manual_axes=manual,
            uniform_compute=uniform, zero1=zero1)
      else:
        build = (make_smap_1f1b_grad_fn if schedule == "1f1b"
                 else make_smap_gpipe_grad_fn)
        engine_cache["fn"] = build(
            feed_fn, stage_fn, emit_fn, S, M, mesh, specs,
            batch_spec=bspec, manual_axes=manual,
            uniform_compute=uniform, zero1=zero1)
    mbs = split_micro_batches(
        {k: v for k, v in batch.items()
         if k in ("ids", "labels", "mask", "type_ids")}, M)
    (loss, metrics), g = run_smap_engine(
        engine_cache["fn"], schedule, un, mbs, rng, loss_scale)
    metrics = {k: v for k, v in dict(metrics).items()
               if k != "stage_aux_loss"}
    return (loss, metrics), rebox_grads(params, from_engine_grads(g))

  return grad_fn


def make_bert_train_step(model: Bert, config=None):
  """Config-driven train step for BERT, engine-aware (the BERT analog of
  models/gpt.py:make_gpt_train_step): ``pipeline.engine="smap"`` with
  pipeline stages dispatches the shard_map engine (schedule policy picks
  gpipe/1f1b order); everything else uses the standard autodiff path
  over :func:`bert_mlm_loss`."""
  from easyparallellibrary_tpu.env import Env
  from easyparallellibrary_tpu.runtime.trainer import build_train_step
  from easyparallellibrary_tpu.strategies.scheduler import get_scheduler

  cfg = model.cfg
  conf = config if config is not None else Env.get().config
  if cfg.pipeline_stages > 1 and not cfg.pipeline_debug_sequential:
    sched = get_scheduler(cfg.pipeline_schedule or conf.pipeline.strategy)
    if conf.pipeline.engine == "smap":
      groups = None
      if sched.grouped_apply and conf.optimizer.num_apply_group <= 1:
        groups = cfg.pipeline_stages
      schedule = "1f1b" if sched.remat_stage else "gpipe"
      return build_train_step(
          grad_fn=make_bert_smap_grad_fn(model, schedule=schedule),
          config=conf, num_apply_group=groups)
    if sched.remat_stage:
      # Unlike GPT, BERT has no vmapped 1F1B grad_fn: without the smap
      # engine, PreferBackward* falls back to GPipe-order autodiff (M
      # live activations per stage).  Say so instead of silently
      # mislabeling memory behavior.
      from easyparallellibrary_tpu.utils.logging import get_logger
      get_logger().warning(
          "pipeline.strategy=%s on BERT runs as GPipe-order autodiff "
          "unless pipeline.engine='smap' (no vmapped 1F1B wiring for "
          "BERT); set pipeline.engine='smap' for true 1F1B order.",
          sched.name)
  return build_train_step(lambda p, b, r: bert_mlm_loss(model, p, b, r),
                          config=conf)
