"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the only one that touches JAX, drives the main path once at
the full width of GPT-350M (24 layers, d 1024, 16 heads, d_ff 4096, vocab
32768, S 1024, bf16; random weights from a seed) through the entry points
a user calls, and checks what comes out by the repo's own references:

  kernels         flash fwd + dk/dv + dq (resident and streaming) against
                  ``_dense_causal_attention``; paged attention (f32, bf16)
                  against ``paged_attention_reference``; ``kv_write``
                  (bit for bit) and ``slot_attn`` against their reference
                  lowerings at the serving cells' leaves, kept in rows
                  (GPT-2 medium ``[96, 1040, 1024]`` chunk 16, the hybrid
                  ``[128, 8200, 128]`` chunk 8) and in positions; all
                  compiled
  train           the GPT-350M trainer at the largest batch that fits:
                  2 warm-up + 5 steps
  serve           ``ContinuousBatchingEngine`` on the same model, 8
                  requests, contiguous then paged; greedy streams against
                  ``generate(use_cache=True)``, alone and beside one slot
                  that samples; where the compiled step keeps its sort
  hybrid          the selective scan (f32, bf16) against
                  ``ssm_scan_reference``; a four-layer cut of the hybrid
                  decoder (models/jamba.py: 3 Mamba + 1 attention layer at
                  AI21-Jamba2-3B's widths) through the engine: both
                  kernels resolved, one ``ssm_scan`` call a Mamba layer,
                  the fused step's logits against the reference lowering
  experts         the sparse-expert decoder with a latent cache
                  (models/glm_moe.py, GLM-4.7-Flash's widths): ``moe_gmm``,
                  the one-leaf ``kv_write`` and the one-leaf ``slot_attn``
                  at its cell's shapes (f32, bf16) against their reference
                  lowerings; a three-layer cut (one dense, two expert
                  layers, a vocabulary of 32768) through the engine: all
                  three kernels resolved, their calls counted, the fused
                  step's logits against the reference lowerings, and the
                  served tokens against the teacher-forced full forward
                  (expanded attention, ``ragged_dot``)
  lfm2            the decoder of gated short convolutions beside grouped
                  attention with routed experts and no shared one
                  (models/lfm2_moe.py, LFM2-8B-A1B's widths): ``kv_write``
                  and ``slot_attn`` in rows at its cell's leaf ``[128,
                  4112, 512]`` (8 K/V heads of 64 under 32 query heads:
                  heads that share a lane tile AND are grouped), ``moe_gmm``
                  at its two products over 32 experts (f32, bf16) against
                  their reference lowerings; a three-layer cut (conv +
                  dense, attention + experts, conv + experts) through the
                  engine as for ``experts``
  dots3           (PR 39) the decoder whose full layers select the rows
                  they attend and whose others attend behind a window
                  (models/dots3_note.py, dots3-note-prev's widths): the
                  ring ``kv_write``, ``dsa_index``, ``kth_largest``,
                  ``slot_attn_sel`` and ``slot_attn_win`` at its cell's
                  leaves (12,832 rows, rings of 640, chunk 32, selection
                  2048, window 513; f32, bf16) against their references; a
                  three-layer cut (full + dense, full + experts, window +
                  experts, 8 of 256 experts held) through the engine as
                  for ``experts``
  gigachat        (PR 50) the decoder of gated delta-rule linear attention
                  beside a gated latent attention (models/gigachat.py,
                  GigaChat3.5-432B-A28B's widths): every kernel rule at its
                  cell's shapes (128 slots x chunk 32) with what it
                  resolved, ``gdn_scan`` at its cell's heads (32 key | 64
                  value heads of 128, chunk 32; f32, bf16) against the
                  reference scan, both forms and an idle slot bit for bit;
                  a three-layer cut (linear + dense, full + experts, linear
                  + experts, 4 of 256 experts held) through the engine as
                  for ``experts``
  overlap         (PR 33) GPT-2 medium and the LFM2 cut, 32 requests each
                  through the engine's overlapped loop (step k+1 launched
                  before step k's tokens are fetched) and through the
                  serial one: every stream equal, one compile, no position
                  wasted; the two median periods side by side
  four chips      (when the machine has four) the trainer as ``data:4``
                  and as ``data:2,model:2``

Any failed check raises; nothing catches it, so the exit code is non-zero
and no result line is printed.  Finding no TPU is a failure.  The last
line of standard output of a passing run is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--only <phase>`` runs that phase alone (no result line: not the whole
proof).  ``--rehearse-cpu`` runs the same control flow at toy sizes with the
kernels interpreted, to debug the script without a chip.  It says so, it
prints no result line, and it always exits non-zero: it is not a pass.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.kernels import (
    flash_attention, kv_write_pallas, kv_write_reference,
    paged_attention_pallas, paged_attention_reference)
from easyparallellibrary_tpu.kernels.moe_gmm import (
    MOE_GMM, moe_gmm_pallas, moe_gmm_reference)
from easyparallellibrary_tpu.kernels.slot_attention import (
    SLOT_ATTN, block_positions, slot_attention_pallas,
    slot_attention_reference)
from easyparallellibrary_tpu.kernels.ssm_scan import (
    SSM_SCAN, ssm_scan_pallas, ssm_scan_reference)
from easyparallellibrary_tpu.kernels import dsa_index as dsa_lib
gdn_lib = importlib.import_module("easyparallellibrary_tpu.kernels.gdn_scan")
# the package's ``flash_attention`` is the function: the module by its name
fa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.flash_attention")
from easyparallellibrary_tpu.kernels import slot_attention as slot_attn_lib
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.dots3_note import (
    Dots3Note, Dots3NoteConfig)
from easyparallellibrary_tpu.models.gigachat import GigaChat, GigaChatConfig
from easyparallellibrary_tpu.models.glm_moe import GlmMoe, GlmMoeConfig
from easyparallellibrary_tpu.models.jamba import Jamba, JambaConfig
from easyparallellibrary_tpu.models.layer_kinds import FULL, MAMBA, SLIDING
from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from easyparallellibrary_tpu.models.smallthinker import (
    SmallThinker, SmallThinkerConfig)
from easyparallellibrary_tpu.models.gpt import (
    _dense_causal_attention, generate, gpt_loss, make_gpt_train_step)
from easyparallellibrary_tpu.models.slot_core import slot_step_logits
from easyparallellibrary_tpu.observability.device import specs_of
from easyparallellibrary_tpu.parallel import (
    TrainState, create_sharded_train_state, parallelize)
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.testing import chaos
from easyparallellibrary_tpu.testing.hlo import op_sites
from easyparallellibrary_tpu.utils import compile_cache
from easyparallellibrary_tpu.utils.pytree import tree_bytes

MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
REHEARSAL_EXIT = 2

# Largest batch first; the smaller ones are tried only when the larger
# one is refused for memory (RESOURCE_EXHAUSTED), never on another error.
BATCH_CANDIDATES = (16, 12, 8)


def gpt350m_config(**overrides) -> GPTConfig:
  """GPT-350M: 24L, d 1024, 16 heads, d_ff 4096, vocab 32768, S 1024,
  bf16.

  loss_chunk: the vocab-32k LM head was the round-1 memory bottleneck —
  chunked CE keeps the [B,S,V] logits out of HBM (tested equal to the
  full loss).  pallas_flash + dots_flash: the 512-block flash kernel
  removes the [B,H,S,S] score temps, and the dots_flash remat policy
  saves the kernel outputs so the backward never re-runs the forward
  kernel."""
  kw = dict(vocab_size=32768, num_layers=24, num_heads=16, d_model=1024,
            d_ff=4096, max_seq_len=1024, dtype=jnp.bfloat16, remat=True,
            attn_impl="pallas_flash", remat_policy="dots_flash",
            loss_chunk=256)
  kw.update(overrides)
  return GPTConfig(**kw)


class SmokeFailure(Exception):
  """A check did not hold."""


def check(ok, what: str) -> None:
  if not ok:
    raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
  """What one run drives.  ``real()`` is the contract; ``toy()`` exists
  for the CPU rehearsal only."""
  rehearsal: bool
  train_cfg: GPTConfig
  batch_candidates: tuple
  serve_cfg: GPTConfig            # the 24-layer bf16 server
  cut_cfg: GPTConfig              # float32 2-layer cut, same width
  prompt_lens: tuple
  new_tokens: int
  flash_shapes: tuple             # (B, H, S, D, dtype)
  paged_shape: tuple              # (T, H, hd, block, table width)
  kv_shapes: tuple                # (slots, Lc, H, H_kv, hd, chunk) each
  hybrid_cfg: JambaConfig         # four layers of the hybrid decoder
  ssm_scan_shape: tuple           # (slots, d_state, d_inner, chunk)
  experts_cfg: GlmMoeConfig       # one dense + two expert layers
  moe_gmm_shapes: tuple           # (rows, K, N, experts) of a layer's two
  latent_shape: tuple             # (slots, Lc, heads, latent, rank, chunk)
  lfm2_cfg: Lfm2MoeConfig         # conv + dense, attention + experts, conv
  lfm2_kv_shape: tuple            # (slots, Lc, H, H_kv, hd, chunk)
  lfm2_gmm_shapes: tuple          # (rows, K, N, experts) of a layer's two
  dots3_cfg: Dots3NoteConfig      # full + dense, full + experts, window
  dots3_shapes: tuple             # (slots, Lc, chunk, ring rows) of the
                                  # kernels' checks
  smallthinker_cfg: SmallThinkerConfig   # one period, the window cut
  smallthinker_cell: tuple        # (cell's config, slots, chunk): the
                                  # kernels' checks and the rules' report
  gigachat_cfg: GigaChatConfig    # linear + dense, full + experts, linear
  gigachat_cell: tuple            # (cell's config, slots, chunk)

  @staticmethod
  def real() -> "Sizes":
    serve = gpt350m_config(remat=False, attn_impl="xla",
                                 remat_policy="nothing", loss_chunk=0)
    return Sizes(
        rehearsal=False,
        train_cfg=gpt350m_config(),
        batch_candidates=BATCH_CANDIDATES,
        serve_cfg=serve,
        cut_cfg=dataclasses.replace(serve, num_layers=2,
                                    dtype=jnp.float32),
        prompt_lens=(64, 160, 320, 512),
        new_tokens=32,
        # Resident at the trainer's shape and at the train cell's shape
        # a chip (gpt2l-train-zero1-4chip: 8 rows of 20 heads); streaming
        # past _RESIDENT_MAX_BYTES (two heads keep the dense reference's
        # [S, S] scores inside HBM).
        flash_shapes=((2, 16, 1024, 64, jnp.bfloat16),
                      (8, 20, 1024, 64, jnp.bfloat16),
                      (1, 2, 16384, 64, jnp.bfloat16)),
        paged_shape=(16, 16, 64, 16, 8),
        # The serving cells' leaves (1024 + one chunk of slack of GPT-2
        # medium's 16 heads of 64, 8192 + a chunk of the hybrid's one K/V
        # head of 128 under 20 query heads: both fill whole lane tiles
        # and are kept in rows), and every head on one narrow K/V head,
        # which stays in positions.
        kv_shapes=((96, 1040, 16, 16, 64, 16), (128, 8200, 20, 1, 128, 8),
                   (8, 1040, 16, 1, 64, 16)),
        # AI21-Jamba2-3B's widths, one attention layer among three Mamba
        # layers instead of two among 26; a cache for 1024 positions.
        hybrid_cfg=JambaConfig(num_layers=4, attn_layer_period=4,
                               attn_layer_offset=1, max_seq_len=1024),
        ssm_scan_shape=(16, 16, 5120, 8),
        # GLM-4.7-Flash's widths, every one of its 64 experts; the
        # vocabulary cut to 32768 and the context to 1024 so that a
        # float32 copy fits beside nothing else.
        experts_cfg=GlmMoeConfig(vocab_size=32768, num_layers=3,
                                 max_seq_len=1024, dtype=jnp.float32,
                                 param_dtype=jnp.float32),
        # The cell's: 96 slots x chunk 8 x 4 experts a token.
        moe_gmm_shapes=((3072, 2048, 3072, 64), (3072, 1536, 2048, 64)),
        latent_shape=(8, 4104, 20, 576, 512, 8),
        # LFM2-8B-A1B's widths, every one of its 32 experts, one layer of
        # each kind of mixer and of feed-forward; vocabulary and context
        # cut as for the expert cut above.
        lfm2_cfg=Lfm2MoeConfig(
            vocab_size=32768, layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, max_seq_len=1024, dtype=jnp.float32,
            param_dtype=jnp.float32),
        # The cell's: 128 slots x (4096 + 16) positions x 8 K/V heads of
        # 64; 128 slots x chunk 16 x 4 experts a token.
        lfm2_kv_shape=(128, 4112, 32, 8, 64, 16),
        lfm2_gmm_shapes=((8192, 2048, 3584, 32), (8192, 1792, 2048, 32)),
        # dots3-note-prev's widths, one layer of each kind of mixer and of
        # feed-forward, 8 of its 256 experts held (the router keeps its
        # width); vocabulary and context cut as above, and the selection
        # (128 rows) and the window (129: a ring of 256 rows) cut so that
        # a request of a few hundred positions discards rows and wraps.
        dots3_cfg=Dots3NoteConfig(
            vocab_size=8192, layer_types=(FULL, FULL, SLIDING),
            experts_held=(8, 8), index_topk=128, sliding_window=129,
            max_seq_len=1024, dtype=jnp.float32, param_dtype=jnp.float32),
        # The cell's leaves (12,800 + 32 positions, rings of 640 rows) at
        # its chunk, on 8 slots (the references' score tensors for 32
        # would not fit).
        dots3_shapes=(8, 12832, 32, 640),
        # SmallThinker-21BA3B's widths, one period (a full layer without
        # positions, three window layers with rotary), every one of its 64
        # experts; vocabulary and context cut as above, and the window cut
        # to 129 (a ring of 256 rows) so that a request of a few hundred
        # positions wraps it.  The kernels and the rules at the cell's own
        # geometry: the published two periods in bfloat16, 48 slots x chunk
        # 32, window 4096 (rings of 4,224 rows), context 16384.
        smallthinker_cfg=SmallThinkerConfig(
            vocab_size=32768, window_layout=(0, 1, 1, 1),
            rope_layout=(0, 1, 1, 1), sliding_window=129, max_seq_len=1024,
            dtype=jnp.float32, param_dtype=jnp.float32),
        smallthinker_cell=(SmallThinkerConfig(
            window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2),
                           48, 32),
        # GigaChat3.5's widths: a dense linear layer, a full expert layer,
        # a linear expert layer, 4 of its 256 experts held (float32 weights
        # of 16 would not fit beside the check); vocabulary and context cut
        # as above.  The rules and the kernel at the cell's own geometry:
        # the cut of perfbench/configs/gigachat3.5-432b-a28b.json in
        # bfloat16, 128 slots x chunk 32, context 4096.
        gigachat_cfg=GigaChatConfig(
            vocab_size=16032, num_layers=3, full_attention_layers=(1,),
            first_k_dense=1, experts_held=(0, 4), max_seq_len=1024,
            dtype=jnp.float32, param_dtype=jnp.float32),
        gigachat_cell=(GigaChatConfig(
            vocab_size=16032, num_layers=5, full_attention_layers=(1,),
            first_k_dense=1, experts_held=(0, 16)), 128, 32))

  @staticmethod
  def toy() -> "Sizes":
    train = GPTConfig(vocab_size=512, num_layers=2, num_heads=4,
                      d_model=128, d_ff=256, max_seq_len=128,
                      dtype=jnp.float32, remat=True,
                      attn_impl="pallas_flash", remat_policy="dots_flash",
                      loss_chunk=32)
    serve = dataclasses.replace(train, remat=False, attn_impl="xla",
                                remat_policy="nothing", loss_chunk=0)
    return Sizes(
        rehearsal=True, train_cfg=train, batch_candidates=(4,),
        serve_cfg=serve, cut_cfg=serve, prompt_lens=(8, 20, 40, 64),
        new_tokens=8,
        flash_shapes=((1, 4, 128, 32, jnp.float32),   # rows: four heads a tile
                      (1, 1, 256, 32, jnp.float32)),
        paged_shape=(6, 4, 32, 8, 4),
        kv_shapes=((4, 136, 4, 4, 32, 8), (4, 136, 4, 1, 32, 8)),
        hybrid_cfg=JambaConfig(
            vocab_size=512, num_layers=4, d_model=64, d_ff=128, num_heads=4,
            num_kv_heads=1, attn_layer_period=4, attn_layer_offset=1,
            mamba_dt_rank=4, max_seq_len=128, dtype=jnp.float32,
            param_dtype=jnp.float32),
        ssm_scan_shape=(4, 16, 128, 4),
        experts_cfg=GlmMoeConfig(
            vocab_size=512, num_layers=3, d_model=128, d_ff=256,
            moe_d_ff=128, num_heads=4, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2, max_seq_len=128,
            dtype=jnp.float32, param_dtype=jnp.float32),
        moe_gmm_shapes=((200, 128, 256, 8),),
        latent_shape=(4, 136, 4, 40, 32, 8),
        lfm2_cfg=Lfm2MoeConfig(
            vocab_size=512, d_model=256, d_ff=256, moe_d_ff=128, num_heads=4,
            num_kv_heads=2, layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, n_routed_experts=8, num_experts_per_tok=2,
            max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32),
        lfm2_kv_shape=(4, 136, 4, 2, 64, 8),
        lfm2_gmm_shapes=((200, 256, 256, 8),),
        dots3_cfg=Dots3NoteConfig(
            vocab_size=512, layer_types=(FULL, FULL, SLIDING), d_model=128,
            d_ff=256, moe_d_ff=128, num_heads=8, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, index_n_heads=2, index_head_dim=128,
            index_topk=16, sliding_window=33, swa_num_heads=8,
            swa_q_lora_rank=32, swa_kv_lora_rank=48, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=8, swa_v_head_dim=16, n_routed_experts=8,
            experts_held=(2, 4), num_experts_per_tok=2, max_seq_len=256,
            dtype=jnp.float32, param_dtype=jnp.float32),
        dots3_shapes=(4, 264, 8, 128),
        smallthinker_cfg=SmallThinkerConfig(
            vocab_size=512, d_model=128, num_heads=14, num_kv_heads=2,
            head_dim=16, moe_d_ff=128, n_routed_experts=8,
            num_experts_per_tok=3, sliding_window=33,
            window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
            max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32),
        smallthinker_cell=(SmallThinkerConfig(
            vocab_size=512, d_model=128, num_heads=14, num_kv_heads=2,
            head_dim=128, moe_d_ff=128, n_routed_experts=8,
            num_experts_per_tok=3, sliding_window=100,
            window_layout=(0, 1), rope_layout=(0, 1), max_seq_len=256,
            dtype=jnp.float32), 6, 16),
        gigachat_cfg=GigaChatConfig(
            vocab_size=512, num_layers=3, full_attention_layers=(1,),
            d_model=128, d_ff=256, moe_d_ff=128, num_heads=2,
            q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, rope_original_max=64,
            linear_num_key_heads=1, linear_num_value_heads=2,
            n_routed_experts=8, experts_held=(2, 4), num_experts_per_tok=2,
            first_k_dense=1, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32),
        gigachat_cell=(GigaChatConfig(
            vocab_size=512, num_layers=2, full_attention_layers=(1,),
            d_model=128, d_ff=256, moe_d_ff=128, num_heads=2,
            q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, linear_num_key_heads=1,
            linear_num_value_heads=2, n_routed_experts=8,
            experts_held=(2, 4), num_experts_per_tok=2, first_k_dense=1,
            max_seq_len=256, dtype=jnp.float32), 6, 16))


def say(msg: str) -> None:
  print(msg, flush=True)


def rel_err(got, ref) -> float:
  """Largest deviation as a share of the reference's largest value."""
  got = np.asarray(got, np.float32)
  ref = np.asarray(ref, np.float32)
  return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def compile_here(fn, *args, mosaic_calls: int, rehearsal: bool):
  """AOT-compile ``fn`` for these arguments and prove the kernel did not
  run interpreted: the optimized program holds exactly ``mosaic_calls``
  Mosaic custom calls."""
  compiled = jax.jit(fn).lower(*args).compile()
  if not rehearsal:
    found = compiled.as_text().count(MOSAIC_CALL)
    check(found == mosaic_calls,
          f"expected {mosaic_calls} Mosaic custom calls in the compiled "
          f"program, found {found}: the kernel ran interpreted or was "
          "not reached")
  return compiled


# ---------------------------------------------------------------- kernels --


def check_flash(B, H, S, D, dtype, rehearsal: bool) -> None:
  """The flash kernels against the dense float32 reference, through every
  form this shape can take: the entry as the rule lays it out
  (``flash_layout``: rows where the heads fill lane tiles and a head is
  resident), and where that is ``rows`` also the fused projection's one
  ``[B, S, 3 x H x D]`` operand (the train cell's call) and the head-major
  kernels behind their transposes (ring attention's primitives)."""
  r = np.random.RandomState(S)
  q, k, v, dout = (jnp.asarray(r.randn(B, S, H, D), dtype)
                   for _ in range(4))
  itemsize = jnp.dtype(dtype).itemsize
  layout = fa.flash_layout(S, H, D, itemsize)
  bq = fa._default_block(S, d=D, itemsize=itemsize)

  def fwd_bwd(attend, q, k, v, dout):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(dout.astype(out.dtype))

  def from_qkv(q, k, v):
    cut = lambda x: x.reshape(B, S, H * D)
    qkv = jnp.concatenate([cut(q), cut(k), cut(v)], axis=-1)
    return fa.flash_attention_qkv(qkv, H, causal=True).reshape(B, S, H, D)

  def head_major(q, k, v):
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(fa._flash(t(q), t(k), t(v), True, bq, bq))

  forms = {layout: functools.partial(flash_attention, causal=True)}
  if fa.flash_layout(S, H, D, itemsize, fused=True) == "rows":
    forms["rows of one qkv"] = from_qkv
  forms.setdefault("heads", head_major)
  # The reference sees the same (storage-dtype) values in float32, so
  # the difference is the kernel's own error, not the reference's.
  f32 = [x.astype(jnp.float32) for x in (q, k, v, dout)]
  with jax.default_matmul_precision("highest"):
    ref = jax.jit(functools.partial(
        fwd_bwd, lambda q, k, v: _dense_causal_attention(
            q, k, v, jnp.float32)))(*f32)
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  for form, attend in forms.items():
    kernel = compile_here(functools.partial(fwd_bwd, attend), q, k, v, dout,
                          mosaic_calls=3, rehearsal=rehearsal)
    got = kernel(q, k, v, dout)
    errs = {}
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, ref):
      check(bool(jnp.isfinite(g.astype(jnp.float32)).all()),
            f"flash {name} in {form} not finite at {(B, H, S, D)}")
      errs[name] = rel_err(g, w)
      check(errs[name] <= tol,
            f"flash {name} in {form} at {(B, H, S, D)} "
            f"{jnp.dtype(dtype).name}: error {errs[name]:.3g} of the "
            f"reference's max, tol {tol}")
    say(f"  flash B{B} H{H} S{S} D{D} {jnp.dtype(dtype).name} in {form}: "
        + " ".join(f"{n} {e:.2e}" for n, e in errs.items())
        + f" (tol {tol})")


def check_paged(T, H, hd, bs, MB, dtype, rehearsal: bool) -> None:
  r = np.random.RandomState(1)
  NB = 2 * MB + 1
  q = jnp.asarray(r.randn(T, H, hd), dtype)
  kp = jnp.asarray(r.randn(NB, bs, H, hd), dtype)
  vp = jnp.asarray(r.randn(NB, bs, H, hd), dtype)
  tables = jnp.asarray(r.randint(0, NB, (T, MB)), jnp.int32)
  positions = jnp.asarray(r.randint(0, MB * bs, (T,)), jnp.int32)
  args = (q, kp, vp, tables, positions)
  kernel = compile_here(
      functools.partial(paged_attention_pallas, interpret=rehearsal),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  got = kernel(*args)
  with jax.default_matmul_precision("highest"):
    ref = jax.jit(paged_attention_reference)(*args)
  rtol, atol = (2e-2, 2e-2) if dtype == jnp.bfloat16 else (2e-5, 2e-6)
  got32, ref32 = (np.asarray(x, np.float32) for x in (got, ref))
  check(np.isfinite(got32).all(), "paged attention output not finite")
  check(np.allclose(got32, ref32, rtol=rtol, atol=atol),
        f"paged attention {jnp.dtype(dtype).name}: max abs error "
        f"{np.abs(got32 - ref32).max():.3g} (rtol {rtol}, atol {atol})")
  say(f"  paged T{T} H{H} hd{hd} block{bs} {jnp.dtype(dtype).name}: "
      f"max abs error {np.abs(got32 - ref32).max():.2e}")


def leaf_orders(Hkv, hd):
  """The orders a ``[slots, Lc, H_kv, hd]`` leaf can be kept in: positions
  always (what every leaf was, and what a narrow one stays), rows where
  the heads' width fills whole lane tiles (what ``cache_leaves`` then
  makes it)."""
  return ("rows", "positions") if (Hkv * hd) % 128 == 0 else ("positions",)


def in_order(x, order):
  """A ``[slots, Lc, H_kv, hd]`` leaf in ``order``."""
  return x.reshape(x.shape[:2] + (-1,)) if order == "rows" else x


def check_kv_write(B, Lc, H, hd, C, dtype, order, rehearsal: bool) -> None:
  """The in-place window write against ``vmap(dynamic_update_slice)``,
  bit for bit over both whole leaves kept in ``order``: cursors at a
  leaf's start, across a 128-position boundary (and so across a stripe's
  edge, on an odd row), and at the last legal window.  The rows form is
  also told that two slots are idle: theirs it must leave as they were."""
  r = np.random.RandomState(2)
  ck, cv = (in_order(jnp.asarray(r.randn(B, Lc, H, hd), dtype), order)
            for _ in range(2))
  k, v = (jnp.asarray(r.randn(B, C, H, hd), dtype) for _ in range(2))
  cursors = jnp.asarray(
      ([0, 128 - C // 2, Lc - C, 127] + list(r.randint(0, Lc - C, B)))[:B],
      jnp.int32)
  fed = np.ones((B,), bool)
  if order == "rows":
    fed[[1, B - 1]] = False
  args = (ck, cv, k, v, cursors, jnp.asarray(fed, jnp.int32))
  kernel = compile_here(
      functools.partial(kv_write_pallas, interpret=rehearsal),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  bits = lambda x: np.asarray(x).view(
      {2: np.uint16, 4: np.uint32}[x.dtype.itemsize])
  for name, g, w, old in zip("KV", kernel(*args),
                             jax.jit(kv_write_reference)(*args[:5]),
                             (ck, cv)):
    check((bits(g)[fed] == bits(w)[fed]).all(),
          f"kv_write {name} leaf {jnp.dtype(dtype).name} in {order} differs "
          f"from the reference in {(bits(g)[fed] != bits(w)[fed]).sum()} "
          "elements")
    check((bits(g)[~fed] == bits(old)[~fed]).all(),
          f"kv_write {name} leaf in {order}: an idle slot was written")
  say(f"  kv_write slots{B} Lc{Lc} H{H} hd{hd} chunk{C} "
      f"{jnp.dtype(dtype).name} in {order}: K and V leaves bit-identical"
      + (", idle slots untouched" if order == "rows" else ""))


def edge_cases(r, B, Lc, C, block):
  """``(cursors, num_valid)`` for an attend's check: a leaf's start, on
  and across a block's edge, the last legal window, a bound inside a
  tail's last granule, then random ones (each a bound somewhere inside a
  tail piece); a partial chunk and an idle slot among them; the LAST slot
  full to the leaf's last row (a copy that went beyond it would leave the
  array)."""
  cursors = np.asarray(
      ([0, block - C // 2, Lc - C, block, 7, min(block + 37, Lc - C)]
       + list(r.randint(0, Lc - C, B)))[:B], np.int32)
  num_valid = np.asarray(([C, C, C, 1, 0, C // 2 or 1] + [1] * B)[:B],
                         np.int32)
  cursors[-1], num_valid[-1] = Lc - C, C
  return cursors, num_valid


def check_slot_attn(B, Lc, H, Hkv, hd, C, dtype, order,
                    rehearsal: bool) -> None:
  """The live-rows attend over leaves kept in ``order`` against the
  einsums over every row: cursors at a leaf's start, on and across a
  block's edge and at the last legal window, a partial chunk, an idle
  slot, and NaN in every row at or beyond a slot's bound (the kernel must
  not read them; the reference gets the clean leaves)."""
  r = np.random.RandomState(3)
  q = jnp.asarray(r.randn(B, C, H, hd), dtype)
  ck, cv = (r.randn(B, Lc, Hkv, hd).astype(np.float32) for _ in range(2))
  block = block_positions(in_order(ck, order).shape, dtype, C, H, hd)
  cursors, num_valid = edge_cases(r, B, Lc, C, block)
  dirty_k, dirty_v = ck.copy(), cv.copy()
  for b in range(B):
    bound = cursors[b] + num_valid[b] if num_valid[b] else 0
    dirty_k[b, bound:] = np.nan
    dirty_v[b, bound:] = np.nan
  args = (q, in_order(jnp.asarray(dirty_k, dtype), order),
          in_order(jnp.asarray(dirty_v, dtype), order),
          jnp.asarray(cursors), jnp.asarray(num_valid))
  # Both sides at the highest precision: float32 operands then multiply
  # as float32 in the kernel too (16-bit ones are exact either way).
  with jax.default_matmul_precision("highest"):
    kernel = compile_here(
        functools.partial(slot_attention_pallas.__wrapped__,
                          interpret=rehearsal),
        *args, mosaic_calls=1, rehearsal=rehearsal)
    got = np.asarray(kernel(*args), np.float32)
    ref = np.asarray(jax.jit(slot_attention_reference)(
        q.astype(jnp.float32),
        in_order(jnp.asarray(ck, dtype).astype(jnp.float32), order),
        in_order(jnp.asarray(cv, dtype).astype(jnp.float32), order),
        jnp.asarray(cursors)))
  real = (np.arange(C)[None] < num_valid[:, None])[:, :, None, None]
  check(np.isfinite(got).all(), "slot_attn output not finite")
  check((np.where(real, 0, got) == 0).all(),
        "slot_attn: rows it does not compute are not zeros")
  err = rel_err(np.where(real, got, 0), np.where(real, ref, 0))
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  check(err <= tol, f"slot_attn {jnp.dtype(dtype).name} in {order}: error "
        f"{err:.3g} of the reference's max, tol {tol}")
  say(f"  slot_attn slots{B} Lc{Lc} H{H}/{Hkv} hd{hd} chunk{C} block"
      f"{block} {jnp.dtype(dtype).name} in {order}: {err:.2e} of the "
      "reference's max, NaN beyond the bounds unread, idle rows zeros")


def phase_kernels(sizes: Sizes) -> None:
  for shape in sizes.flash_shapes:
    check_flash(*shape, rehearsal=sizes.rehearsal)
  for dtype in (jnp.float32, jnp.bfloat16):
    check_paged(*sizes.paged_shape, dtype, rehearsal=sizes.rehearsal)
    for B, Lc, H, Hkv, hd, C in sizes.kv_shapes:
      for order in leaf_orders(Hkv, hd):
        check_kv_write(B, Lc, Hkv, hd, C, dtype, order,
                       rehearsal=sizes.rehearsal)
        check_slot_attn(B, Lc, H, Hkv, hd, C, dtype, order,
                        rehearsal=sizes.rehearsal)
  say("PASS kernels: flash fwd/bwd "
      + ("at toy shapes, paged, kv_write and slot_attn f32 + bf16 in rows "
         "and in positions, all INTERPRETED" if sizes.rehearsal else
         "resident + streaming, paged, kv_write and slot_attn at the "
         "serving cells' leaves (GPT-2 medium's, the hybrid's one K/V "
         "head, and every head on one narrow head) f32 + bf16, in rows and "
         "in positions, all compiled")
      + ", within tolerance")


# ------------------------------------------------------------------ train --


def seeded_batch(cfg: GPTConfig, batch_size: int, seed: int = 0):
  ids = np.random.RandomState(seed).randint(
      0, cfg.vocab_size, (batch_size, cfg.max_seq_len + 1))
  return {"ids": jnp.asarray(ids, jnp.int32)}


def build_trainer(model: GPT, mesh, batch, seed: int = 0):
  """``(state, step)`` through the library's normal entry points: a
  sharded AdamW train state and the config-dispatched GPT train step
  compiled over ``mesh`` (what examples/train_gpt.py does)."""
  tx = optax.adamw(3e-4, weight_decay=0.01)

  def init_fn(r):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(r, batch["ids"][:, :-1])["params"], tx=tx)

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(seed))
  return state, parallelize(make_gpt_train_step(model), mesh, shardings)


def largest_batch_trainer(model: GPT, mesh, candidates=BATCH_CANDIDATES,
                          per_replica: int = 1):
  """Build the trainer at the first candidate batch that fits and take
  its first step (compile and first execution are where a batch too
  large for the chip is refused); ``per_replica`` scales the candidates
  to a global batch.  Returns ``(state, step, batch, first_metrics)``."""
  rng = jax.random.PRNGKey(0)
  for i, cand in enumerate(candidates):
    batch = seeded_batch(model.cfg, cand * per_replica)
    state = step = None
    try:
      state, step = build_trainer(model, mesh, batch)
      state, metrics = step(state, batch, rng)
      return state, step, batch, jax.block_until_ready(metrics)
    except jax.errors.JaxRuntimeError as e:
      if "RESOURCE_EXHAUSTED" not in str(e) or i == len(candidates) - 1:
        raise
      print(f"chip_smoke: batch {cand} out of device memory, trying "
            f"{candidates[i + 1]}", file=sys.stderr)
  raise ValueError("no batch candidates")


def flash_call_operands(hlo: str):
  """Operand shapes of every Mosaic custom call in an optimized HLO
  module, as ``{(shape, ...): count}``."""
  seen = {}
  for line in hlo.splitlines():
    if MOSAIC_CALL not in line:
      continue
    ops = line.split("operand_layout_constraints={")[1].split("}, ")[0]
    key = tuple(re.findall(r"\w+\[[\d,]*\]", ops))
    seen[key] = seen.get(key, 0) + 1
  return seen


def take_steps(step, state, batch, n: int):
  """``n`` steps on the fixed batch, each timed both ways: host clock
  to ``block_until_ready`` on everything the step returns, then on to a
  scalar fetch that depends on the step.  If ``block_until_ready``
  waits for the device the second adds next to nothing."""
  rng = jax.random.PRNGKey(0)
  losses, ready_ms, fetch_ms = [], [], []
  for _ in range(n):
    t0 = time.perf_counter()
    state, metrics = step(state, batch, rng)
    jax.block_until_ready((state, metrics))
    t1 = time.perf_counter()
    losses.append(float(jax.device_get(metrics["loss"])))
    t2 = time.perf_counter()
    ready_ms.append(1e3 * (t1 - t0))
    fetch_ms.append(1e3 * (t2 - t0))
  return state, losses, ready_ms, fetch_ms


def run_trainer(sizes: Sizes, devices, tensor_parallel: bool = False):
  """The trainer through its normal entry points on ``devices``: 2
  warm-up + 5 steps on a fixed seeded batch, checked.  Returns
  ``(state, batch, losses, hlo)`` for the checks a layout adds."""
  epl.init(devices=devices)
  cfg = dataclasses.replace(sizes.train_cfg,
                            tensor_parallel=tensor_parallel)
  with epl.replicate(1):
    model = GPT(cfg)
  if tensor_parallel:
    with epl.split(2):
      pass
  mesh = epl.current_plan().build_mesh()
  shape = {a: s for a, s in zip(mesh.axis_names, mesh.devices.shape)
           if s > 1}
  say(f"  mesh {shape or '{single chip}'} over devices "
      f"{[d.id for d in mesh.devices.reshape(-1)]}")

  t0 = time.perf_counter()
  state, step, batch, first = largest_batch_trainer(
      model, mesh, candidates=sizes.batch_candidates,
      per_replica=len(devices))
  losses = [float(first["loss"])]
  say(f"  set-up (init + compile + first step): "
      f"{time.perf_counter() - t0:.1f} s, global batch "
      f"{batch['ids'].shape[0]}")
  state, later, ready_ms, fetch_ms = take_steps(step, state, batch, 6)
  losses += later
  say("  loss per step: " + " ".join(f"{l:.4f}" for l in losses))
  say("  step ms to block_until_ready: "      # the 5 after warm-up
      + " ".join(f"{t:.1f}" for t in ready_ms[1:]))
  say("  step ms to dependent scalar fetch: "
      + " ".join(f"{t:.1f}" for t in fetch_ms[1:]))
  check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
  check(losses[-1] < losses[0],
        f"loss did not fall: {losses[0]} -> {losses[-1]}")
  check(step.jitted._cache_size() == 1,
        f"train step compiled {step.jitted._cache_size()} times")
  compiled = step.jitted.lower(state, batch,
                               jax.random.PRNGKey(0)).compile()
  hlo = compiled.as_text()
  plan = compiled.memory_analysis()
  say(f"  compiled step, per chip: arguments "
      f"{plan.argument_size_in_bytes / 2 ** 30:.2f} GiB + temporaries "
      f"{plan.temp_size_in_bytes / 2 ** 30:.2f} GiB (XLA's plan)")
  if not sizes.rehearsal:
    calls = hlo.count(MOSAIC_CALL)
    check(calls == 3 * cfg.num_layers,
          f"{calls} Mosaic custom calls in the train step, expected "
          f"{3 * cfg.num_layers} (fwd, dk/dv, dq per layer)")
  return state, batch, losses, hlo


def peak_hbm(dev, at_least: int) -> str:
  """Device memory high-water marks since the process started, as the
  backend reports them, in words: resident buffers
  (``peak_bytes_in_use`` — a real number, at least what the state
  takes) and what it reserved to run programs (``peak_bytes_reserved``:
  the largest step's temporaries).  Within one phase they add up to the
  peak; across phases each mark may come from a different program."""
  stats = dev.memory_stats()
  check(stats is not None, f"memory_stats() is None on {dev}")
  used = stats["peak_bytes_in_use"]
  check(used >= at_least,
        f"peak_bytes_in_use {used} on {dev} is below the {at_least} "
        "bytes the state alone takes")
  gib = 2 ** 30
  return (f"{used / gib:.2f} in use, "
          f"{stats['peak_bytes_reserved'] / gib:.2f} reserved, limit "
          f"{stats['bytes_limit'] / gib:.2f}")


def phase_train(sizes: Sizes, dev) -> None:
  state, batch, _, _ = run_trainer(sizes, [dev])
  if not sizes.rehearsal:
    say(f"  peak HBM GiB at batch {batch['ids'].shape[0]}: "
        f"{peak_hbm(dev, tree_bytes(state))} (memory_stats)")
  say("PASS train: loss finite and falling, zero recompiles after "
      "warm-up"
      + ("" if sizes.rehearsal else ", Mosaic calls present, real peak "
                                    "HBM"))


# ------------------------------------------------------------------ serve --


def seeded_requests(sizes: Sizes, cfg: GPTConfig, n: int = 8):
  r = np.random.RandomState(0)
  lens = [sizes.prompt_lens[i % len(sizes.prompt_lens)] for i in range(n)]
  return [r.randint(0, cfg.vocab_size, (n_tok,)).astype(np.int32)
          for n_tok in r.permutation(lens)]


class _StepSpecs(chaos._StepFnWrapper):
  """Remembers the fused step's argument specs, so the program the
  engine compiled can be lowered again and read."""
  specs = None

  def __call__(self, *args):
    if self.specs is None:
      self.specs = specs_of(args)
    return self.inner(*args)


def serve(model, params, prompts, new_tokens: int, paged: bool,
          rehearsal: bool, sampled: bool = False):
  """All requests through one engine at the default ``serving.*``
  config, to completion; with ``sampled``, one more request rides along
  that samples (temperature, top-k and top-p on), so the greedy ones
  share their steps with the sampling branch.  Returns
  ``{uid: prompt + generated}`` of the greedy requests."""
  eng = ContinuousBatchingEngine(model, params, paged=paged)
  spy = _StepSpecs(eng)
  for uid, p in enumerate(prompts):
    check(eng.submit(Request(uid=uid, prompt=p,
                             max_new_tokens=new_tokens)),
          f"request {uid} refused at admission")
  if sampled:
    check(eng.submit(Request(uid="sampled", prompt=prompts[0],
                             max_new_tokens=new_tokens, temperature=0.8,
                             top_k=40, top_p=0.95, seed=7)),
          "the sampled request was refused at admission")
  out = eng.run()
  if sampled:
    check(len(out.pop("sampled")) == len(prompts[0]) + new_tokens,
          "the sampled request did not run to its length")
  for uid, p in enumerate(prompts):
    check(uid in out, f"request {uid} never finished")
    check(eng.finished[uid].finish_reason == "length"
          and len(out[uid]) == len(p) + new_tokens,
          f"request {uid}: {eng.finished[uid].finish_reason}, "
          f"{len(out[uid])} tokens for a {len(p)}-token prompt")
    check((out[uid][:len(p)] == p).all(), f"request {uid}: prompt changed")
  check(spy._cache_size() == 1,
        f"fused step compiled {spy._cache_size()} times")
  # The step sorts the vocabulary only for slots that ask for it: the
  # program this backend built keeps its one sort in a branch.
  hlo = spy.inner.lower(*spy.specs).compile().as_text()
  always, in_branch = op_sites(hlo, "sort")
  check(not always and len(in_branch) == 1,
        f"fused step: sorts that every step runs {always}, sorts inside a "
        f"conditional {in_branch}; expected none and one")
  say(f"  fused {'paged' if paged else 'contiguous'} step, "
      f"{model.cfg.num_layers} layers: its one sort sits in branch "
      f"computation {in_branch[0]} of a conditional, none on the path "
      "every step takes")
  if not paged:
    say(f"  contiguous engine: {kv_lib.resolved(eng.lowerings)}")
  if not rehearsal:
    impls = ((eng._paged_impl,) if paged else
             tuple(kv_lib.resolved(eng.lowerings).values()))
    check(all(impl == "pallas" for impl in impls),
          f"{'paged attend' if paged else 'cache write and attend'} "
          f"resolved to {impls}, not the kernel")
    # One paged attend a layer; one write and one attend a layer.
    layers = model.cfg.num_layers
    calls = hlo.count(MOSAIC_CALL)
    check(calls == layers * len(impls),
          f"{calls} Mosaic custom calls in the fused "
          f"{'paged' if paged else 'contiguous'} step, expected "
          f"{len(impls)} per layer ({layers} layers)")
    if not paged:
      attends = named_calls(hlo, SLOT_ATTN)
      check(attends == layers,
            f"{attends} slot_attn calls in the contiguous step, expected "
            f"one per layer ({layers})")
  return out


def named_calls(hlo: str, name: str) -> int:
  """Custom calls of the kernel ``name`` in an optimized HLO module."""
  return len(re.findall(rf"%{name}[.\d]* = ", hlo))


def reference_streams(model, params, prompts, new_tokens: int):
  gen = jax.jit(lambda p, ids: generate(model, p, ids, new_tokens))
  return [np.asarray(gen(params, jnp.asarray(p)[None]))[0]
          for p in prompts]


def first_difference(a, b):
  diff = np.nonzero(np.asarray(a) != np.asarray(b))[0]
  return int(diff[0]) if diff.size else None


def padded_forward(model, params, streams):
  """Teacher-forced logits of every stream in one [n, max_seq_len]
  forward (causal: the padding cannot reach a real position).  Returns
  per-position ``(argmax, all-finite, top-2 gap)``."""
  ids = np.zeros((len(streams), model.cfg.max_seq_len), np.int32)
  for i, s in enumerate(streams):
    ids[i, :len(s)] = s

  @jax.jit
  def fwd(p, ids):
    logits = model.apply({"params": p}, ids).astype(jnp.float32)
    top2 = jax.lax.top_k(logits, 2)[0]
    return (jnp.argmax(logits, -1), jnp.isfinite(logits).all(-1),
            top2[..., 0] - top2[..., 1], jnp.abs(logits).max(-1))

  return [np.asarray(x) for x in fwd(params, jnp.asarray(ids))]


def phase_serve(sizes: Sizes) -> None:
  epl.init(devices=jax.devices()[:1])
  init = lambda model: jax.jit(lambda k: model.init(
      k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(0))
  new = sizes.new_tokens

  # Gate: a float32 2-layer cut of the same width must reproduce
  # generate(use_cache=True) token for token, contiguous and paged.  A
  # stream may differ only where the reference's own top-2 logits tie
  # within float32 rounding.
  with jax.default_matmul_precision("highest"):
    model = GPT(sizes.cut_cfg)
    params = init(model)
    prompts = seeded_requests(sizes, sizes.cut_cfg)
    want = reference_streams(model, params, prompts, new)
    gap = scale = None
    for paged in (False, True):
      got = serve(model, params, prompts, new, paged, sizes.rehearsal)
      for uid, ref in enumerate(want):
        at = first_difference(got[uid], ref)
        if at is None:
          continue
        if gap is None:
          _, _, gap, scale = padded_forward(model, params, want)
        tie = 1e-5 * max(1.0, float(scale[uid, at - 1]))
        say(f"  float32 cut, {'paged' if paged else 'contiguous'}, "
            f"request {uid}: differs at position {at}; reference top-2 "
            f"gap there {gap[uid, at - 1]:.3g} (tie below {tie:.3g})")
        check(gap[uid, at - 1] <= tie,
              f"request {uid} emitted a wrong token at position {at}")
      say(f"  float32 {sizes.cut_cfg.num_layers}-layer cut, "
          f"{'paged' if paged else 'contiguous'}: equal to "
          "generate(use_cache=True) up to float32 ties")
      # The same requests beside one that samples: the step takes its
      # sampling branch, and the greedy streams must not notice.
      mixed = serve(model, params, prompts, new, paged, sizes.rehearsal,
                    sampled=True)
      for uid in range(len(prompts)):
        at = first_difference(mixed[uid], got[uid])
        check(at is None,
              f"request {uid}: its greedy stream beside a sampled slot "
              f"differs from the greedy-only run at position {at}")
      say(f"  float32 cut, {'paged' if paged else 'contiguous'}: greedy "
          "streams beside one sampled slot equal the greedy-only run's")

  # The full-depth bf16 server.  Bit-equality across batch shapes on the
  # MXU is reported, not gated.
  model = GPT(sizes.serve_cfg)
  params = init(model)
  prompts = seeded_requests(sizes, sizes.serve_cfg)
  want = reference_streams(model, params, prompts, new)
  for paged in (False, True):
    name = "paged" if paged else "contiguous"
    got = serve(model, params, prompts, new, paged, sizes.rehearsal)
    streams = [got[uid] for uid in range(len(prompts))]
    argmax, finite, _, _ = padded_forward(model, params, streams)
    same = agree = 0
    for uid, (p, s) in enumerate(zip(prompts, streams)):
      rows = slice(len(p) - 1, len(s) - 1)      # logits that chose s[len(p):]
      check(finite[uid, rows].all(),
            f"{name} request {uid}: non-finite logits on its stream")
      agree += int((argmax[uid, rows] == s[len(p):]).sum())
      same += first_difference(s, want[uid]) is None
    total = len(prompts) * new
    say(f"  {model.cfg.num_layers}-layer "
        f"{jnp.dtype(model.cfg.dtype).name} {name}: {same}/{len(prompts)} "
        "streams equal generate(use_cache=True); teacher-forced argmax "
        f"agrees on {agree}/{total} generated tokens (not gated)")
    say(f"PASS serve {name}: {len(prompts)} requests finished at the "
        "right length with finite logits, fused step compiled once"
        + (", paged attend is the compiled kernel"
           if paged and not sizes.rehearsal else ""))


# ------------------------------------------------------------- four chips --


def one_chip_loss(sizes: Sizes, dev, batch) -> float:
  """The loss of the freshly initialised model on ``batch``, computed on
  one chip in slices that fit it."""
  epl.init(devices=[dev])
  model = GPT(sizes.train_cfg)
  ids = np.asarray(batch["ids"])
  per = sizes.batch_candidates[-1]
  params = jax.jit(lambda k: model.init(
      k, jnp.zeros((per, model.cfg.max_seq_len), jnp.int32))["params"])(
          jax.random.PRNGKey(0))
  loss = jax.jit(lambda p, b: gpt_loss(model, p, b,
                                       jax.random.PRNGKey(0))[0])
  parts = [float(loss(params, {"ids": jnp.asarray(ids[i:i + per])}))
           for i in range(0, len(ids), per)]
  return float(np.mean(parts))


def check_row_overlap(devices) -> None:
  """The row-parallel Dense's ring (communication.overlap) on a pure-TP
  mesh at the model's width, against the fused program."""
  from easyparallellibrary_tpu import ops
  from flax import linen as nn

  class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
      with epl.split():
        h = ops.Dense(4096, parallel="column")(x)
        return ops.Dense(1024, parallel="row")(nn.relu(h))

  x = jnp.asarray(np.random.RandomState(0).randn(512, 1024), jnp.float32)
  outs = {}
  for mode in ("off", "on", "auto"):
    epl.init(epl.Config({"communication.overlap": mode}), devices=devices)
    with epl.split():
      pass
    epl.current_plan().build_mesh()
    model = Net()
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), x)["params"])
    with jax.default_matmul_precision("highest"):
      outs[mode] = jax.jit(
          lambda p, x: model.apply({"params": p}, x))(params, x)
  for mode in ("on", "auto"):
    err = rel_err(outs[mode], outs["off"])
    check(err <= 1e-5, f"overlap={mode} differs from fused by {err:.3g}")
  say(f"  row-parallel Dense on model:{len(devices)}: overlap on/auto "
      "equal the fused program")


def phase_four_chips(sizes: Sizes) -> None:
  devices = jax.devices()[:4]
  cfg = sizes.train_cfg
  refs = {}
  for name, tp in (("data:4", False), ("data:2,model:2", True)):
    say(f"  -- {name}")
    state, batch, losses, hlo = run_trainer(sizes, devices, tp)
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
      placed = {s.device for s in leaf.addressable_shards}
      check(placed == set(devices),
            f"{jax.tree_util.keystr(path)} sits on {len(placed)} devices")
    if not sizes.rehearsal:
      for d in devices:
        say(f"  peak HBM GiB on chip {d.id}: "
            f"{peak_hbm(d, tree_bytes(state) // 4)}")
      B, dp, mp = batch["ids"].shape[0], (2 if tp else 4), (2 if tp else 1)
      b, h, S = B // dp, cfg.num_heads // mp, cfg.max_seq_len
      hd = cfg.d_model // cfg.num_heads
      layout = fa.flash_layout(S, h, hd, jnp.dtype(cfg.dtype).itemsize,
                               fused=mp == 1)
      if layout == "rows":
        # a chip's heads side by side; with every head on the chip q, k
        # and v are the fused projection's one array
        want = {f"bf16[{b},{S},{h * hd}]"}
        if mp == 1:
          want.add(f"bf16[{b},{S},{3 * h * hd}]")
      else:
        want = {f"bf16[{b},{h},{S},{hd}]"}
      operands = flash_call_operands(hlo)
      say(f"  flash custom-call operands in {layout}: {operands}")
      check(all(op in want for ops in operands for op in ops
                if op.startswith("bf16")),
            f"flash operands are not the per-chip shard {sorted(want)}")
      gathers = sorted(set(re.findall(
          r"= (\S+?)\{[^ ]* all-gather(?:-start)?\(", hlo)))
      say(f"  all-gather results in the step: {gathers or 'none'}")
    B = batch["ids"].shape[0]
    if B not in refs:
      refs[B] = one_chip_loss(sizes, devices[0], batch)
    tol = 1e-2 if cfg.dtype == jnp.bfloat16 else 1e-4
    say(f"  first-step loss {losses[0]:.5f}, one chip on the same "
        f"global batch {refs[B]:.5f} (tol {tol})")
    check(abs(losses[0] - refs[B]) <= tol,
          f"{name}: first-step loss {losses[0]} vs one chip {refs[B]}")
    del state
  check_row_overlap(devices)
  say("PASS four chips: data:4 and data:2,model:2 took their steps, "
      "state on four devices, losses agree with one chip, flash "
      "operands are per-chip shards, overlap on/auto does not raise")


# ----------------------------------------------------------------- hybrid --


def check_ssm_scan(B, N, Di, C, dtype, rehearsal: bool) -> None:
  """The selective-scan kernel against ``lax.scan``: ragged ``num_valid``
  (idle slots among them), some slots reset.  State and outputs to
  float32 rounding (a 16-bit output to its own rounding); an idle slot's
  state bit for bit."""
  r = np.random.RandomState(3)
  f32 = jnp.float32
  state = jnp.asarray(r.randn(B, N, Di), f32)
  u, z = (jnp.asarray(r.randn(B, C, Di), dtype) for _ in range(2))
  delta = jax.nn.softplus(jnp.asarray(r.randn(B, C, Di) - 3.0, f32))
  Bm, Cm = (jnp.asarray(r.randn(B, C, N), f32) for _ in range(2))
  A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=f32)[:, None], (N, Di))
  D = jnp.ones((Di,), f32)
  num_valid = jnp.asarray(([0, C, 1] + list(r.randint(0, C + 1, B)))[:B],
                          jnp.int32)
  reset = jnp.asarray(([False, True, False] + list(r.rand(B) < 0.3))[:B])
  args = (state, u, delta, Bm, Cm, z, A, D, num_valid, reset)
  kernel = compile_here(
      functools.partial(ssm_scan_pallas, interpret=rehearsal),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  out, new = kernel(*args)
  ref_out, ref_new = jax.jit(ssm_scan_reference)(*args)
  e_state, e_out = rel_err(new, ref_new), rel_err(out, ref_out)
  tol_out = 1e-5 if jnp.dtype(dtype).itemsize == 4 else 1e-2
  check(e_state <= 1e-5 and e_out <= tol_out,
        f"ssm_scan {jnp.dtype(dtype).name}: state error {e_state:.3g}, "
        f"output error {e_out:.3g} against the reference")
  check((np.asarray(new)[0] == np.asarray(state)[0]).all(),
        "ssm_scan moved the state of an idle slot")
  say(f"  ssm_scan slots{B} N{N} Di{Di} chunk{C} {jnp.dtype(dtype).name}: "
      f"state error {e_state:.2e}, output error {e_out:.2e}")


def phase_hybrid(sizes: Sizes) -> None:
  for dtype in (jnp.float32, jnp.bfloat16):
    check_ssm_scan(*sizes.ssm_scan_shape, dtype, rehearsal=sizes.rehearsal)
  cfg = sizes.hybrid_cfg
  model = Jamba(cfg)
  params = jax.jit(lambda: model.init(
      jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])()
  prompts = seeded_requests(sizes, cfg)
  eng = ContinuousBatchingEngine(model, params)
  spy = _StepSpecs(eng)
  for uid, p in enumerate(prompts):
    check(eng.submit(Request(uid=uid, prompt=p,
                             max_new_tokens=sizes.new_tokens)),
          f"request {uid} refused at admission")
  out = eng.run()
  for uid, p in enumerate(prompts):
    check(uid in out and len(out[uid]) == len(p) + sizes.new_tokens,
          f"hybrid request {uid} did not run to its length")
  check(spy._cache_size() == 1,
        f"hybrid fused step compiled {spy._cache_size()} times")
  n_mamba = cfg.layer_kinds().count(MAMBA)
  resolved = kv_lib.resolved(eng.lowerings)
  say(f"  hybrid engine: {len(prompts)} requests, {resolved}, cache "
      f"{eng.cache_layout}")
  if not sizes.rehearsal:
    check(set(resolved) == {"kv_write_impl", "slot_attn_impl",
                            "ssm_scan_impl"}
          and set(resolved.values()) == {"pallas"},
          f"hybrid engine resolved {resolved}: not the three kernels")
    hlo = spy.inner.lower(*spy.specs).compile().as_text()
    # One scan a Mamba layer; one write and, as the rule takes grouped
    # heads, one attend an attention layer.
    scans, attends = named_calls(hlo, SSM_SCAN), named_calls(hlo, SLOT_ATTN)
    n_attn = cfg.num_layers - n_mamba
    check(scans == n_mamba and attends == n_attn
          and hlo.count(MOSAIC_CALL) == n_mamba + 2 * n_attn,
          f"{scans} ssm_scan, {attends} "
          f"slot_attn and {hlo.count(MOSAIC_CALL)} Mosaic calls in the "
          f"hybrid step, expected {n_mamba}, {n_attn} and "
          f"{n_mamba + 2 * n_attn}")
  # One fused call, the kernel against the reference lowering, on the
  # same inputs: prefill chunks, decodes and idle slots side by side.
  N, C = 8, eng.chunk
  r = np.random.RandomState(4)
  tokens = jnp.asarray(r.randint(0, cfg.vocab_size, (N, C)), jnp.int32)
  num_valid = jnp.asarray([C, 1, 0, C // 2, 1, C, 0, 1], jnp.int32)
  first = jnp.asarray([True, False, False, True] * 2)
  kernel_impl = "interpret" if sizes.rehearsal else "pallas"
  logits = {}
  for impl in (kernel_impl, "reference"):
    kv, cursors = kv_lib.allocate_kv_cache(cfg, N, C)
    step = jax.jit(functools.partial(
        slot_step_logits, model, ssm_scan_impl=impl, slot_attn_impl=impl))
    # the second call runs on the state the first carried over
    for reset in (first, jnp.zeros_like(first)):
      got, kv = step(params, kv, tokens, cursors, num_valid=num_valid,
                     reset=reset)
      cursors = cursors + num_valid
    # the positions a slot really fed: beyond them the attend kernel
    # gives zeros where the reference gives what the masked rows hold
    logits[impl] = got[np.arange(C)[None] < np.asarray(num_valid)[:, None]]
  err = rel_err(logits[kernel_impl], logits["reference"])
  tol = 1e-4 if jnp.dtype(cfg.dtype).itemsize == 4 else 3e-2
  check(err <= tol, f"hybrid step logits, kernel against the reference "
        f"lowering: {err:.3g} of the largest logit (limit {tol})")
  say(f"PASS hybrid: ssm_scan f32 + bf16 "
      + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + f" within float32 rounding of the reference; {n_mamba} Mamba + "
      f"{cfg.num_layers - n_mamba} attention layers served; step logits "
      f"kernel against reference lowering {err:.2e}")


# ---------------------------------------------------------------- experts --


def check_moe_gmm(M, K, N, E, dtype, rehearsal: bool) -> None:
  """The grouped matmul against ``ragged_dot``: ragged groups, empty ones
  among them, one that straddles row tiles, and rows of no group."""
  r = np.random.RandomState(5)
  lhs = jnp.asarray(r.randn(M, K), dtype)
  rhs = jnp.asarray(r.randn(E, K, N) / np.sqrt(K), dtype)
  live = M // 2
  cuts = np.sort(r.randint(0, live + 1, E - 1))
  sizes = np.diff(np.concatenate([[0], cuts, [live]]))
  sizes[1] += sizes[0]
  sizes[0] = 0                                       # an empty first group
  sizes = jnp.asarray(sizes, jnp.int32)
  with jax.default_matmul_precision("highest"):
    kernel = compile_here(
        functools.partial(moe_gmm_pallas.__wrapped__, interpret=rehearsal),
        lhs, rhs, sizes, mosaic_calls=1, rehearsal=rehearsal)
    got = np.asarray(kernel(lhs, rhs, sizes), np.float32)
    ref = np.asarray(jax.jit(moe_gmm_reference)(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes))
  check((got[live:] == 0).all(), "moe_gmm: rows of no group are not zeros")
  err = rel_err(got, ref)
  tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
  check(err <= tol, f"moe_gmm {jnp.dtype(dtype).name}: error {err:.3g} of "
        f"the reference's max, tol {tol}")
  say(f"  moe_gmm rows{M} K{K} N{N} experts{E} {jnp.dtype(dtype).name}: "
      f"{err:.2e} of the reference's max over {live} live rows, "
      f"{int((np.asarray(sizes) == 0).sum())} empty groups, dead rows zeros")


def check_latent_leaf(B, Lc, H, hd, rank, C, dtype, rehearsal: bool) -> None:
  """The one-leaf forms: ``kv_write`` of a ``[B, Lc, 1, hd]`` leaf bit for
  bit against ``dynamic_update_slice``, and ``slot_attn`` of ``H x C``
  query rows against that one head, its values the leading ``rank``
  columns of its keys, with NaN at and beyond every bound."""
  r = np.random.RandomState(6)
  leaf = r.randn(B, Lc, 1, hd).astype(np.float32)
  rows = jnp.asarray(r.randn(B, C, 1, hd), dtype)
  block = block_positions((B, Lc, 1, hd), dtype, C, H)
  cursors, num_valid = edge_cases(r, B, Lc, C, block)
  args = (jnp.asarray(leaf, dtype), None, rows, None, jnp.asarray(cursors))
  write = compile_here(
      functools.partial(kv_write_pallas, interpret=rehearsal),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  got, _ = write(*args)
  want, _ = jax.jit(kv_write_reference)(*args)
  bits = lambda x: np.asarray(x).view(
      {2: np.uint16, 4: np.uint32}[x.dtype.itemsize])
  check((bits(got) == bits(want)).all(),
        f"one-leaf kv_write {jnp.dtype(dtype).name} differs from the "
        "reference")
  clean = np.asarray(want.astype(jnp.float32))
  dirty = clean.copy()
  for b in range(B):
    dirty[b, cursors[b] + num_valid[b] if num_valid[b] else 0:] = np.nan
  q = jnp.asarray(r.randn(B, C, H, hd) / np.sqrt(hd), dtype)
  scale = 1.0 / 16.0
  with jax.default_matmul_precision("highest"):
    attend = compile_here(
        functools.partial(slot_attention_pallas.__wrapped__,
                          interpret=rehearsal, v_width=rank, scale=scale),
        q, jnp.asarray(dirty, dtype), None, jnp.asarray(cursors),
        jnp.asarray(num_valid), mosaic_calls=1, rehearsal=rehearsal)
    out = np.asarray(attend(q, jnp.asarray(dirty, dtype), None,
                            jnp.asarray(cursors), jnp.asarray(num_valid)),
                     np.float32)
    ref = np.asarray(jax.jit(functools.partial(
        slot_attention_reference, v_width=rank, scale=scale))(
            q.astype(jnp.float32), jnp.asarray(clean), None,
            jnp.asarray(cursors)))
  real = (np.arange(C)[None] < num_valid[:, None])[:, :, None, None]
  check(np.isfinite(out).all(), "one-leaf slot_attn output not finite")
  err = rel_err(np.where(real, out, 0), np.where(real, ref, 0))
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  check(err <= tol, f"one-leaf slot_attn {jnp.dtype(dtype).name}: error "
        f"{err:.3g} of the reference's max, tol {tol}")
  say(f"  latent leaf slots{B} Lc{Lc} heads{H}/1 width{hd} values{rank} "
      f"chunk{C} block{block} {jnp.dtype(dtype).name}: kv_write "
      f"bit-identical, slot_attn {err:.2e} of the reference's max, NaN "
      "beyond the bounds unread")


def serve_expert_cut(sizes: Sizes, model, want_calls: dict, what: str,
                     gmm_may_decline: bool = False):
  """A cut of an expert decoder through the engine: every request runs to
  its length on ONE compile, every kernel resolved and counted
  (``want_calls``: custom calls by name in the compiled step), the served
  tokens against the teacher-forced full forward (the experts by
  ``ragged_dot``), and one fused call's logits, kernels against reference
  lowerings, every entry of the engine's record of them
  (``gmm_may_decline``: the grouped matmul's rule may take the
  reference, as it does for float32 experts of a hidden size of 5120,
  whose tiles pass its VMEM budget: then no ``moe_gmm`` call is expected).
  Returns ``(gap, err)`` of the last two."""
  cfg = model.cfg
  params = jax.jit(lambda: model.init(
      jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"])()
  prompts = seeded_requests(sizes, cfg)
  recurrent = kv_lib.has_recurrent_state(cfg)
  with jax.default_matmul_precision("highest"):
    eng = ContinuousBatchingEngine(model, params)
    spy = _StepSpecs(eng)
    for uid, p in enumerate(prompts):
      check(eng.submit(Request(uid=uid, prompt=p,
                               max_new_tokens=sizes.new_tokens)),
            f"request {uid} refused at admission")
    out = eng.run()
    for uid, p in enumerate(prompts):
      check(uid in out and len(out[uid]) == len(p) + sizes.new_tokens,
            f"{what} request {uid} did not run to its length")
    check(spy._cache_size() == 1,
          f"{what}'s fused step compiled {spy._cache_size()} times")
    resolved = kv_lib.resolved(eng.lowerings)
    say(f"  {what} engine: {len(prompts)} requests, {resolved}, cache "
        f"{eng.cache_layout}")
    # Where the grouped matmul's rule declined the experts' shapes, no
    # ``moe_gmm`` call is expected and the kernel side of the comparison
    # below runs it as the engine did.
    declined = gmm_may_decline and resolved["moe_gmm_impl"] == "reference"
    if not sizes.rehearsal:
      if declined:
        want_calls = dict(want_calls, **{MOE_GMM: 0})
      hlo = spy.inner.lower(*spy.specs).compile().as_text()
      calls = {n: named_calls(hlo, n) for n in want_calls}
      check(all(impl == "pallas" for name, impl in resolved.items()
                if not (declined and name == "moe_gmm_impl"))
            and calls == want_calls,
            f"{what} engine resolved {resolved}; custom calls {calls}, "
            f"expected {want_calls}")
    # The served tokens against the teacher-forced full forward: attention
    # over the whole sequence, the experts by ragged_dot.
    streams = [out[uid] for uid in range(len(prompts))]
    ids = np.zeros((len(streams), cfg.max_seq_len), np.int32)
    for i, s in enumerate(streams):
      ids[i, :len(s)] = s
    full = jax.jit(lambda p, ids: model.apply(
        {"params": p}, ids, moe_gmm_impl="reference").astype(jnp.float32))
    logits = np.asarray(full(params, jnp.asarray(ids)))
    gap = 0.0
    for i, (p, s) in enumerate(zip(prompts, streams)):
      rows = logits[i, len(p) - 1:len(s) - 1]
      served = rows[np.arange(len(rows)), s[len(p):]]
      gap = max(gap, float((rows.max(-1) - served).max()
                           / np.abs(rows).max()))
    check(gap <= 1e-4, f"a served token's logit lies {gap:.3g} of the "
          "largest logit below the teacher-forced best (limit 1e-4)")
    # One fused call, kernels against reference lowerings, on the same
    # inputs: prefill chunks, decodes and idle slots side by side.
    N, C = 8, eng.chunk
    r = np.random.RandomState(4)
    tokens = jnp.asarray(r.randint(0, cfg.vocab_size, (N, C)), jnp.int32)
    num_valid = jnp.asarray([C, 1, 0, C // 2, 1, C, 0, 1], jnp.int32)
    state_args = {"reset": jnp.zeros((N,), jnp.bool_)} if recurrent else {}
    kernel_impl = "interpret" if sizes.rehearsal else "pallas"
    got = {}
    for impl in (kernel_impl, "reference"):
      kv, cursors = kv_lib.allocate_kv_cache(cfg, N, C)
      step = jax.jit(functools.partial(
          slot_step_logits, model, **dict(
              dict.fromkeys(resolved, impl),
              **({"moe_gmm_impl": "reference"} if declined else {}))))
      for _ in range(2):       # the second call reads what the first wrote
        lg, kv = step(params, kv, tokens, cursors, num_valid=num_valid,
                      **state_args)
        cursors = cursors + num_valid
      got[impl] = lg[np.arange(C)[None] < np.asarray(num_valid)[:, None]]
  err = rel_err(got[kernel_impl], got["reference"])
  tol = 1e-4 if jnp.dtype(cfg.dtype).itemsize == 4 else 3e-2
  check(err <= tol, f"{what} step logits, kernels against the reference "
        f"lowerings: {err:.3g} of the largest logit (limit {tol})")
  return gap, err


def phase_experts(sizes: Sizes) -> None:
  for dtype in (jnp.float32, jnp.bfloat16):
    for shape in sizes.moe_gmm_shapes:
      check_moe_gmm(*shape, dtype, rehearsal=sizes.rehearsal)
    check_latent_leaf(*sizes.latent_shape, dtype, rehearsal=sizes.rehearsal)
  cfg = sizes.experts_cfg
  n_moe = cfg.num_layers - cfg.first_k_dense
  gap, err = serve_expert_cut(
      sizes, GlmMoe(cfg), {MOE_GMM: 2 * n_moe, SLOT_ATTN: cfg.num_layers,
                           "kv_write": cfg.num_layers}, "expert model")
  say(f"PASS experts: moe_gmm and the one-leaf kv_write and slot_attn f32 "
      "+ bf16 " + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + f" against their references; {cfg.first_k_dense} dense + {n_moe} "
      f"expert layers served, served tokens within {gap:.1e} of the "
      f"teacher-forced best; step logits kernels against reference "
      f"lowerings {err:.2e}")


def phase_lfm2(sizes: Sizes) -> None:
  B, Lc, H, Hkv, hd, C = sizes.lfm2_kv_shape
  for dtype in (jnp.float32, jnp.bfloat16):
    check_kv_write(B, Lc, Hkv, hd, C, dtype, "rows",
                   rehearsal=sizes.rehearsal)
    check_slot_attn(B, Lc, H, Hkv, hd, C, dtype, "rows",
                    rehearsal=sizes.rehearsal)
    for shape in sizes.lfm2_gmm_shapes:
      check_moe_gmm(*shape, dtype, rehearsal=sizes.rehearsal)
  cfg = sizes.lfm2_cfg
  kinds = cfg.layer_kinds()
  n_attn = sum(k == "attention" for k in kinds)
  n_moe = cfg.num_layers - cfg.num_dense_layers
  gap, err = serve_expert_cut(
      sizes, Lfm2Moe(cfg), {MOE_GMM: 2 * n_moe, SLOT_ATTN: n_attn,
                            "kv_write": n_attn}, "lfm2")
  say(f"PASS lfm2: kv_write and slot_attn in rows on grouped heads that "
      "share a lane tile, moe_gmm over "
      f"{cfg.n_routed_experts} experts, f32 + bf16 "
      + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + f" against their references; {len(kinds) - n_attn} conv + {n_attn} "
      f"attention layers, {cfg.num_dense_layers} dense + {n_moe} expert, "
      f"served tokens within {gap:.1e} of the teacher-forced best; step "
      f"logits kernels against reference lowerings {err:.2e}")


# ------------------------------------------------------------------ dots3 --


def _launches(chunk: int) -> int:
  """Launches of a tiled kernel a call: the decoding slots take one of
  their own where the chunk is tiled (``slot_attention.split_decodes``)."""
  return 1 if chunk % 8 or chunk == 8 else 2


def _bits(x):
  return np.asarray(x).view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def pack_flat(q, num_valid, rows: int):
  """``q`` ``[B, C, ..]`` as a step's flat batch of ``rows`` rows: each
  slot's first ``num_valid[b]`` positions in slot order, zeros after them;
  the flat row of each slot's first position; the ``[B, C]`` mask of the
  live positions."""
  live = np.arange(q.shape[1])[None] < num_valid[:, None]
  flat = np.zeros((rows,) + q.shape[2:], np.float32)
  flat[:int(num_valid.sum())] = np.asarray(q.astype(jnp.float32))[live]
  return (jnp.asarray(flat, q.dtype),
          jnp.asarray(np.cumsum(num_valid) - num_valid, jnp.int32), live)


def check_flat_attend(kernel, name, q, num_valid, out, operands, tol,
                      rehearsal: bool) -> None:
  """A one-leaf tile form from the step's FLAT batch: ``q``'s live positions
  packed in slot order into ``T`` rows, the last live slot ending three rows
  before the batch's end (its last tile is read from ``T - 8`` on and
  worked a shift further down).  The result must lie at the rows the
  queries lie at, equal what the ``[slots, chunk]`` call gave there
  (``out``), and be zeros in the rows no position lives in."""
  C = q.shape[1]
  total = int(num_valid.sum())
  flat, starts, live = pack_flat(q, num_valid, total + 3)
  attend = compile_here(
      lambda flat, starts, *operands: kernel(flat, *operands, starts=starts,
                                             chunk=C),
      flat, starts, *operands, mosaic_calls=_launches(C), rehearsal=rehearsal)
  got = np.asarray(attend(flat, starts, *operands), np.float32)
  check(got.shape == (total + 3,) + out.shape[2:],
        f"{name} from the flat batch: output {got.shape}")
  err = rel_err(got[:total], out[live])
  check(err <= tol, f"{name} from the flat batch: {err:.3g} of the "
        f"[slots, chunk] call's max, tol {tol}")
  check((got[total:] == 0).all(),
        f"{name} from the flat batch: rows beyond the live ones not zeros")
  say(f"  {name} from the flat batch ({total} live of {total + 3} rows): "
      f"{err:.2e} of the [slots, chunk] call's max at the queries' rows, "
      "zeros beyond")


def check_ring_write(B, R, W, C, dtype, rehearsal: bool) -> None:
  """``kv_write`` of a ring ``[B, R, 1, W]`` bit for bit against the rows
  written at their positions modulo ``R``: windows at the ring's start,
  across a tile's edge, across the ring's END (two stripes, the second at
  the leaf's head) and many turns in."""
  r = np.random.RandomState(11)
  leaf = jnp.asarray(r.randn(B, R, 1, W), dtype)
  rows = jnp.asarray(r.randn(B, C, 1, W), dtype)
  cursors = jnp.asarray(([0, 128 - C // 2, R - C // 2, R - 1, 7 * R + R - 3]
                         + list(r.randint(0, 20 * R, B)))[:B], jnp.int32)
  args = (leaf, None, rows, None, cursors)
  write = compile_here(
      functools.partial(kv_write_pallas, interpret=rehearsal, ring=True),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  got, _ = write(*args)
  want, _ = jax.jit(functools.partial(kv_write_reference, ring=True))(*args)
  check((_bits(got) == _bits(want)).all(),
        f"ring kv_write {jnp.dtype(dtype).name} differs from the reference")
  say(f"  ring write slots{B} rows{R} width{W} chunk{C} "
      f"{jnp.dtype(dtype).name}: bit-identical, "
      f"{int((np.asarray(cursors) % R + C > R).sum())} windows across the "
      "ring's end")


def check_dsa_index(B, Lc, C, Hi, d, top_k, dtype, rehearsal: bool):
  """``dsa_index`` against the einsum over every head (NaN in the index
  rows at and beyond every bound), and ``kth_largest`` of its scores
  against a sort.  Returns what the selected attend's check goes on with:
  ``(cursors, num_valid, scores, thresholds)``."""
  r = np.random.RandomState(12)
  q = jnp.asarray(r.randn(B, C, Hi, d), dtype)
  w = jnp.asarray(r.randn(B, C, Hi), jnp.float32)
  keys = r.randn(B, Lc, d).astype(np.float32)
  cursors, num_valid = edge_cases(r, B, Lc, C, 512 if Lc > 1024 else 128)
  cur, nv = jnp.asarray(cursors), jnp.asarray(num_valid)
  with jax.default_matmul_precision("highest"):
    want = np.asarray(jax.jit(dsa_lib.dsa_index_reference)(
        q, w, jnp.asarray(keys, dtype), cur, nv))
    for b in range(B):
      keys[b, cursors[b] + num_valid[b]:] = np.nan
    dirty = jnp.asarray(keys, dtype)
    kernel = compile_here(
        functools.partial(dsa_lib.dsa_index_pallas.__wrapped__,
                          interpret=rehearsal),
        q, w, dirty, cur, nv, mosaic_calls=_launches(C),
        rehearsal=rehearsal)
    scores = kernel(q, w, dirty, cur, nv)
  got = np.asarray(scores)
  real = np.arange(C)[None] < num_valid[:, None]
  check(np.isfinite(got[real]).all(), "dsa_index read a row beyond a bound")
  err = rel_err(got[real], want[real])
  tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
  check(err <= tol, f"dsa_index {jnp.dtype(dtype).name}: error {err:.3g} "
        f"of the reference's max, tol {tol}")
  t = cursors[:, None] + np.arange(C)[None]
  k_each = np.clip(t + 1, 1, top_k).reshape(-1)
  flat = jnp.where(jnp.asarray(real.reshape(-1, 1)),
                   scores.reshape(B * C, Lc), dsa_lib.MASKED)
  thr = np.asarray(jax.jit(dsa_lib.kth_largest)(flat, jnp.asarray(k_each)))
  by_sort = np.sort(np.asarray(flat), 1)[:, ::-1][np.arange(B * C), k_each - 1]
  check((thr == by_sort).all(), "kth_largest differs from a sort")
  say(f"  dsa_index slots{B} Lc{Lc} chunk{C} heads{Hi} width{d} "
      f"{jnp.dtype(dtype).name}: {err:.2e} of the reference's max, NaN "
      f"beyond the bounds unread; the {top_k}th largest of every live "
      "query's scores equals a sort's")
  return cur, nv, flat.reshape(B, C, Lc), jnp.asarray(thr).reshape(B, C)


def check_selected_attend(B, Lc, C, H, W, rank, picked, dtype,
                          rehearsal: bool) -> None:
  """``slot_attn_sel`` against the masked einsums: ``picked`` is what
  :func:`check_dsa_index` returned (cursors, bounds, scores, thresholds);
  NaN in every latent row at or beyond a bound."""
  cur, nv, scores, thr = picked
  r = np.random.RandomState(13)
  leaf = r.randn(B, Lc, 1, W).astype(np.float32)
  q = jnp.asarray(r.randn(B, C, H, W) / np.sqrt(W), dtype)
  scale = 1.0 / np.sqrt(192.0)
  cursors, num_valid = np.asarray(cur), np.asarray(nv)
  with jax.default_matmul_precision("highest"):
    ref = np.asarray(jax.jit(functools.partial(
        slot_attn_lib.slot_attention_selected_reference, v_width=rank,
        scale=scale))(q.astype(jnp.float32), jnp.asarray(leaf), scores, thr,
                      cur), np.float32)
    for b in range(B):
      leaf[b, cursors[b] + num_valid[b]:] = np.nan
    dirty = jnp.asarray(leaf, dtype)
    attend = compile_here(
        functools.partial(
            slot_attn_lib.slot_attention_selected_pallas.__wrapped__,
            interpret=rehearsal, v_width=rank, scale=scale),
        q, dirty, scores, thr, cur, nv, mosaic_calls=_launches(C),
        rehearsal=rehearsal)
    out = np.asarray(attend(q, dirty, scores, thr, cur, nv), np.float32)
  real = (np.arange(C)[None] < num_valid[:, None])[:, :, None, None]
  check(np.isfinite(out).all(), "slot_attn_sel output not finite")
  err = rel_err(np.where(real, out, 0), np.where(real, ref, 0))
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  check(err <= tol, f"slot_attn_sel {jnp.dtype(dtype).name}: error "
        f"{err:.3g} of the reference's max, tol {tol}")
  with jax.default_matmul_precision("highest"):
    check_flat_attend(
        functools.partial(
            slot_attn_lib.slot_attention_selected_pallas.__wrapped__,
            interpret=rehearsal, v_width=rank, scale=scale),
        "slot_attn_sel", q, num_valid, out, (dirty, scores, thr, cur, nv),
        tol, rehearsal)
  say(f"  selected attend slots{B} Lc{Lc} heads{H}/1 width{W} values{rank} "
      f"chunk{C} {jnp.dtype(dtype).name}: {err:.2e} of the reference's "
      "max, NaN beyond the bounds unread")


def check_window_attend(B, R, C, H, W, rank, window, dtype,
                        rehearsal: bool) -> None:
  """``slot_attn_win`` over rings filled position by position (never
  written rows and the dead rows of this step's write hold NaN) against
  plain attention over each query's window of the slot's history."""
  r = np.random.RandomState(14)
  q = jnp.asarray(r.randn(B, C, H, W) / np.sqrt(W), dtype)
  cursors = np.asarray(([0, window - 3, R - C // 2, 3 * R + 5]
                        + list(r.randint(0, 4 * R, B)))[:B], np.int32)
  num_valid = np.asarray(([C, C, C, 1, 0, C // 2 or 1] + [1] * B)[:B],
                         np.int32)
  top = int((cursors + C).max())
  hist = r.randn(B, top, W).astype(np.float32)
  ring = np.full((B, R, 1, W), np.nan, np.float32)
  for b in range(B):
    for p in range(max(0, cursors[b] + num_valid[b] - R),
                   cursors[b] + num_valid[b]):
      ring[b, p % R, 0] = hist[b, p]
    for p in range(cursors[b] + num_valid[b], cursors[b] + C):
      ring[b, p % R, 0] = np.nan
  scale = 1.0 / 16.0
  cur, nv = jnp.asarray(cursors), jnp.asarray(num_valid)
  with jax.default_matmul_precision("highest"):
    attend = compile_here(
        functools.partial(
            slot_attn_lib.slot_attention_window_pallas.__wrapped__,
            interpret=rehearsal, window=window, v_width=rank, scale=scale),
        q, jnp.asarray(ring, dtype), cur, nv, mosaic_calls=_launches(C),
        rehearsal=rehearsal)
    out = np.asarray(attend(q, jnp.asarray(ring, dtype), cur, nv),
                     np.float32)

  # Plain attention over each live query's window, on the host, from
  # the history as the leaf's dtype holds it.
  held = np.asarray(jnp.asarray(hist, dtype).astype(jnp.float32))
  qs = np.asarray(q.astype(jnp.float32))
  worst, peak = 0.0, 1e-30
  for b in range(B):
    for i in range(num_valid[b]):
      t = cursors[b] + i
      keys = held[b, max(0, t - window + 1):t + 1]
      s_ = qs[b, i] @ keys.T * scale
      p = np.exp(s_ - s_.max(-1, keepdims=True))
      want = (p / p.sum(-1, keepdims=True)) @ keys[:, :rank]
      worst = max(worst, float(np.abs(out[b, i] - want).max()))
      peak = max(peak, float(np.abs(want).max()))
  check(np.isfinite(out).all(), "slot_attn_win output not finite")
  dead = np.arange(C)[None] >= num_valid[:, None]
  check((out[dead] == 0).all(), "slot_attn_win: dead positions not zeros")
  err = worst / peak
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  check(err <= tol, f"slot_attn_win {jnp.dtype(dtype).name}: error "
        f"{err:.3g} of the reference's max, tol {tol}")
  with jax.default_matmul_precision("highest"):
    check_flat_attend(
        functools.partial(
            slot_attn_lib.slot_attention_window_pallas.__wrapped__,
            interpret=rehearsal, window=window, v_width=rank, scale=scale),
        "slot_attn_win", q, num_valid, out, (jnp.asarray(ring, dtype), cur, nv),
        tol, rehearsal)
  say(f"  window attend slots{B} ring{R} heads{H}/1 width{W} values{rank} "
      f"window{window} chunk{C} {jnp.dtype(dtype).name}: {err:.2e} of "
      "plain attention over each window, unwritten and dead rows unread")


def phase_dots3(sizes: Sizes) -> None:
  cfg = sizes.dots3_cfg
  B, Lc, C, R = sizes.dots3_shapes
  full, swa = cfg.latent_dims(FULL), cfg.latent_dims(SLIDING)
  if sizes.rehearsal:
    top_k, window = cfg.index_topk, cfg.sliding_window
  else:
    # The kernels at the cell's own sizes, whatever the served cut's.
    top_k, window = 2048, 513
  for dtype in (jnp.float32, jnp.bfloat16):
    check_ring_write(B, R, swa.latent_dim, C, dtype, sizes.rehearsal)
    picked = check_dsa_index(B, Lc, C, full.indexer.num_heads,
                             full.indexer.head_dim, top_k, dtype,
                             sizes.rehearsal)
    check_selected_attend(B, Lc, C, full.num_heads, full.latent_dim,
                          full.kv_lora_rank, picked, dtype, sizes.rehearsal)
    check_window_attend(B, R, C, swa.num_heads, swa.latent_dim,
                        swa.kv_lora_rank, window, dtype, sizes.rehearsal)
  kinds = cfg.layer_types
  n_full, n_win = kinds.count(FULL), kinds.count(SLIDING)
  n_moe = cfg.num_layers - cfg.first_k_dense
  gap, err = serve_expert_cut(
      sizes, Dots3Note(cfg),
      {MOE_GMM: 2 * n_moe, slot_attn_lib.SLOT_ATTN_SEL: 2 * n_full,
       slot_attn_lib.SLOT_ATTN_WIN: 2 * n_win, dsa_lib.DSA_INDEX: 2 * n_full,
       "kv_write": 2 * n_full + n_win}, "dots3",
      gmm_may_decline=True)
  say(f"PASS dots3: the ring kv_write, dsa_index, kth_largest, "
      "slot_attn_sel and slot_attn_win f32 + bf16 "
      + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + f" against their references; {n_full} selecting + {n_win} window "
      f"layers, {cfg.first_k_dense} dense + {n_moe} expert holding "
      f"{cfg.experts_held[1]} of {cfg.n_routed_experts}, served tokens "
      f"within {gap:.1e} of the teacher-forced best; step logits kernels "
      f"against reference lowerings {err:.2e}")


# ----------------------------------------------------------- smallthinker --


def check_kv_ring_write(B, R, W, C, dtype, rehearsal: bool) -> None:
  """``kv_write`` of a K/V PAIR kept in rows as a ring ``[B, R, W]`` bit for
  bit against the rows written at their positions modulo ``R`` in every
  slot the step feeds, an idle slot's ring untouched: windows at the ring's
  start, across a stripe's edge, across the ring's END (two stripes, the
  second at the leaf's head) and many turns in."""
  r = np.random.RandomState(21)
  leaves = [jnp.asarray(r.randn(B, R, W), dtype) for _ in range(2)]
  rows = [jnp.asarray(r.randn(B, C, W), dtype) for _ in range(2)]
  cursors = jnp.asarray(([0, 128 - C // 2, R - C // 2, R - 1, 7 * R + R - 3]
                         + list(r.randint(0, 20 * R, B)))[:B], jnp.int32)
  num_valid = jnp.asarray(([C, C, C, 1, C // 2 or 1, 0] + [1, C] * B)[:B],
                          jnp.int32)
  args = (*leaves, *rows, cursors, num_valid)
  write = compile_here(
      functools.partial(kv_write_pallas, interpret=rehearsal, ring=True),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  got = write(*args)
  want = jax.jit(functools.partial(kv_write_reference, ring=True))(*args[:5])
  fed = np.asarray(num_valid) > 0
  for g, w, old in zip(got, want, leaves):
    check((_bits(g)[fed] == _bits(w)[fed]).all()
          and (_bits(g)[~fed] == _bits(old)[~fed]).all(),
          f"ring kv_write of a pair {jnp.dtype(dtype).name} differs from "
          "the reference in a fed slot, or touched an idle one")
  say(f"  K/V ring write slots{B} rows{R} width{W} chunk{C} "
      f"{jnp.dtype(dtype).name}: bit-identical in {int(fed.sum())} fed "
      f"slots, {int((~fed).sum())} idle untouched, "
      f"{int((np.asarray(cursors) % R + C > R).sum())} windows across the "
      "ring's end")


def check_kv_window_attend(B, R, C, H, Hkv, hd, window, dtype,
                           rehearsal: bool) -> None:
  """``slot_attn_kvwin`` over K and V rings filled position by position
  (never written rows and the dead rows of this step's write hold NaN)
  against plain grouped attention over each query's window of the slot's
  history."""
  r = np.random.RandomState(22)
  W = Hkv * hd
  q = jnp.asarray(r.randn(B, C, H, hd), dtype)
  cursors = np.asarray(([0, window - 3, R - C // 2, 3 * R + 5]
                        + list(r.randint(0, 4 * R, B)))[:B], np.int32)
  num_valid = np.asarray(([C, C, C, 1, 0, C // 2 or 1] + [1, C] * B)[:B],
                         np.int32)
  top = int((cursors + C).max())
  hist = r.randn(2, B, top, W).astype(np.float32)
  rings = np.full((2, B, R, W), np.nan, np.float32)
  for b in range(B):
    for p in range(max(0, cursors[b] + num_valid[b] - R),
                   cursors[b] + num_valid[b]):
      rings[:, b, p % R] = hist[:, b, p]
    for p in range(cursors[b] + num_valid[b], cursors[b] + C):
      rings[:, b, p % R] = np.nan
  cur, nv = jnp.asarray(cursors), jnp.asarray(num_valid)
  ring_k, ring_v = (jnp.asarray(x, dtype) for x in rings)
  with jax.default_matmul_precision("highest"):
    attend = compile_here(
        functools.partial(
            slot_attn_lib.slot_attention_kv_window_pallas.__wrapped__,
            interpret=rehearsal, window=window, scale=hd ** -0.5),
        q, ring_k, ring_v, cur, nv, mosaic_calls=_launches(C),
        rehearsal=rehearsal)
    out = np.asarray(attend(q, ring_k, ring_v, cur, nv), np.float32)

  held = np.asarray(jnp.asarray(hist, dtype).astype(jnp.float32))
  qs = np.asarray(q.astype(jnp.float32))
  G = H // Hkv
  worst, peak = 0.0, 1e-30
  for b in range(B):
    for i in range(num_valid[b]):
      t = cursors[b] + i
      lo = max(0, t - window + 1)
      for g in range(Hkv):
        keys = held[0, b, lo:t + 1, g * hd:(g + 1) * hd]
        vals = held[1, b, lo:t + 1, g * hd:(g + 1) * hd]
        s_ = qs[b, i, g * G:(g + 1) * G] @ keys.T * hd ** -0.5
        p = np.exp(s_ - s_.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ vals
        worst = max(worst, float(np.abs(out[b, i, g * G:(g + 1) * G]
                                        - want).max()))
        peak = max(peak, float(np.abs(want).max()))
  check(np.isfinite(out).all(), "slot_attn_kvwin output not finite")
  dead = np.arange(C)[None] >= num_valid[:, None]
  check((out[dead] == 0).all(), "slot_attn_kvwin: dead positions not zeros")
  err = worst / peak
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  check(err <= tol, f"slot_attn_kvwin {jnp.dtype(dtype).name}: error "
        f"{err:.3g} of the reference's max, tol {tol}")
  say(f"  K/V window attend slots{B} ring{R} heads{H}/{Hkv} of {hd} "
      f"window{window} chunk{C} {jnp.dtype(dtype).name}: {err:.2e} of "
      "plain attention over each window, unwritten and dead rows unread")


def report_rules(cfg, slots: int, chunk: int) -> dict:
  """What every kernel rule resolved for an engine of ``slots x chunk`` over
  ``cfg`` (``kv_cache.step_lowerings``: whatever model it is handed), the
  leaves and expert stacks the rules saw and, where one declined, why."""
  leaves = {}
  for i, kind in enumerate(kv_lib.layer_kinds(cfg)):
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        kv_lib.cache_leaves(cfg, slots, chunk)[f"block_{i}"]):
      leaves[f"{kind} {path[-1].key}"] = tuple(leaf.shape)
  if getattr(cfg, "n_routed_experts", 0):
    E = (getattr(cfg, "experts_held", None) or (0, cfg.n_routed_experts))[1]
    leaves["expert rows and stacks"] = (
        (slots * chunk * cfg.num_experts_per_tok, cfg.d_model),
        (E, cfg.d_model, 2 * cfg.moe_d_ff), (E, cfg.moe_d_ff, cfg.d_model))
  for what, shape in leaves.items():
    say(f"  {what}: {shape} {jnp.dtype(cfg.dtype).name}")
  from easyparallellibrary_tpu.serving.engine import flat_width
  record = kv_lib.step_lowerings(cfg, slots, chunk,
                                 width=flat_width(slots, chunk))
  resolved = kv_lib.resolved(record)
  # One chip, no mesh: a rule declines for its backend or for the shapes.
  why = (f": DECLINED, the backend is {jax.default_backend()}"
         if jax.default_backend() != "tpu"
         else ": DECLINED, the shapes do not fit the kernel")
  for name, impl in resolved.items():
    say(f"  rule {name}: {impl}" + ("" if impl == "pallas" else why))
  if record["tile_attn_out"] is not None:
    say(f"  the tile-grid attends read and write: {record['tile_attn_out']}"
        f" (a flat batch of {flat_width(slots, chunk)} rows)")
  return resolved


def phase_smallthinker(sizes: Sizes) -> None:
  cell_cfg, slots, C = sizes.smallthinker_cell
  resolved = report_rules(cell_cfg, slots, C)
  if not sizes.rehearsal:
    check(all(i == "pallas" for i in resolved.values()),
          f"a rule declined at the cell's shapes: {resolved}")
  R = cell_cfg.ring_length(C)
  W = cell_cfg.num_kv_heads * cell_cfg.head_dim
  Lc = kv_lib.kv_leaf_shape(cell_cfg, slots, C)[1]
  for dtype in (jnp.float32, jnp.bfloat16):
    # The full layers' attend at the cell's leaf, on 8 slots (the
    # reference's score tensor for 48 would not fit).
    check_slot_attn(min(slots, 8), Lc, cell_cfg.num_heads,
                    cell_cfg.num_kv_heads, cell_cfg.head_dim, C, dtype,
                    "rows", rehearsal=sizes.rehearsal)
    check_kv_ring_write(slots, R, W, C, dtype, sizes.rehearsal)
    check_kv_window_attend(slots, R, C, cell_cfg.num_heads,
                           cell_cfg.num_kv_heads, cell_cfg.head_dim,
                           cell_cfg.sliding_window, dtype, sizes.rehearsal)
  cfg = sizes.smallthinker_cfg
  n_win = sum(cfg.window_layout)
  n_full = cfg.num_layers - n_win
  gap, err = serve_expert_cut(
      sizes, SmallThinker(cfg),
      {MOE_GMM: 2 * cfg.num_layers, SLOT_ATTN: n_full,
       slot_attn_lib.SLOT_ATTN_KVWIN: 2 * n_win, "kv_write": cfg.num_layers},
      "smallthinker")
  say(f"PASS smallthinker: the K/V ring kv_write and slot_attn_kvwin f32 + "
      "bf16 " + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + f" at the cell's leaves against their references; {n_full} full "
      f"layer(s) without positions + {n_win} window layers with rotary, "
      f"{cfg.n_routed_experts} ReLU experts top-{cfg.num_experts_per_tok} "
      f"routed from the layer's input, served tokens within {gap:.1e} of "
      f"the teacher-forced best; step logits kernels against reference "
      f"lowerings {err:.2e}")


# -------------------------------------------------------------- gigachat --


def check_gdn_scan(B, Hk, Hv, d, C, dtype, rehearsal: bool) -> None:
  """The gated delta rule's kernel (the convolution over the slot's window
  inside it) against ``lax.scan``: an idle slot, a decoding one (one
  position's form), whole and partly valid chunks (the chunk's form), some
  from ``reset``.  State and outputs to float32
  rounding (a 16-bit output to its own rounding); an idle slot's state bit
  for bit; zeros beyond a slot's live positions."""
  r = np.random.RandomState(5)
  f32 = jnp.float32
  state = jnp.asarray(r.randn(B, Hv, d, d), f32)
  W = 2 * Hk * d + Hv * d
  x = jnp.asarray(r.randn(B, C, W), dtype)
  window = jnp.asarray(r.randn(B, 3, W), dtype)
  taps = jnp.asarray(0.5 * r.randn(4, W), f32)
  g = -jax.nn.softplus(jnp.asarray(r.randn(B, C, Hv) - 2.0, f32))
  beta = jax.nn.sigmoid(jnp.asarray(r.randn(B, C, Hv), f32))
  num_valid = jnp.asarray(([0, 1, C, C // 2 + 1, 1, 2]
                           + list(r.randint(0, C + 1, B)))[:B], jnp.int32)
  reset = jnp.asarray(([False, False, True, False, True, False]
                       + list(r.rand(B) < 0.3))[:B])
  args = (state, window, x, taps, g, beta, num_valid, reset)
  kernel = compile_here(
      functools.partial(gdn_lib.gdn_scan_pallas, interpret=rehearsal),
      *args, mosaic_calls=1, rehearsal=rehearsal)
  out, new, new_window = kernel(*args)
  ref_out, ref_new, ref_window = jax.jit(gdn_lib.gdn_scan_reference)(*args)
  check((np.asarray(new_window, np.float32)
         == np.asarray(ref_window, np.float32)).all(),
        "gdn_scan's two lowerings advance the window differently")
  e_state, e_out = rel_err(new, ref_new), rel_err(out, ref_out)
  tol_out = 2e-5 if jnp.dtype(dtype).itemsize == 4 else 1e-2
  check(e_state <= 2e-5 and e_out <= tol_out,
        f"gdn_scan {jnp.dtype(dtype).name}: state error {e_state:.3g}, "
        f"output error {e_out:.3g} against the reference")
  check((np.asarray(new)[0].view(np.uint32)
         == np.asarray(state)[0].view(np.uint32)).all(),
        "gdn_scan moved the state of an idle slot")
  live = np.arange(C)[None] < np.asarray(num_valid)[:, None]
  check(not np.asarray(out, np.float32)[~live].any(),
        "gdn_scan wrote beyond a slot's live positions")
  say(f"  gdn_scan slots{B} heads{Hk}|{Hv} of {d} chunk{C} "
      f"{jnp.dtype(dtype).name}: state error {e_state:.2e}, output error "
      f"{e_out:.2e}")

def kernel_us(call, args, name: str, reps: int = 4) -> float:
  """Device time of the custom calls named ``name`` in one run of the
  compiled ``call(*args)``, in us, from a profiler trace of ``reps`` runs
  (the kernel alone: a wall clock around the call reads its wrapper's
  relayouts and fills too)."""
  import glob
  import tempfile
  jax.block_until_ready(call(*args))
  with tempfile.TemporaryDirectory() as where:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=opts)
    for _ in range(reps):
      out = call(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{where}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    ns = sum(ev.duration_ns for plane in data.planes
             if plane.name == "/device:TPU:0" for line in plane.lines
             if line.name == "XLA Ops" for ev in line.events
             if re.match(rf"%{name}(\.\d+)* = .* custom-call\(", ev.name))
  return ns / reps / 1e3


def check_plain_tile_attend(B, L, H, W, r, C, dtype, rehearsal: bool,
                            timed: bool = False) -> None:
  """The PLAIN one-leaf attend on the tile grid (``slot_attn`` from the
  step's flat batch: kernels/slot_attention.py ``plain_tile_form``) against
  the einsums over every row, at a serving step's mix: one slot in eight
  prefilling (whole chunks, a partial one), the others decoding one
  position, two idle, bounds up to the leaf's last row, NaN in every row
  at or beyond a slot's bound.  ``timed``: the kernel alone at blocks of
  512 / 1024 / 2048 beside the first grid's on the same operands gathered
  into ``[slots, chunk]`` order."""
  rs = np.random.RandomState(11)
  num_valid = np.where(np.arange(B) % 8 == 3, C, 1).astype(np.int32)
  num_valid[[1, B - 2]] = 0
  num_valid[2] = C // 2 + 1
  cursors = rs.randint(0, L - C, B).astype(np.int32)
  # a decode from a leaf's start, a partial chunk, a whole chunk that ends
  # at the leaf's last row, a cursor beyond it (clamped, as the write's)
  cursors[[0, 2, 3, B - 1]] = [0, 5, L - C, L - 1]
  leaf = rs.randn(B, L, 1, W).astype(np.float32)
  q = jnp.asarray(rs.randn(B, C, H, W) / np.sqrt(W), dtype)
  scale = 0.3
  dirty = leaf.copy()
  clamped = np.clip(cursors, 0, L - C)
  for b in range(B):
    dirty[b, clamped[b] + num_valid[b] if num_valid[b] else 0:] = np.nan
  total = int(num_valid.sum())
  T = -(-max(total + 3, 8) // 8) * 8
  flat, starts, live = pack_flat(q, num_valid, T)
  operands = (jnp.asarray(dirty, dtype), jnp.asarray(cursors),
              jnp.asarray(num_valid))

  def tiled(block=None):
    return compile_here(
        lambda flat, starts, *ops: slot_attn_lib.slot_attention_tiled_pallas(
            flat, *ops, interpret=rehearsal, block=block, starts=starts,
            chunk=C, v_width=r, scale=scale),
        flat, starts, *operands, mosaic_calls=_launches(C),
        rehearsal=rehearsal)

  # Both sides at the highest precision, as ``check_slot_attn``: float32
  # operands then multiply as float32 in the kernel too.
  with jax.default_matmul_precision("highest"):
    got = np.asarray(tiled()(flat, starts, *operands), np.float32)
    # The reference eight slots at a time: its score tensor for 128 slots
    # of 64 heads x 32 positions x 4224 rows would not fit.
    ref = jax.jit(lambda q, leaf, cur: slot_attention_reference(
        q, leaf, None, cur, v_width=r, scale=scale))
    clean = jnp.asarray(leaf, dtype).astype(jnp.float32)
    want = np.concatenate([
        np.asarray(ref(q[b:b + 8].astype(jnp.float32), clean[b:b + 8],
                       jnp.asarray(clamped[b:b + 8])))
        for b in range(0, B, 8)])
  check(np.isfinite(got).all(), "the plain tile attend's output not finite")
  err = rel_err(got[:total], want[live])
  tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
  check(err <= tol, f"plain tile attend {jnp.dtype(dtype).name}: {err:.3g} "
        f"of the reference's max, tol {tol}")
  check((got[total:] == 0).all(),
        "plain tile attend: rows no live position owns are not zeros")
  block = slot_attn_lib._tile_block(
      L, W, dtype, slot_attn_lib.tile_positions(C, H) * H, r, False)
  say(f"  slot_attn on the tile grid slots{B} L{L} H{H} W{W} values{r} "
      f"chunk{C} block{block} {jnp.dtype(dtype).name} ({total} live of {T} "
      f"flat rows, {int((num_valid == 1).sum())} decoding slots): {err:.2e} "
      "of the reference's max, NaN beyond the bounds unread, zeros beyond "
      "the live rows")
  if not timed or rehearsal:
    return
  first = compile_here(
      lambda q, *ops: slot_attention_pallas.__wrapped__(
          q, ops[0], None, *ops[1:], v_width=r, scale=scale),
      q, *operands, mosaic_calls=1, rehearsal=rehearsal)
  say(f"  the kernels alone, us a call (device trace, {SLOT_ATTN}): first "
      f"grid on [slots, chunk] operands "
      f"{kernel_us(first, (q,) + operands, SLOT_ATTN):.0f}; tile grid "
      + ", ".join(
          f"block {b} "
          f"{kernel_us(tiled(b), (flat, starts) + operands, SLOT_ATTN):.0f}"
          for b in (512, 1024, 2048)))


def phase_gigachat(sizes: Sizes) -> None:
  cell_cfg, slots, C = sizes.gigachat_cell
  resolved = report_rules(cell_cfg, slots, C)
  if not sizes.rehearsal:
    check(all(i == "pallas" for i in resolved.values()),
          f"a rule declined at the cell's shapes: {resolved}")
    from easyparallellibrary_tpu.serving.engine import flat_width
    check(kv_lib.step_lowerings(cell_cfg, slots, C, width=flat_width(
        slots, C))["tile_attn_out"] == "flat",
          "the cell's plain latent leaf did not take the tile grid")
  d = cell_cfg.linear_key_head_dim
  for dtype in (jnp.float32, jnp.bfloat16):
    # The cell's heads and chunk on 8 slots (the reference scans 32
    # positions of 64 matrix states a slot).
    check_gdn_scan(8, cell_cfg.linear_num_key_heads,
                   cell_cfg.linear_num_value_heads, d, C, dtype,
                   sizes.rehearsal)
    # The latent layer's attend as the cell's step runs it (PR 51): the
    # plain leaf on the tile grid, from the flat batch (a toy cut's two
    # heads are no sublane tile: eight there).
    L = kv_lib.kv_leaf_shape(cell_cfg, slots, C)[1]
    check_plain_tile_attend(
        slots, L, 8 if sizes.rehearsal else cell_cfg.num_heads,
        cell_cfg.latent_dim, cell_cfg.kv_lora_rank, C, dtype,
        sizes.rehearsal, timed=dtype == jnp.bfloat16)
  cfg = sizes.gigachat_cfg
  n_full = len(cfg.full_attention_layers)
  n_linear = cfg.num_layers - n_full
  n_moe = cfg.num_layers - cfg.first_k_dense
  gap, err = serve_expert_cut(
      sizes, GigaChat(cfg),
      {gdn_lib.GDN_SCAN: n_linear, MOE_GMM: 2 * n_moe, SLOT_ATTN: n_full,
       "kv_write": n_full}, "gigachat", gmm_may_decline=True)
  say(f"PASS gigachat: gdn_scan f32 + bf16 "
      + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + f" at the cell's heads against the reference scan; {n_linear} "
      f"gated delta-rule layers + {n_full} gated latent attention, "
      f"{cfg.experts_held[1]} of {cfg.n_routed_experts} experts held, "
      f"served tokens within {gap:.1e} of the teacher-forced best (the "
      f"chunked rule over the sequence); step logits kernels against "
      f"reference lowerings {err:.2e}")


# ------------------------------------------------------------------ glm5 --


def glm5_cell_cfg(**overrides) -> GlmMoeConfig:
  """GLM-5's cut as its cell serves it (perfbench/configs/glm-5.json): every
  layer a selecting latent attention, 64 of 256 experts held on the host."""
  return GlmMoeConfig(**{**dict(
      vocab_size=19360, num_layers=6, d_model=6144, d_ff=12288,
      moe_d_ff=2048, num_heads=64, q_lora_rank=2048, kv_lora_rank=512,
      qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
      index_topk=2048, index_n_heads=32, index_head_dim=128,
      n_routed_experts=256, experts_held=(0, 64), num_experts_per_tok=8,
      first_k_dense=1, routed_scaling_factor=2.5, max_seq_len=10752),
                          **overrides})


def check_exchange(cfg, chips: int, positions: int, uneven: bool,
                   rehearsal: bool) -> None:
  """One expert layer's routed sum with the held experts DIVIDED over
  ``chips`` chips (``models/moe.py:exchanged_experts`` under a
  ``shard_map``, the grouped matmul in the lowering the backend takes)
  against the one-chip layer that holds them all (``dropless_experts``
  with the same lowering of the grouped matmul, which ``check_moe_gmm``
  holds to ``ragged_dot`` apart: XLA's own lowering of it multiplies every
  row by every expert, 5e16 operations at these shapes), position for
  position.  ``uneven``: most
  assignments on the first chip's experts and none on the last's, so that
  the exchange takes several rounds."""
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
  from easyparallellibrary_tpu.models import moe as moe_lib
  from easyparallellibrary_tpu.utils.compat import shard_map
  first, held = cfg.experts_held
  D, F, k = cfg.d_model, cfg.moe_d_ff, cfg.num_experts_per_tok
  per = held // chips
  N = chips * positions
  r = np.random.RandomState(47)
  mesh = Mesh(np.array(jax.devices()[:chips]), ("expert",))
  split, whole = (NamedSharding(mesh, P("expert")),
                  NamedSharding(mesh, P()))
  draw = lambda shape, scale: jax.jit(
      lambda key: (scale * jax.random.normal(key, shape, jnp.float32)
                   ).astype(cfg.dtype), out_shardings=split)
  w_gate_up = draw((held, D, 2 * F), D ** -0.5)(jax.random.PRNGKey(1))
  w_down = draw((held, F, D), F ** -0.5)(jax.random.PRNGKey(2))
  x = jax.device_put(jnp.asarray(r.randn(N, D), cfg.dtype), split)
  if uneven:
    # 3 of 4 choices among the first chip's experts, the rest among the
    # second's and third's and the absent ones; none on the last chip's.
    pool = np.concatenate([np.repeat(first + np.arange(per), 12),
                           first + per + np.arange(2 * per),
                           (first + held + np.arange(per)) %
                           cfg.n_routed_experts])
    chosen = np.stack([r.choice(np.unique(pool), k, replace=False,
                                p=np.bincount(pool, minlength=
                                              cfg.n_routed_experts)[
                                                  np.unique(pool)]
                                / len(pool)) for _ in range(N)])
  else:
    chosen = np.stack([r.choice(cfg.n_routed_experts, k, replace=False)
                       for _ in range(N)])
  chosen = jax.device_put(jnp.asarray(chosen, jnp.int32), split)
  weights = jax.device_put(jnp.asarray(r.rand(N, k), jnp.float32), split)
  live = jax.device_put(jnp.asarray(r.rand(N) < 0.9), split)
  # The cell's rows a pair and round; a toy's would never be exceeded.
  rows = 8 if rehearsal else moe_lib.exchange_rows(
      positions, k, per, cfg.n_routed_experts)
  impl = "interpret" if rehearsal else "pallas"
  if rehearsal and D % 128:
    impl = "reference"

  def local(x, chosen, weights, live, a, b):
    y, sizes, sent, left, rounds = moe_lib.exchanged_experts(
        x, chosen, weights, live, a, b, axis="expert", rows=rows, impl=impl,
        first=first)
    return y, sizes, sent[None], left[None], rounds[None]
  mapped = jax.jit(shard_map(
      local, mesh, in_specs=(P("expert"),) * 6,
      out_specs=(P("expert"),) * 5))
  y, sizes, sent, left, rounds = mapped(x, chosen, weights, live, w_gate_up,
                                        w_down)
  one = jax.devices()[0]
  on_one = lambda a: jax.device_put(a, one)
  want, want_sizes = jax.jit(functools.partial(
      moe_lib.dropless_experts, impl=impl, first=first))(
          *map(on_one, (x, chosen, weights, live, w_gate_up, w_down)))
  check(np.array_equal(np.asarray(sizes), np.asarray(want_sizes)),
        "the exchange's experts were sent other rows than the one-chip "
        f"layer's: {np.asarray(sizes)} against {np.asarray(want_sizes)}")
  check(int(np.asarray(sent).sum()) == int(np.asarray(want_sizes).sum()),
        "an assignment to a held expert was dropped")
  err = rel_err(np.asarray(y, np.float32), np.asarray(want, np.float32))
  tol = 2e-2 if cfg.dtype == jnp.bfloat16 else 1e-4
  check(err <= tol, f"exchanged layer: error {err:.3g} of the one-chip "
        f"layer's max, tol {tol}")
  took = int(np.asarray(rounds).max())
  check(took > 1 if uneven else took == 1 or rehearsal,
        f"the exchange took {took} round(s), uneven={uneven}")
  by_chip = np.asarray(want_sizes).reshape(chips, per).sum(1)
  say(f"  exchange {chips} chips x {positions} positions, {per} experts a "
      f"chip, {rows} rows a pair and round, {'uneven' if uneven else 'even'}"
      f" routing: rows received a chip {by_chip.tolist()}, "
      f"{int(np.asarray(left).sum())} of {int(np.asarray(sent).sum())} "
      f"held assignments left their chip, {took} round(s), {err:.2e} of "
      "the one-chip layer's max, none dropped")


def phase_glm5(sizes: Sizes) -> None:
  """The GLM-5 cell's kernels and its exchange before the long runs: the
  four rules on ONE chip's share of the cell's leaves, the index scores,
  the selected attend and the grouped matmul at those shapes, and (four
  chips) one exchanged expert layer at the cell's widths against the
  one-chip layer that holds the same 64 experts, under even and under
  uneven routing."""
  count = len(jax.devices())
  if sizes.rehearsal:
    cfg = glm5_cell_cfg(
        vocab_size=512, num_layers=3, d_model=128, d_ff=256, moe_d_ff=128,
        num_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_topk=16, index_n_heads=2,
        index_head_dim=128, n_routed_experts=16, experts_held=(4, 8),
        num_experts_per_tok=2, max_seq_len=232, dtype=jnp.float32,
        param_dtype=jnp.float32)
    slots, C, check_slots, positions = 4, 8, 4, 16
  else:
    cfg = glm5_cell_cfg()
    slots, C, check_slots, positions = 32, 32, 8, 512
  resolved = report_rules(cfg, slots, C)
  if not sizes.rehearsal:
    check(all(i == "pallas" for i in resolved.values()),
          f"a rule declined at the cell's shapes a chip: {resolved}")
  dims = cfg.latent_dims()
  Lc = kv_lib.cache_length(cfg, C)
  dtype = cfg.dtype
  picked = check_dsa_index(check_slots, Lc, C, dims.indexer.num_heads,
                           dims.indexer.head_dim, cfg.index_topk, dtype,
                           sizes.rehearsal)
  check_selected_attend(check_slots, Lc, C, dims.num_heads, dims.latent_dim,
                        dims.kv_lora_rank, picked, dtype, sizes.rehearsal)
  if not sizes.rehearsal:
    per = cfg.experts_held[1] // 4
    rows = 4 * 384
    for K, N in ((cfg.d_model, 2 * cfg.moe_d_ff), (cfg.moe_d_ff,
                                                   cfg.d_model)):
      check_moe_gmm(rows, K, N, per, dtype, sizes.rehearsal)
  if count < 4:
    say(f"  exchange: skipped, the machine has {count} device(s)")
  else:
    for uneven in (False, True):
      check_exchange(cfg, 4, positions, uneven, sizes.rehearsal)
  say("PASS glm5: the rules on a chip's share of the cell's leaves, "
      "dsa_index, kth_largest, slot_attn_sel and moe_gmm "
      + ("INTERPRETED" if sizes.rehearsal else "compiled")
      + " at them" + ("" if count < 4 else
                      "; one exchanged expert layer over four chips equal to "
                      "the one-chip layer under even and uneven routing"))


# ---------------------------------------------------------------- overlap --


def serve_both_loops(model, params, sizes: Sizes, what: str, n: int = 32):
  """``n`` requests through the engine's overlapped loop (step k+1 launched
  before step k's tokens are fetched) and through the serial one on the
  same program: every stream equal, one compile each, nothing wasted
  (every request ends by length), and the median time between two
  ``step()`` returns of each loop.  The period is a host clock around a
  loop that waits for the device once a step; only a chip run gives it."""
  cfg = model.cfg
  prompts = seeded_requests(sizes, cfg, n=n)
  streams, period_ms = {}, {}
  for loop in ("serial", "overlapped"):
    eng = ContinuousBatchingEngine(model, params, num_slots=n)
    check(eng.step_overlap == "on",
          f"{what}: the plain contiguous engine says step overlap "
          f"{eng.step_overlap!r}")
    if loop == "serial":
      eng._overlap = False     # nothing in flight yet: same program
    eng.submit(Request(uid="warm", prompt=prompts[0][:8], max_new_tokens=4))
    eng.run()
    for uid, p in enumerate(prompts):
      check(eng.submit(Request(uid=uid, prompt=p,
                               max_new_tokens=sizes.new_tokens)),
            f"request {uid} refused at admission")
    out, returns = {}, []
    while eng.has_work:
      for fin in eng.step():
        out[fin.uid] = fin.tokens
      returns.append(time.perf_counter())
    for uid, p in enumerate(prompts):
      check(uid in out and len(out[uid]) == len(p) + sizes.new_tokens
            and eng.finished[uid].finish_reason == "length",
            f"{what}, {loop}: request {uid} did not run to its length")
    check(eng._step_fn._cache_size() == 1,
          f"{what}, {loop}: fused step compiled "
          f"{eng._step_fn._cache_size()} times")
    check(eng.scheduler.wasted_positions == 0,
          f"{what}, {loop}: {eng.scheduler.wasted_positions} positions "
          "wasted on requests that end by length")
    streams[loop] = out
    period_ms[loop] = 1e3 * float(np.median(np.diff(returns)))
    say(f"  {what}, {loop} loop: {eng._steps} steps of {n} slots x chunk "
        f"{eng.chunk}, median period {period_ms[loop]:.3f} ms")
    eng.close()
    del eng
  for uid in range(n):
    at = first_difference(streams["overlapped"][uid], streams["serial"][uid])
    check(at is None, f"{what} request {uid}: the overlapped loop's stream "
          f"differs from the serial loop's at position {at}")
  return period_ms


def phase_overlap(sizes: Sizes) -> None:
  epl.init(devices=jax.devices()[:1])
  init = lambda model, seed: jax.jit(lambda: model.init(
      jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"])()
  lines = []
  for what, model, seed in (("GPT-2 medium", GPT(sizes.serve_cfg), 0),
                            ("lfm2 cut", Lfm2Moe(sizes.lfm2_cfg), 2)):
    ms = serve_both_loops(model, init(model, seed), sizes, what)
    lines.append(f"{what} serial {ms['serial']:.3f} ms | overlapped "
                 f"{ms['overlapped']:.3f} ms")
  say("PASS overlap: 32 requests a model, every stream of the overlapped "
      "loop equal to the serial loop's, one compile, none wasted; median "
      "period " + "; ".join(lines)
      + (" (REHEARSAL: no measurement)" if sizes.rehearsal else ""))


# ------------------------------------------------------------------- main --


def require_tpu() -> jax.Device:
  """The first device, which must be a TPU: a measurement path that
  finds no chip fails, it does not fall back."""
  dev = jax.devices()[0]
  if dev.platform != "tpu":
    raise SystemExit(
        f"no TPU: jax {jax.__version__} found platform {dev.platform!r} "
        f"({dev.device_kind!r}, {len(jax.devices())} device(s)); this "
        "program measures on a TPU only")
  return dev


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument(
      "--rehearse-cpu", action="store_true",
      help="toy sizes, interpreted kernels, on the CPU; always exits "
           f"{REHEARSAL_EXIT}; not a pass")
  parser.add_argument(
      "--only", default=None,
      help="run this one phase (kernels, train, serve, hybrid, experts, "
           "lfm2, dots3, smallthinker, gigachat, glm5, overlap); prints no "
           "result line")
  args = parser.parse_args(argv)
  t_start = time.perf_counter()
  cache_dir = compile_cache.configure()

  dev = jax.devices()[0]
  count = len(jax.devices())
  say(f"jax {jax.__version__}")
  say(f"platform {dev.platform}")
  say(f"device_kind {dev.device_kind}")
  say(f"device_count {count}")
  say(f"compile cache {cache_dir}")
  if args.rehearse_cpu:
    say("REHEARSAL: toy sizes, interpreted kernels, whatever platform "
        "this is.  Nothing below is a pass or a measurement.")
    sizes = Sizes.toy()
  else:
    require_tpu()
    sizes = Sizes.real()

  for name, phase in (("kernels", lambda: phase_kernels(sizes)),
                      ("train", lambda: phase_train(sizes, dev)),
                      ("serve", lambda: phase_serve(sizes)),
                      ("hybrid", lambda: phase_hybrid(sizes)),
                      ("experts", lambda: phase_experts(sizes)),
                      ("lfm2", lambda: phase_lfm2(sizes)),
                      ("dots3", lambda: phase_dots3(sizes)),
                      ("smallthinker", lambda: phase_smallthinker(sizes)),
                      ("gigachat", lambda: phase_gigachat(sizes)),
                      ("glm5", lambda: phase_glm5(sizes)),
                      ("overlap", lambda: phase_overlap(sizes))):
    if args.only not in (None, name):
      continue
    t0 = time.perf_counter()
    say(f"== {name}")
    phase()
    say(f"   ({name}: {time.perf_counter() - t0:.1f} s)")
  if args.only is not None:
    say(f"only {args.only!r} ran: not the whole proof, no result line")
    return REHEARSAL_EXIT if args.rehearse_cpu else 0
  if count >= 4:
    t0 = time.perf_counter()
    say("== four chips")
    phase_four_chips(sizes)
    say(f"   (four chips: {time.perf_counter() - t0:.1f} s)")
  else:
    say(f"== four chips: skipped, the machine has {count} device(s)")
  say(f"total {time.perf_counter() - t_start:.1f} s")

  if args.rehearse_cpu:
    say("REHEARSAL complete: every phase ran.  This is not a pass; "
        f"exiting {REHEARSAL_EXIT}.")
    return REHEARSAL_EXIT
  print(json.dumps({"ok": True, "device": {
      "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
  return 0


if __name__ == "__main__":
  # The exit code, said where a reader of the output's end cannot miss it
  # (a failed check raises past a ``tail``): on standard error, so that a
  # passing run's result line stays the last of standard output.
  code = 1
  try:
    code = main()
  finally:
    print(f"chip_smoke.py exit code {code}", file=sys.stderr, flush=True)
  sys.exit(code)
