"""The decoder of gated short convolutions beside grouped attention with
routed experts and no shared one (models/lfm2_moe.py) against the
benchmark's plain reference (perfbench/reference/lfm2_moe.py), and its
convolution windows, K/V rows and expert layers in the continuous-batching
engine.

Toy sizes with every mechanism: hidden 512 as 8 query heads of 64 on 2 K/V
heads (so the K/V leaf FOLDS: 2 x 64 = one lane tile, kept in rows, two
heads sharing it, four query heads a K/V head: the cell's leaf in small),
layers conv + dense, attention + experts, conv + experts, conv + experts, 8
experts top-2 of width 128, no shared one, vocabulary 256.  float32 on both
sides, matmuls at ``highest``.  Tolerances: logits are O(1-10) (weights
N(0, 0.1): the published 0.02 would give a model that copies its input at
this depth), and program and reference differ by float32 rounding in
another order of the same sums (a window and a cache against the whole
sequence, sorted rows against a loop over experts), so ``3e-4`` absolute on
logits is ~30 x what is seen (1e-5) and far below what any lower precision
gives (bfloat16 matmuls or a bfloat16 window: >= 1e-2, held by a test) or
a dropped term, a stale window or a mis-turned rotary (>= 1e-2).  The seed
is one whose router has no near-tie between its 2nd and 3rd expert within
rounding (tests/test_glm_moe.py says why).
"""

import dataclasses
import functools
import gc
import importlib
import os
import sys
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.kernels.kv_write import kv_write  # noqa: E402
from easyparallellibrary_tpu.kernels.slot_attention import (  # noqa: E402
    slot_attention)
from easyparallellibrary_tpu.models import GPTConfig  # noqa: E402
from easyparallellibrary_tpu.models import moe as moe_lib  # noqa: E402
from easyparallellibrary_tpu.models.glm_moe import GlmMoeConfig  # noqa: E402
from easyparallellibrary_tpu.models.layer_kinds import ATTENTION, CONV  # noqa: E402
from easyparallellibrary_tpu.models.lfm2_moe import Lfm2MoeConfig  # noqa: E402
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import (  # noqa: E402
    ROADMAP_RECURRENT_STATE, check_draft_compatible, check_servable)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter  # noqa: E402
from perfbench.reference import lfm2_moe as ref  # noqa: E402
from perfbench.runners import epl_lfm2_moe as glue  # noqa: E402

KERNELS = [importlib.import_module(f"easyparallellibrary_tpu.kernels.{m}")
           for m in ("kv_write", "slot_attention", "moe_gmm")]

REF_CFG = ref.Lfm2MoeConfig(
    layer_types=("conv", "full_attention", "conv", "conv"), hidden_size=512,
    intermediate_size=256, moe_intermediate_size=128, num_attention_heads=8,
    num_key_value_heads=2, conv_L_cache=3, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, vocab_size=256, n_positions=128,
    initializer_range=0.1, bias_std=0.05)
F32 = {"dtype": "float32", "param_dtype": "float32"}
LOGIT_TOL = 3e-4
S = 40


@pytest.fixture(scope="module")
def both():
  """(program model, its params, reference params) from one seed."""
  epl.init()
  key = ref.seed_key(2 ** 31 + 5)
  model, shell_of = glue.build_model(REF_CFG, F32)
  params = glue.program_params(
      REF_CFG, key, shell_of(jnp.zeros((1, 8), jnp.int32)))
  return model, params, jax.jit(lambda k: ref.init_params(REF_CFG, k))(key)


@pytest.fixture(scope="module")
def ids():
  return jax.random.randint(jax.random.PRNGKey(0), (3, S), 0, 256)


# One compile of the reference for every ``[.., S]`` batch of ids.
REF_LOGITS = jax.jit(lambda p, ids: ref.logits(REF_CFG, p, ids))


@pytest.fixture(scope="module")
def want(both, ids):
  return REF_LOGITS(both[2], ids)


def _backend_takes(monkeypatch, impl):
  for mod in KERNELS:
    monkeypatch.setattr(mod, "_backend_impl", lambda: impl)


_STEPS = {}


def _step_fn(model, **impls):
  """``slot_step_logits`` of ``model``, jitted once a choice of
  lowerings (a chunk width is a shape: its own compile, made once)."""
  key = tuple(sorted(impls.items()))
  if key not in _STEPS:
    _STEPS[key] = jax.jit(
        lambda params, kv, tokens, cursors, num_valid, reset:
        slot_step_logits(model, params, kv, tokens, cursors,
                         num_valid=num_valid, reset=reset, **impls))
  return _STEPS[key]


def _through_the_cache(model, params, ids, chunk, ragged=None, **impls):
  """Chunked prefill, then decode, through ``slot_step_logits``: the
  logits at every position, and the final cache.  ``ragged`` caps what
  slot ``b`` feeds in step ``s`` at ``ragged[(s + b) % len]`` (0: idle)."""
  B, n_tok = ids.shape
  kv, cursors = kv_lib.allocate_kv_cache(model.cfg, B, chunk)
  fed = np.zeros((B,), np.int64)
  out = [[] for _ in range(B)]
  step = 0
  call = functools.partial(_step_fn(model, **impls), params)
  with jax.default_matmul_precision("highest"):
    while (fed < n_tok).any():
      tokens = np.zeros((B, chunk), np.int32)
      num_valid = np.zeros((B,), np.int32)
      for b in range(B):
        n = min(chunk, n_tok - fed[b])
        if ragged is not None:
          n = min(n, ragged[(step + b) % len(ragged)])
        tokens[b, :n] = np.asarray(ids[b, fed[b]:fed[b] + n])
        num_valid[b] = n
      logits, kv = call(kv, jnp.asarray(tokens), cursors,
                        jnp.asarray(num_valid), jnp.asarray(fed == 0))
      for b in range(B):
        out[b].append(logits[b, :num_valid[b]])
      cursors = cursors + num_valid
      fed += num_valid
      step += 1
  return jnp.stack([jnp.concatenate(o) for o in out]), kv


# ------------------------------------------------------ model vs reference --


def test_weights_sit_where_the_reference_has_them(both):
  """The glue makes a layer at a time what ``init_params`` makes: the same
  values (the sums of squares agree to rounding), gate and up joined in
  one stack, no shared expert and no head of its own in the tree."""
  model, params, rp = both
  a = float(glue.sum_of_squares(params))
  b = float(glue.sum_of_squares(rp))
  assert abs(a - b) <= 1e-5 * b
  p = nn.meta.unbox(params)
  ex = rp["layers"][2]["ff"]["experts"]
  np.testing.assert_array_equal(
      np.asarray(p["block_2"]["moe"]["experts_gate_up"]),
      np.concatenate([np.asarray(ex["gate"], np.float32),
                      np.asarray(ex["up"], np.float32)], -1))
  assert set(p) == {"embed", "norm_f"} | {f"block_{i}" for i in range(4)}
  assert set(p["block_0"]) == {"norm_in", "norm_ff", "conv", "mlp"}
  assert set(p["block_1"]) == {"norm_in", "norm_ff", "attn", "moe"}
  assert set(p["block_1"]["attn"]) == {"q", "k", "v", "o", "q_norm",
                                       "k_norm"}
  assert p["block_1"]["attn"]["q_norm"]["scale"].shape == (64,)
  assert REF_CFG.param_count() == sum(
      x.size for x in jax.tree_util.tree_leaves(rp)) == sum(
          x.size for x in jax.tree_util.tree_leaves(p))
  assert model.cfg.layer_kinds() == (CONV, ATTENTION, CONV, CONV)


def test_full_forward_matches_the_reference(both, ids, want):
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    got = jax.jit(lambda p, ids: model.apply({"params": p}, ids))(params,
                                                                  ids)
  assert float(jnp.abs(want).max()) > 1.0          # not a model of zeros
  assert float((jnp.argmax(want, -1) == ids).mean()) < 0.5   # nor a copier
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("precision", ["bfloat16", "bf16conv"])
def test_the_tolerance_is_one_a_lower_precision_fails(both, ids, want,
                                                      precision):
  """What the comparisons above and below would catch: every matmul's
  operands in bfloat16, or only the convolution's inputs (what a bfloat16
  window in a float32 configuration would hold), moves the logits by far
  more than the tolerance."""
  low = ref.logits(REF_CFG, both[2], ids, precision)
  assert float(jnp.abs(low - want).max()) > 30 * LOGIT_TOL


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_prefill_in_chunks_then_decode_matches_the_full_forward(
    both, ids, want, chunk):
  """Chunks of 16 and 4 are prefill, 1 is decode through the windows and
  the K/V rows: every position's logits equal the reference's
  whole-sequence forward (logits, not tokens)."""
  model, params, _ = both
  got, _ = _through_the_cache(model, params, ids, chunk)
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_ragged_chunks_and_idle_slots(both, ids, want):
  """Slots advance by 0, 1, 3 or a whole chunk in the same call: each
  window takes exactly its own ``num_valid`` products, rotary turns each
  token by ``cursor + i``, and only live positions reach an expert."""
  model, params, _ = both
  got, _ = _through_the_cache(model, params, ids, 8, ragged=(8, 0, 1, 3))
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_interpreted_kernels_equal_the_reference_lowerings(both, ids, want):
  """All three kernels in the step, interpreted: the rows-form write and
  attend on the folded leaf (two K/V heads in one lane tile, four query
  heads a K/V head) and the grouped matmul, against the reference's
  logits."""
  model, params, _ = both
  assert kv_lib.kv_leaf_shape(model.cfg, 3, 8) == (3, 136, 128)
  got, _ = _through_the_cache(
      model, params, ids, 8, ragged=(8, 3, 8, 1), kv_write_impl="interpret",
      slot_attn_impl="interpret", moe_gmm_impl="interpret")
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


# --------------------------------------------------- the convolution window --


def test_an_idle_slot_keeps_its_window_bit_for_bit(both, ids):
  """``num_valid`` 0 leaves a window as it was; a slot that feeds 3 of 8
  positions keeps the products of its 2nd and 3rd, not of its 7th and
  8th."""
  model, params, _ = both
  _, kv = _through_the_cache(model, params, ids[:, :16], 8)
  cursors = jnp.full((3,), 16, jnp.int32)
  tokens = np.asarray(ids[:, 16:24])
  nv = jnp.asarray([8, 0, 3], jnp.int32)
  step = lambda t: _step_fn(model)(
      params, kv, jnp.asarray(t, jnp.int32), cursors, nv,
      jnp.zeros((3,), bool))[1]
  after = step(tokens)
  garbled = tokens.copy()
  garbled[2, 3:] = (garbled[2, 3:] + 7) % 256       # beyond num_valid
  after_garbled = step(garbled)
  windows = [n for n, block in kv.items() if "conv" in block]
  assert len(windows) == 3
  for name in windows:
    before = np.asarray(kv[name]["conv"]["conv_state"])
    now = np.asarray(after[name]["conv"]["conv_state"])
    assert before.shape == (3, 2, 512)
    np.testing.assert_array_equal(before[1], now[1])
    assert not np.array_equal(before[0], now[0])
    # positions beyond num_valid neither read into nor advance the window
    np.testing.assert_array_equal(
        now[2], np.asarray(after_garbled[name]["conv"]["conv_state"])[2])


def test_a_reused_slot_starts_from_an_empty_window(both, ids, want):
  """A slot that held another request gives, after ``reset``, the logits
  of a fresh cache.  Without the reset it does not: a stale window is
  masked by nothing."""
  model, params, _ = both
  other = jnp.flip(ids, axis=1)
  _, kv = _through_the_cache(model, params, other, 8)

  def replay(reset):
    cursors = jnp.full((3,), S, jnp.int32)
    with jax.default_matmul_precision("highest"):
      logits, _ = _step_fn(model)(
          params, kv, ids[:, :8], jnp.where(reset, 0, cursors),
          jnp.full((3,), 8, jnp.int32), reset)
    return logits

  fresh = replay(jnp.ones((3,), bool))
  assert float(jnp.abs(fresh - want[:, :8]).max()) < LOGIT_TOL
  # slot 1 keeps its cursor AND its window: both are the other request's
  stale = replay(jnp.asarray([True, False, True]))
  assert float(jnp.abs(stale[1] - want[1, :8]).max()) > 1e-2
  assert float(jnp.abs(stale[0] - want[0, :8]).max()) < LOGIT_TOL


def test_a_stale_window_alone_changes_the_first_positions(both, ids, want):
  """The window by itself: a cursor back at 0 (so no K/V row of the old
  request is visible) but NO reset reads the old request's last two
  products into positions 0 and 1 of the new one."""
  model, params, _ = both
  _, kv = _through_the_cache(model, params, jnp.flip(ids, axis=1), 8)
  with jax.default_matmul_precision("highest"):
    logits, _ = _step_fn(model)(
        params, kv, ids[:, :8], jnp.zeros((3,), jnp.int32),
        jnp.full((3,), 8, jnp.int32), jnp.zeros((3,), bool))
  assert float(jnp.abs(logits[:, 0] - want[:, 0]).max()) > 1e-2


def test_rotary_turns_by_cursor_plus_i(both, ids, want):
  """Fed at the right cursors a chunk gives the full forward's logits (the
  tests above); fed the SAME cache rows but told a cursor that is off by
  one for the rotation only, it does not: the rotation is a function of
  ``cursor + i``."""
  model, params, _ = both
  _, kv = _through_the_cache(model, params, ids[:, :16], 8)
  kv = jax.tree_util.tree_map(lambda x: x, kv)
  attn = kv["block_1"]["attn"]
  # the same 16 rows one position later, the cursor with them
  shifted = dict(kv, block_1={"attn": {
      k: jnp.roll(v, 1, axis=1) for k, v in attn.items()}})
  nv, keep = jnp.full((3,), 8, jnp.int32), jnp.zeros((3,), bool)
  step = functools.partial(_step_fn(model), params)
  with jax.default_matmul_precision("highest"):
    right, _ = step(kv, ids[:, 16:24], jnp.full((3,), 16, jnp.int32), nv,
                    keep)
    moved, _ = step(shifted, ids[:, 16:24], jnp.full((3,), 17, jnp.int32),
                    nv, keep)
  assert float(jnp.abs(right - want[:, 16:24]).max()) < LOGIT_TOL
  # relative positions are the same but for the new row 0 (zeros at
  # position 0 of the rolled leaf): rotary is relative, so the logits
  # move only by what that one extra visible row adds ...
  assert float(jnp.abs(moved - want[:, 16:24]).max()) > 1e-3
  # ... and an ABSOLUTE mis-turn (rows where they were, cursor off by
  # one) moves them far more than the tolerance.
  with jax.default_matmul_precision("highest"):
    off, _ = step(kv, ids[:, 16:24], jnp.full((3,), 15, jnp.int32), nv, keep)
  assert float(jnp.abs(off - want[:, 16:24]).max()) > 1e-2


# ------------------------------------- rows on grouped, lane-sharing heads --


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_rows_write_and_attend_on_two_kv_heads_of_64_under_eight(impl):
  """The cell's leaf in small, at the kernels' own door: K/V kept in rows
  ``[B, Lc, 2 x 64]`` (two heads in one lane tile) under 8 query heads (4
  a K/V head) gives what the same leaf kept in positions ``[B, Lc, 2,
  64]`` gives under the reference lowerings: the write bit for bit on the
  fed slots, the attend to float32 rounding on the live positions."""
  r = np.random.RandomState(1)
  B, Lc, C, H, Hkv, hd = 4, 136, 8, 8, 2, 64
  ck, cv = (jnp.asarray(r.randn(B, Lc, Hkv, hd), jnp.float32)
            for _ in range(2))
  k, v = (jnp.asarray(r.randn(B, C, Hkv, hd), jnp.float32) for _ in range(2))
  q = jnp.asarray(r.randn(B, C, H, hd), jnp.float32)
  cursors = jnp.asarray([0, 125, 60, 128], jnp.int32)
  nv = jnp.asarray([8, 3, 0, 1], jnp.int32)
  rows = lambda x: x.reshape(B, Lc, Hkv * hd)
  with jax.default_matmul_precision("highest"):
    wk, wv = kv_write(ck, cv, k, v, cursors, nv, impl="reference")
    want = slot_attention(q, wk, wv, cursors, nv, impl="reference")
    gk, gv = kv_write(rows(ck), rows(cv), k, v, cursors, nv, impl=impl)
    got = slot_attention(q, gk, gv, cursors, nv, impl=impl)
  assert gk.shape == (B, Lc, 128) and got.shape == (B, C, H, hd)
  fed = np.asarray(nv) > 0
  np.testing.assert_array_equal(np.asarray(gk)[fed],
                                np.asarray(rows(wk))[fed])
  np.testing.assert_array_equal(np.asarray(gv)[fed],
                                np.asarray(rows(wv))[fed])
  live = (np.arange(C)[None] < np.asarray(nv)[:, None])[:, :, None, None]
  np.testing.assert_allclose(np.where(live, got, 0), np.where(live, want, 0),
                             atol=2e-5)


def test_the_rules_take_the_cells_leaf(monkeypatch):
  """On a TPU backend every rule resolves the kernel for the cell's
  shapes: the leaf ``[128, 4112, 512]`` in rows (8 K/V heads of 64 under
  32 query heads, chunk 16) and the two grouped products over 32 experts;
  there is no scan to resolve."""
  _backend_takes(monkeypatch, "pallas")
  cfg = Lfm2MoeConfig(layer_types=Lfm2MoeConfig().layer_types[:14])
  assert kv_lib.kv_leaf_shape(cfg, 128, 16) == (128, 4112, 512)
  assert kv_lib.kv_write_impl(cfg, 128, 16) == "pallas"
  assert kv_lib.slot_attn_impl(cfg, 128, 16) == "pallas"
  assert kv_lib.moe_gmm_impl(cfg, 128, 16) == "pallas"
  assert kv_lib.ssm_scan_impl(cfg, 128, 16) is None
  assert kv_lib.has_recurrent_state(cfg)


# -------------------------------------------------- no shared expert, GLM --


class _MoeCfg:
  d_model, n_routed_experts, num_experts_per_tok, moe_d_ff = 64, 8, 2, 32
  routed_scaling_factor, norm_topk_prob, route_norm_eps = 1.8, True, 1e-20
  dtype = param_dtype = jnp.float32

  def __init__(self, n_shared_experts, **more):
    self.n_shared_experts = n_shared_experts
    self.__dict__.update(more)


def test_no_shared_expert_means_no_shared_in_the_tree_and_glm_is_unchanged():
  """``n_shared_experts`` 0 builds no shared MLP and returns the routed
  sum alone.  With a shared expert the tree and the output are what they
  were: ``Shared(x) + routed`` bit for bit, the routed part from the very
  same parameters."""
  x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64))
  glm = moe_lib.DroplessMoE(_MoeCfg(1))
  bare = moe_lib.DroplessMoE(_MoeCfg(0))
  p_glm = nn.meta.unbox(glm.init(jax.random.PRNGKey(4), x)["params"])
  p_bare = nn.meta.unbox(bare.init(jax.random.PRNGKey(4), x)["params"])
  routed_keys = {"router_kernel", "e_score_correction_bias",
                 "experts_gate_up", "experts_down"}
  assert set(p_bare) == routed_keys
  assert set(p_glm) == routed_keys | {"shared"}
  assert set(p_glm["shared"]) == {"gate", "up", "down"}
  routed_params = {k: p_glm[k] for k in routed_keys}
  routed = bare.apply({"params": routed_params}, x)
  from easyparallellibrary_tpu.models.blocks import GatedMLP
  shared = GatedMLP(_MoeCfg(1), d_ff=32).apply(
      {"params": p_glm["shared"]}, x)
  np.testing.assert_array_equal(
      np.asarray(glm.apply({"params": p_glm}, x)),
      np.asarray(shared + routed))
  assert float(jnp.abs(routed).max()) > 0


def test_the_routers_epsilon_is_glms_by_default_and_the_models_in_the_layer():
  r = np.random.RandomState(2)
  x = jnp.asarray(r.standard_normal((16, 64)), jnp.float32)
  w = jnp.asarray(r.standard_normal((64, 8)) * 0.3, jnp.float32)
  b = jnp.zeros((8,), jnp.float32)
  c0, w0 = moe_lib.noaux_tc_route(x, w, b, 2, 1.8)
  c1, w1 = moe_lib.noaux_tc_route(x, w, b, 2, 1.8, True, 1e-20)
  np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
  np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
  s = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ w)), np.asarray(c0),
                         1)
  _, w6 = moe_lib.noaux_tc_route(x, w, b, 2, 1.0, True, 1e-6)
  np.testing.assert_allclose(np.asarray(w6),
                             s / (s.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
  # the layer reads the model's own epsilon
  lfm = moe_lib.DroplessMoE(_MoeCfg(0, route_norm_eps=0.5))
  glm = moe_lib.DroplessMoE(_MoeCfg(0))
  xs = x[None]
  params = glm.init(jax.random.PRNGKey(0), xs)["params"]
  assert float(jnp.abs(lfm.apply({"params": params}, xs)
                       - glm.apply({"params": params}, xs)).max()) > 1e-3


def test_glms_config_has_a_shared_expert_and_its_epsilon():
  cfg = GlmMoeConfig()
  assert (cfg.n_shared_experts, cfg.route_norm_eps) == (1, 1e-20)
  assert Lfm2MoeConfig().n_shared_experts == 0
  assert Lfm2MoeConfig().route_norm_eps == 1e-6


# -------------------------------------------------------------- the engine --


def _requests():
  r = np.random.RandomState(0)
  return [Request(uid=i, prompt=r.randint(0, 256, n).astype(np.int32),
                  max_new_tokens=m)
          for i, (n, m) in enumerate(((5, 6), (17, 4), (9, 8), (30, 5),
                                      (12, 7), (3, 9)))]


def _serve(model, params, **kw):
  eng = ContinuousBatchingEngine(model, params, num_slots=4,
                                 prefill_chunk=8, **kw)
  for req in _requests():
    assert eng.submit(req)
  with jax.default_matmul_precision("highest"):
    out = eng.run()
  assert eng._step_fn._cache_size() == 1
  return eng, out


def test_engine_on_mixed_prompts_equals_per_request_reference_decoding(both):
  """Six requests of mixed lengths through four slots (prefill chunks
  beside decodes beside idle slots in one step, every slot reused): every
  served token is the reference's own next token for that request
  alone."""
  model, params, rp = both
  eng, out = _serve(model, params)
  assert kv_lib.resolved(eng.lowerings) == dict.fromkeys(
      ("kv_write_impl", "slot_attn_impl", "moe_gmm_impl"), "reference")
  assert eng.lowerings["ssm_scan_impl"] is None
  reqs = _requests()
  padded = np.zeros((len(reqs), S), np.int32)
  for req in reqs:
    stream = np.asarray(out[req.uid])
    assert len(stream) == len(req.prompt) + req.max_new_tokens <= S
    padded[req.uid, :len(stream)] = stream
  lg = np.asarray(REF_LOGITS(rp, jnp.asarray(padded)))   # causal: the
  for req in reqs:                      # padding behind a stream is unseen
    n, end = len(req.prompt), len(out[req.uid])
    served = lg[req.uid, np.arange(n - 1, end - 1), padded[req.uid, n:end]]
    best = lg[req.uid, n - 1:end - 1].max(-1)
    assert float((best - served).max()) < LOGIT_TOL


def test_an_engine_frees_its_cache_when_its_owner_lets_go(both):
  """No reference cycle through the engine: dropping the last outside
  reference frees the cache at once, the collector off (the cell's run
  hands the chip to the float32 reference right after the window)."""
  model, params, _ = both
  gc.collect()
  gc.disable()
  try:
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   prefill_chunk=4)
    eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3))
    eng.run()
    alive = weakref.ref(eng)
    leaf = weakref.ref(jax.tree_util.tree_leaves(eng._kv)[0])
    eng.close()
    del eng
    assert alive() is None and leaf() is None
  finally:
    gc.enable()


def test_the_cache_is_a_window_a_conv_layer_and_rows_an_attention_layer(both):
  cfg = both[0].cfg
  leaves = kv_lib.cache_leaves(cfg, 5, 8)
  assert set(leaves["block_0"]) == {"conv"}
  assert set(leaves["block_0"]["conv"]) == {"conv_state"}
  assert leaves["block_0"]["conv"]["conv_state"].shape == (5, 2, 512)
  assert leaves["block_1"]["attn"]["cached_key"].shape == (5, 136, 128)
  layout = kv_lib.cache_layout(cfg, 5, 8)
  assert layout == {"kv_bytes": 2 * 5 * 136 * 128 * 4, "kv_leaves": 2,
                    "state_bytes": 3 * 5 * 2 * 512 * 4, "state_leaves": 3,
                    "kv_order": "rows"}
  assert kv_lib.recurrent_kinds(cfg) == (CONV,)
  # the cell's: 3 attention layers of [128, 4112, 512] rows, 11 windows
  real = Lfm2MoeConfig(layer_types=Lfm2MoeConfig().layer_types[:14])
  cell = kv_lib.cache_layout(real, 128, 16)
  assert cell["kv_bytes"] == 128 * 4112 * 3 * 2 * 512 * 2 == 3233808384
  assert (cell["state_leaves"], cell["state_bytes"]) == (
      11, 11 * 128 * 2 * 2048 * 2)
  assert cell["kv_order"] == "rows"


def test_the_engine_says_what_it_holds_and_counts_what_it_touched(both):
  """Trace metadata and counters of a model that has recurrent state AND
  routed experts: ``serving/state_resets`` beside ``serving/
  routed_positions``, ``serving/expert_load_max`` and the new ``serving/
  experts_touched_min``, against a count made by hand from the model's
  own router on a step's inputs."""
  model, params, _ = both
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  stats = ServingStats()
  try:
    eng, _ = _serve(model, params, stats=stats)
    events = tracer.events()
  finally:
    trace_lib.install(None)
  meta = {ev["name"]: ev["args"] for ev in events if ev["ph"] == "M"
          and ev["name"].startswith("serving/")}
  assert meta["serving/moe_gmm_impl"] == {"impl": "reference"}
  assert "serving/ssm_scan_impl" not in meta
  assert meta["serving/cache_layout"] == eng.cache_layout
  assert meta["serving/cache_layout"]["state_leaves"] == 3
  assert meta["serving/cache_layout"]["kv_order"] == "rows"
  counters = lambda name: [ev["args"]["value"] for ev in events
                           if ev["ph"] == "C" and ev["name"] == name]
  touched = counters("serving/experts_touched_min")
  routed = counters("serving/routed_positions")
  assert len(touched) == len(routed) == len(
      counters("serving/expert_load_max")) == len(
          counters("serving/state_resets")) > 0
  assert sum(counters("serving/state_resets")) == len(_requests())
  # at most min(E, live positions x top_k) experts can be touched, and a
  # step that routed anything touched at least top_k
  for t, n in zip(touched, routed):
    assert t == int(t) and (2 if n else 0) <= t <= min(8, 2 * n)
  summary = stats.summary()
  live = [t for t, n in zip(touched, routed) if n]
  assert summary["experts_touched_min_mean"] == pytest.approx(
      sum(live) / len(live))


def test_experts_touched_min_is_the_hand_count(both):
  """One fused step against a count by hand: the model's own routers on
  the hidden states they saw say which experts each live position chose;
  the fewest distinct experts over the three expert layers is what the
  step hands back beside the worst load."""
  from easyparallellibrary_tpu.serving.engine import _expert_stats
  model, params, _ = both
  B, C = 4, 8
  nv = jnp.asarray([8, 1, 0, 2], jnp.int32)
  tokens = jnp.asarray(np.random.RandomState(7).randint(0, 256, (B, C)),
                       jnp.int32)
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, C)
  _, _, sown = slot_step_logits(
      model, params, kv, tokens, cur, num_valid=nv, stats=True,
      reset=jnp.ones((B,), bool))
  load_max, touched_min = map(float, _expert_stats(sown))
  per_layer = [float(s["moe"]["experts_touched"][0])
               for _, s in sorted(sown.items())]
  assert len(per_layer) == 3 and touched_min == min(per_layer)
  # by hand, layer by layer: capture what each expert layer was handed
  live = np.asarray(np.arange(C)[None] < np.asarray(nv)[:, None]).reshape(-1)
  _, inter = model.apply(
      {"params": params, "cache": kv}, tokens, decode=True,
      slot_cursors=cur, num_valid=nv, reset=jnp.ones((B,), bool),
      mutable=["cache", "stats"], capture_intermediates=(
          lambda mdl, name: isinstance(mdl, nn.Module)
          and mdl.name == "norm_ff" and name == "__call__"))
  p = nn.meta.unbox(params)
  hand = []
  for i in (1, 2, 3):
    h = inter["intermediates"][f"block_{i}"]["norm_ff"]["__call__"][0]
    chosen, _ = moe_lib.noaux_tc_route(
        h.reshape(-1, 512), p[f"block_{i}"]["moe"]["router_kernel"],
        p[f"block_{i}"]["moe"]["e_score_correction_bias"], 2, 1.0, True,
        1e-6)
    hand.append(len(set(np.asarray(chosen)[live].reshape(-1).tolist())))
  assert per_layer == hand
  assert 1.0 <= load_max <= 8.0
  # 11 live positions x 2 choices cannot touch fewer than 2 experts, and
  # nothing live touches none
  assert 2 <= touched_min <= 8
  _, _, idle = slot_step_logits(
      model, params, kv, tokens, cur, num_valid=jnp.zeros((B,), jnp.int32),
      stats=True, reset=jnp.ones((B,), bool))
  assert float(_expert_stats(idle)[1]) == 0.0


# ------------------------------------------------------------ capabilities --


def test_the_decoder_is_servable(both):
  check_servable(both[0].cfg)
  check_servable(Lfm2MoeConfig())


@pytest.mark.parametrize("feature", [
    {"paged": True, "block_size": 16},
    {"paged": True, "block_size": 16, "prefix_cache": True},
    {"drafter": NgramDrafter(k=2)}, {"resilience": True}],
    ids=["paged", "prefix_cache", "speculation", "guarded_retry"])
def test_what_rolls_a_cursor_back_refuses_a_conv_window_with_the_one_message(
    both, feature):
  model, params, _ = both
  with pytest.raises(ValueError) as e:
    ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                             **feature)
  assert ROADMAP_RECURRENT_STATE in str(e.value) and "R7" in str(e.value)
  assert "recurrent-state layers (Lfm2MoeConfig) of kind conv" in str(
      e.value)


def test_a_conv_draft_model_is_refused_with_the_same_message(both):
  gpt_cfg = GPTConfig(vocab_size=256, num_layers=1, num_heads=2, d_model=16,
                      d_ff=32, max_seq_len=64)
  with pytest.raises(ValueError) as e:
    check_draft_compatible(gpt_cfg, both[0].cfg)
  assert ROADMAP_RECURRENT_STATE in str(e.value)


def test_unknown_layer_types_are_refused():
  cfg = dataclasses.replace(Lfm2MoeConfig(), layer_types=("conv", "mamba"))
  with pytest.raises(ValueError, match="layer_types"):
    cfg.layer_kinds()
