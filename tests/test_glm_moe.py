"""The sparse-expert decoder with a latent cache (models/glm_moe.py,
models/moe.py's dropless layer) against the benchmark's plain reference
(perfbench/reference/glm4_moe_lite.py), and its latent leaf and expert
layers in the continuous-batching engine.

Toy widths with every mechanism: hidden 64, 4 heads, ranks 32 / 32, head
dims 16 | 8 | 16, 8 experts top-2 beside 1 shared, 1 dense + 2 expert
layers, vocabulary 256.  float32 on both sides, matmuls at ``highest``.
Tolerances: logits are O(1-10) (weights N(0, 0.2): at width 64 the
published 0.02 gives a model that copies its input, which would test
nothing), and program and reference differ by float32 rounding in another
order of the same sums (absorbed against expanded products, sorted rows
against a loop over experts), so ``3e-4`` absolute on logits is ~30 x what
is seen (1e-5) and far below a dropped term, a bias that leaks into the
weights or a mis-routed position (>= 1e-2).  The seed is one whose router
has no near-tie between its 2nd and 3rd expert within rounding: a flip
there is a different (and equally valid) choice, which moves logits far
more than rounding; the cell's check on the chip lives with it (PERF.md).
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import GPTConfig  # noqa: E402
from easyparallellibrary_tpu.models import moe as moe_lib  # noqa: E402
from easyparallellibrary_tpu.models.blocks import rotary  # noqa: E402
from easyparallellibrary_tpu.models.glm_moe import GlmMoeConfig  # noqa: E402
from easyparallellibrary_tpu.models.layer_kinds import LATENT  # noqa: E402
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import (  # noqa: E402
    ROADMAP_LATENT_CACHE, ROADMAP_MOE_SERVING, check_draft_compatible,
    check_servable)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter  # noqa: E402
from perfbench.reference import glm4_moe_lite as ref  # noqa: E402
from perfbench.runners import epl_glm4_moe_lite as glue  # noqa: E402

KERNELS = [importlib.import_module(f"easyparallellibrary_tpu.kernels.{m}")
           for m in ("kv_write", "slot_attention", "moe_gmm")]

REF_CFG = ref.Glm4MoeLiteConfig(
    num_hidden_layers=3, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, vocab_size=256, n_positions=128,
    initializer_range=0.2, bias_std=0.05)
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


@pytest.fixture(scope="module")
def want(both, ids):
  return ref.logits(REF_CFG, both[2], ids)


def _backend_takes(monkeypatch, impl):
  for mod in KERNELS:
    monkeypatch.setattr(mod, "_backend_impl", lambda: impl)


def _chunked(model, params, ids, chunk, num_valid_of=None, **impls):
  """``ids`` [B, S] through slot mode ``chunk`` positions at a time;
  returns the logits of every position [B, S, V]."""
  B, S_ = ids.shape
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, chunk)
  outs = []
  with jax.default_matmul_precision("highest"):
    for s in range(0, S_, chunk):
      nv = jnp.full((B,), min(chunk, S_ - s), jnp.int32)
      block = jnp.zeros((B, chunk), jnp.int32).at[:, :int(nv[0])].set(
          ids[:, s:s + chunk])
      lg, kv = slot_step_logits(model, params, kv, block, cur,
                                num_valid=nv, **impls)
      cur = cur + nv
      outs.append(lg[:, :int(nv[0])])
  return jnp.concatenate(outs, 1)


# ------------------------------------------------------ model vs reference --


def test_weights_sit_where_the_reference_has_them(both):
  """The glue makes a layer at a time what ``init_params`` stacks: the
  same values (the sums of squares agree to rounding), gate and up joined
  in one stack, the router's bfloat16-rounded values held in float32."""
  _, params, rp = both
  a = float(glue.sum_of_squares(params))
  b = float(glue.sum_of_squares(rp))
  assert abs(a - b) <= 1e-5 * b
  p = nn.meta.unbox(params)
  ex = rp["moe"]["experts"]
  np.testing.assert_array_equal(
      np.asarray(p["block_2"]["moe"]["experts_gate_up"]),
      np.concatenate([np.asarray(ex["gate"][1], np.float32),
                      np.asarray(ex["up"][1], np.float32)], -1))
  np.testing.assert_array_equal(
      np.asarray(p["block_1"]["moe"]["router_kernel"]),
      np.asarray(rp["moe"]["router"][0].astype(jnp.float32)))
  assert "mlp" in p["block_0"] and "moe" not in p["block_0"]
  assert REF_CFG.param_count() == sum(
      x.size for x in jax.tree_util.tree_leaves(rp))


def test_full_forward_matches_the_reference(both, ids, want):
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": params}, ids)
  assert float(jnp.abs(want).max()) > 1.0          # not a model of zeros
  assert float((jnp.argmax(want, -1) == ids).mean()) < 0.5   # nor a copier
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("chunk", [1, 4, 8, 16])
def test_prefill_in_chunks_then_decode_matches_the_full_forward(
    both, ids, want, chunk):
  """Chunks of 16 and 8 are prefill, 1 is decode through the latent
  cache: every position's logits equal the reference's whole-sequence
  forward (logits, not tokens)."""
  model, params, _ = both
  got = _chunked(model, params, ids, chunk)
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_absorbed_attention_equals_expanded(both, ids):
  """The program's two forms of one attention, on the program's own
  weights: slot mode (latent cache, absorbed products) against the full
  forward (expanded keys and values)."""
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    expanded = model.apply({"params": params}, ids)
  absorbed = _chunked(model, params, ids, 8)
  assert float(jnp.abs(absorbed - expanded).max()) < LOGIT_TOL


def test_interpreted_kernels_equal_the_reference_lowerings(both, ids, want):
  """All three kernels in the step (one-leaf write, one-leaf attend,
  grouped matmul), interpreted, against the reference's logits."""
  model, params, _ = both
  got = _chunked(model, params, ids, 8, kv_write_impl="interpret",
                 slot_attn_impl="interpret", moe_gmm_impl="interpret")
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_rotary_is_a_rotation_by_position():
  """Norm kept, position 0 untouched, and the inner product of a rotated
  query and key depends on the DISTANCE of their positions alone."""
  x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 8))
  pos = jnp.arange(6)[None]
  y = rotary(x, pos, 1e6)
  np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                             np.linalg.norm(x, axis=-1), rtol=1e-5)
  np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                             rtol=1e-6)
  q = jnp.broadcast_to(x[:, :1], x.shape)
  a = rotary(q, pos, 100.0)
  b = rotary(q, pos + 7, 100.0)
  np.testing.assert_allclose(
      np.asarray(jnp.sum(a[0, 1] * a[0, 4], -1)),
      np.asarray(jnp.sum(b[0, 1] * b[0, 4], -1)), rtol=1e-4)


# ------------------------------------------------------------- the router --


def _router_case(bias):
  r = np.random.RandomState(2)
  x = jnp.asarray(r.standard_normal((16, 64)), jnp.float32)
  w = jnp.asarray(r.standard_normal((64, 8)) * 0.3, jnp.float32)
  return x, w, jnp.asarray(bias, jnp.float32)


@pytest.mark.parametrize("norm", [True, False], ids=["normalised", "raw"])
def test_router_chooses_with_the_bias_and_weighs_without_it(norm):
  bias = np.zeros(8, np.float32)
  bias[5] = 10.0                      # expert 5 is chosen by every position
  x, w, b = _router_case(bias)
  chosen, weights = moe_lib.noaux_tc_route(x, w, b, 2, 1.8, norm)
  s = np.asarray(jax.nn.sigmoid(x @ w))
  assert (np.asarray(chosen) == 5).any(axis=1).all()
  # the other choice is the unbiased best of the rest
  rest = np.where(np.arange(8)[None] == 5, -1.0, s)
  np.testing.assert_array_equal(
      np.sort(np.asarray(chosen), 1),
      np.sort(np.stack([np.full(16, 5), rest.argmax(1)], 1), 1))
  picked = np.take_along_axis(s, np.asarray(chosen), 1)
  want = 1.8 * (picked / (picked.sum(1, keepdims=True) + 1e-20)
                if norm else picked)
  np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-6)
  if norm:
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.8, rtol=1e-6)


def test_a_bias_changes_the_choice_and_not_the_scores():
  x, w, zero = _router_case(np.zeros(8))
  plain, _ = moe_lib.noaux_tc_route(x, w, zero, 2, 1.8)
  bias = np.zeros(8, np.float32)
  worst = int(np.asarray(jax.nn.sigmoid(x @ w)).mean(0).argmin())
  bias[worst] = 0.6
  moved, weights = moe_lib.noaux_tc_route(x, w, jnp.asarray(bias), 2, 1.8)
  assert (np.asarray(moved) != np.asarray(plain)).any()
  s = np.asarray(jax.nn.sigmoid(x @ w))
  picked = np.take_along_axis(s, np.asarray(moved), 1)
  np.testing.assert_allclose(
      np.asarray(weights), 1.8 * picked / picked.sum(1, keepdims=True),
      rtol=1e-6)


def test_router_runs_in_float32_whatever_the_compute_dtype():
  x, w, b = _router_case(np.zeros(8))
  c32, w32 = moe_lib.noaux_tc_route(x, w, b, 2, 1.8)
  c16, w16 = moe_lib.noaux_tc_route(x.astype(jnp.bfloat16), w, b, 2, 1.8)
  assert w16.dtype == jnp.float32
  # the bfloat16 INPUT is what it is; the arithmetic on it is float32
  cx, wx = moe_lib.noaux_tc_route(
      x.astype(jnp.bfloat16).astype(jnp.float32), w, b, 2, 1.8)
  np.testing.assert_array_equal(np.asarray(c16), np.asarray(cx))
  np.testing.assert_array_equal(np.asarray(w16), np.asarray(wx))


# ---------------------------------------------------------- droplessness --


def _forced(rp, experts):
  """Reference weights whose selection bias forces every position to
  ``experts`` in every expert layer."""
  bias = np.zeros_like(np.asarray(rp["moe"]["bias"]))
  bias[:, list(experts)] = 50.0
  out = dict(rp)
  out["moe"] = dict(rp["moe"], bias=jnp.asarray(bias))
  return out


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_every_position_on_one_pair_of_experts_is_not_dropped(both, ids,
                                                              impl):
  """The worst imbalance there is: all 3 x 40 positions go to experts 2
  and 6 (a capacity-bounded layer would drop most of them).  The logits
  equal the reference's, which computes every chosen expert per token."""
  model, params, rp = both
  forced = _forced(rp, (2, 6))
  p = nn.meta.unbox(params)
  p = jax.tree_util.tree_map(lambda x: x, p)
  for i, j in ((1, 0), (2, 1)):
    p[f"block_{i}"]["moe"]["e_score_correction_bias"] = forced["moe"][
        "bias"][j]
  want = ref.logits(REF_CFG, forced, ids)
  got = _chunked(model, p, ids, 8, moe_gmm_impl=impl)
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL
  x = jnp.asarray(np.random.RandomState(0).standard_normal((24, 64)),
                  jnp.float32)
  chosen, _ = moe_lib.noaux_tc_route(
      x, p["block_1"]["moe"]["router_kernel"],
      p["block_1"]["moe"]["e_score_correction_bias"], 2, 1.8)
  _, sizes = moe_lib.sort_by_expert(chosen, None, 8)
  assert np.asarray(sizes).tolist() == [0, 0, 24, 0, 0, 0, 24, 0]


@pytest.mark.parametrize("num_valid", [[8, 3, 0, 1], [0, 0, 0, 0],
                                       [8, 8, 8, 8]],
                         ids=["ragged", "all_idle", "all_live"])
def test_dead_positions_are_routed_nowhere(num_valid):
  """Group sizes sum to live positions x top_k; the sorted rows of the
  live assignments come first, in expert order."""
  r = np.random.RandomState(3)
  chosen = jnp.asarray(r.randint(0, 8, (32, 2)), jnp.int32)
  nv = np.asarray(num_valid)
  live = (np.arange(8)[None] < nv[:, None]).reshape(-1)
  order, sizes = moe_lib.sort_by_expert(chosen, jnp.asarray(live), 8)
  assert int(sizes.sum()) == int(live.sum()) * 2
  flat = np.where(np.repeat(live, 2), np.asarray(chosen).reshape(-1), 8)
  assert (np.diff(flat[np.asarray(order)]) >= 0).all()
  np.testing.assert_array_equal(np.asarray(sizes),
                                np.bincount(flat, minlength=9)[:8])


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_a_dead_position_cannot_change_a_live_one(both, impl):
  """Two steps that differ ONLY in the tokens beyond ``num_valid`` and in
  an idle slot's: the live positions' logits are bit-identical, and so is
  the expert load the step reports (dead positions count nowhere)."""
  model, params, _ = both
  B, C = 4, 8
  nv = jnp.asarray([8, 3, 0, 1], jnp.int32)
  r = np.random.RandomState(5)
  a = r.randint(0, 256, (B, C))
  b = a.copy()
  dead = np.arange(C)[None] >= np.asarray(nv)[:, None]
  b[dead] = r.randint(0, 256, int(dead.sum()))
  assert (a != b).any()
  outs = []
  for tokens in (a, b):
    kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, C)
    with jax.default_matmul_precision("highest"):
      lg, _, stats = slot_step_logits(
          model, params, kv, jnp.asarray(tokens, jnp.int32), cur,
          num_valid=nv, stats=True, moe_gmm_impl=impl,
          kv_write_impl=impl, slot_attn_impl=impl)
    outs.append((np.asarray(lg)[~dead], jax.tree_util.tree_leaves(stats)))
  np.testing.assert_array_equal(outs[0][0], outs[1][0])
  # a load and a count of touched experts an expert layer
  assert len(outs[0][1]) == 4
  np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                np.asarray(outs[1][1]))
  assert all(1.0 <= float(x) <= 8.0 for x in outs[0][1])


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
  beside decodes beside idle slots in one step): every served token is
  the reference's own next token for that request alone."""
  model, params, rp = both
  eng, out = _serve(model, params)
  assert kv_lib.resolved(eng.lowerings) == dict.fromkeys(
      ("kv_write_impl", "slot_attn_impl", "moe_gmm_impl"), "reference")
  for req in _requests():
    stream = np.asarray(out[req.uid])
    n = len(req.prompt)
    assert len(stream) == n + req.max_new_tokens
    lg = ref.logits(REF_CFG, rp, jnp.asarray(stream[None]))[0]
    served = np.asarray(lg)[np.arange(n - 1, len(stream) - 1), stream[n:]]
    best = np.asarray(lg.max(-1))[n - 1:len(stream) - 1]
    assert float((best - served).max()) < LOGIT_TOL


def test_engine_commits_the_same_under_the_interpreted_kernels(monkeypatch,
                                                               both):
  model, params, _ = both
  _, want = _serve(model, params)
  _backend_takes(monkeypatch, "interpret")
  eng, got = _serve(model, params)
  # The write and the attend take the toy leaf; the grouped matmul's rule
  # declines a contraction of 64 (not whole lane tiles) and keeps
  # ``ragged_dot``: the kernel inside a step is the test above's.
  assert kv_lib.resolved(eng.lowerings) == {
      "kv_write_impl": "interpret", "slot_attn_impl": "interpret",
      "moe_gmm_impl": "reference"}
  for uid in want:
    np.testing.assert_array_equal(got[uid], want[uid])


def test_an_engine_frees_its_cache_when_its_owner_lets_go(both):
  """No reference cycle through the engine's own hooks: dropping the last
  outside reference frees the cache at once, the collector off.  (The
  cell's run hands the chip to the float32 reference right after the
  window; a cache that waited for the collector left it 1 GB short.)"""
  import gc
  import weakref
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


def test_the_cache_is_one_latent_leaf_a_layer(both):
  cfg = both[0].cfg
  assert cfg.layer_kinds() == (LATENT,) * 3
  assert kv_lib.has_latent_cache(cfg) and not kv_lib.has_recurrent_state(cfg)
  leaves = kv_lib.cache_leaves(cfg, 5, 8)
  for i in range(3):
    assert set(leaves[f"block_{i}"]) == {"latent"}
    leaf = leaves[f"block_{i}"]["latent"]["cached_latent"]
    assert leaf.shape == (5, 128 + 8, 1, 32 + 8)
  layout = kv_lib.cache_layout(cfg, 5, 8)
  assert layout["latent_leaves"] == 3 and layout["kv_leaves"] == 0
  assert layout["latent_bytes"] == 3 * 5 * 136 * 40 * 4 == kv_lib.cache_bytes(
      cfg, 5, 8)
  # the cell's leaf: 576 values a position, 1152 B in bfloat16
  real = kv_lib.cache_leaves(GlmMoeConfig(num_layers=1), 96, 8)
  assert real["block_0"]["latent"]["cached_latent"].shape == (
      96, 4104, 1, 576)


def test_the_engine_says_what_it_holds_and_counts_what_it_routed(both):
  """Trace metadata ``serving/moe_gmm_impl`` and ``serving/cache_layout``
  beside the write's and the attend's; counters ``serving/
  routed_positions`` (the plan's live positions) and ``serving/
  expert_load_max`` beside ``serving/active_slots``; both in the stats'
  summary."""
  model, params, _ = both
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  stats = ServingStats()
  try:
    eng, out = _serve(model, params, stats=stats)
    events = tracer.events()
  finally:
    trace_lib.install(None)
  meta = {ev["name"]: ev["args"] for ev in events if ev["ph"] == "M"
          and ev["name"].startswith("serving/")}
  assert meta["serving/moe_gmm_impl"] == {"impl": "reference"}
  assert meta["serving/kv_write_impl"] == {"impl": "reference"}
  assert meta["serving/slot_attn_impl"] == {"impl": "reference"}
  assert meta["serving/cache_layout"] == eng.cache_layout
  assert meta["serving/cache_layout"]["latent_leaves"] == 3
  assert meta["serving/cache_layout"]["kv_order"] == "positions"
  counters = lambda name: [ev["args"]["value"] for ev in events
                           if ev["ph"] == "C" and ev["name"] == name]
  routed = counters("serving/routed_positions")
  load = counters("serving/expert_load_max")
  assert len(routed) == len(load) == len(counters("serving/active_slots")) > 0
  # every prompt token and every generated token but a request's last is
  # fed (and routed) exactly once
  assert sum(routed) == sum(len(r.prompt) + r.max_new_tokens - 1
                            for r in _requests())
  assert all(1.0 <= x <= 8.0 for x in load)
  summary = stats.summary()
  assert summary["routed_positions_per_step"] == pytest.approx(
      sum(routed) / len(routed))
  assert summary["expert_load_max_mean"] == pytest.approx(
      sum(load) / len(load), rel=1e-5)
  assert eng._capture_context()["serving"]["moe_gmm_impl"] == "reference"


# ------------------------------------------------------------ capabilities --


def test_the_new_decoders_experts_are_servable_and_gpts_still_are_not(both):
  check_servable(both[0].cfg)
  check_servable(GlmMoeConfig())
  with pytest.raises(ValueError) as e:
    check_servable(GPTConfig(vocab_size=64, num_layers=2, num_heads=2,
                             d_model=16, d_ff=32, max_seq_len=32,
                             num_experts=4))
  assert ROADMAP_MOE_SERVING in str(e.value) and "R4" in str(e.value)


@pytest.mark.parametrize("feature", [
    {"paged": True}, {"prefix_cache": True}, {"drafter": NgramDrafter(k=2)},
    {"resilience": True}], ids=["paged", "prefix_cache", "speculation",
                                "guarded_retry"])
def test_rollback_features_refuse_a_latent_leaf_with_one_message(both,
                                                                 feature):
  model, params, _ = both
  with pytest.raises(ValueError) as e:
    ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                             **feature)
  assert ROADMAP_LATENT_CACHE in str(e.value) and "R5" in str(e.value)


def test_a_latent_draft_model_is_refused_with_the_same_message(both):
  gpt_cfg = GPTConfig(vocab_size=256, num_layers=1, num_heads=2, d_model=16,
                      d_ff=32, max_seq_len=64)
  with pytest.raises(ValueError) as e:
    check_draft_compatible(gpt_cfg, both[0].cfg)
  assert ROADMAP_LATENT_CACHE in str(e.value)


def test_engine_on_a_mesh_of_chips_takes_the_reference_lowerings(
    monkeypatch, both):
  """The latent leaf is replicated on a mesh (one head) and the SPMD
  partitioner cannot split a Mosaic call: every rule resolves the
  reference there, and the kernel on one chip."""
  _backend_takes(monkeypatch, "pallas")
  cfg = dataclasses.replace(GlmMoeConfig(), num_layers=2)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  for rule in (kv_lib.kv_write_impl, kv_lib.slot_attn_impl,
               kv_lib.moe_gmm_impl):
    assert rule(cfg, 96, 8, mesh) == "reference", rule.__name__
    assert rule(cfg, 96, 8, None) == "pallas", rule.__name__
  shardings, _ = kv_lib.kv_cache_shardings(cfg, mesh)
  assert shardings["block_0"]["latent"]["cached_latent"].spec == \
      jax.sharding.PartitionSpec()
  assert kv_lib.moe_gmm_impl(GPTConfig(), 96, 8, None) is None
