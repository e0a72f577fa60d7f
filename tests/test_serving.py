"""Serving subsystem: continuous-batching engine, slot cache, scheduler.

The exactness contract under test: the fused prefill+decode engine is a
pure REBATCHING of the legacy ``generate(use_cache=True)`` path — greedy
token ids are bit-identical per request, no matter when a request was
admitted, which slot served it, or who occupied that slot before
(ISSUE 3 acceptance).  ``generate`` stays the oracle.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate, sample_logits
from easyparallellibrary_tpu.profiler import ServingStats, percentile
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, FCFSScheduler, Request, SlotAllocator,
    allocate_kv_cache, cache_length, sample_token_slots)

TINY = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)


def _model_and_params(cfg=TINY, seed=0):
  model = GPT(cfg)
  params = model.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, 4), jnp.int32))["params"]
  return model, params


def _prompts(lengths, vocab=64, seed=0):
  r = np.random.RandomState(seed)
  return [r.randint(0, vocab, (n,)).astype(np.int32) for n in lengths]


def _oracle(model, params, prompt, max_new):
  return np.asarray(
      generate(model, params, jnp.asarray(prompt)[None], max_new))[0]


# ---------------------------------------------------------------- exactness


@pytest.mark.quick
def test_engine_greedy_exact_vs_generate_staggered():
  """Greedy continuous batching is bit-exact vs generate(use_cache=True)
  per request — including requests admitted at different iterations and
  slots reused across retirements (num_slots < num requests)."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 9, 1, 6, 2))
  max_new = (6, 7, 8, 4, 5, 9)
  eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                 prefill_chunk=4)
  # The whole serving drive runs under the device->host transfer guard:
  # the engine's ONE designated per-step fetch is explicit
  # (jax.device_get), so any IMPLICIT sync creeping into the hot loop —
  # a float()/np.asarray on a device value — fails here at runtime,
  # the complement of epl-lint's static host-sync rule
  # (docs/static_analysis.md).
  with jax.transfer_guard_device_to_host("disallow"):
    for i in range(3):
      eng.submit(Request(uid=i, prompt=prompts[i],
                         max_new_tokens=max_new[i]))
    out = {}
    for _ in range(2):  # second wave joins a mid-flight batch
      for fin in eng.step():
        out[fin.uid] = fin.tokens
    for i in range(3, len(prompts)):
      eng.submit(Request(uid=i, prompt=prompts[i],
                         max_new_tokens=max_new[i]))
    out.update(eng.run())
  assert sorted(out) == list(range(len(prompts)))
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(
        out[i], _oracle(model, params, p, max_new[i]), err_msg=f"req {i}")


@pytest.mark.quick
def test_engine_tp2_exact_vs_dense_generate():
  """The engine on a TP=2 virtual mesh (heads sharded over `model`, slot
  cache allocated sharded) reproduces the dense single-program oracle's
  greedy ids exactly."""
  import flax.linen as nn
  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  cfg = GPTConfig(**{**TINY.__dict__, "tensor_parallel": True})
  model = GPT(cfg)
  prompts = _prompts((4, 7, 2), seed=1)

  def init_fn(rng):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(rng, jnp.asarray(prompts[0])[None])["params"],
        tx=optax.sgd(0.1))

  state, _ = create_sharded_train_state(init_fn, mesh,
                                        jax.random.PRNGKey(5))
  eng = ContinuousBatchingEngine(model, state.params, mesh=mesh,
                                 num_slots=2, prefill_chunk=4)
  # Sync-free hot loop on the TP mesh too (see the staggered test).
  with jax.transfer_guard_device_to_host("disallow"):
    for i, p in enumerate(prompts):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=5))
    out = eng.run()

  dense = GPT(TINY)
  host_params = jax.tree_util.tree_map(np.asarray,
                                       nn.meta.unbox(state.params))
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(
        out[i], _oracle(dense, host_params, p, 5), err_msg=f"req {i}")


@pytest.mark.quick
def test_slot_reuse_no_stale_kv_leakage():
  """Retire + readmit reuses the slot with no stale-KV leakage: a SHORT
  request served after a LONG one in the same (only) slot matches its
  from-scratch oracle bit-exactly — the long request's K/V tail is still
  physically in the cache but must never be attendable."""
  epl.init()
  model, params = _model_and_params(seed=2)
  long_p, short_p = _prompts((12, 3), seed=3)
  eng = ContinuousBatchingEngine(model, params, num_slots=1,
                                 prefill_chunk=4)
  # Slot reuse must stay sync-free as well (see the staggered test).
  with jax.transfer_guard_device_to_host("disallow"):
    eng.submit(Request(uid="long", prompt=long_p, max_new_tokens=10))
    out = eng.run()
    eng.submit(Request(uid="short", prompt=short_p, max_new_tokens=6))
    out.update(eng.run())
  np.testing.assert_array_equal(out["long"],
                                _oracle(model, params, long_p, 10))
  np.testing.assert_array_equal(out["short"],
                                _oracle(model, params, short_p, 6))


def test_stop_token_retires_early():
  """A request retires at its stop token (included in the output) —
  output equals the unconstrained greedy decode truncated at the stop
  token's first occurrence."""
  epl.init()
  model, params = _model_and_params()
  (prompt,) = _prompts((5,))
  plen = len(prompt)
  ref = _oracle(model, params, prompt, 4)
  gen_part = list(ref[plen:])
  stop = gen_part[1]  # appears at generated index <= 1
  cut = gen_part.index(stop)  # first occurrence decides retirement
  eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                 prefill_chunk=4)
  eng.submit(Request(uid="s", prompt=prompt, max_new_tokens=20,
                     stop_token=int(stop)))
  fins = []
  while eng.has_work:
    fins.extend(eng.step())
  assert len(fins) == 1 and fins[0].finish_reason == "stop_token"
  np.testing.assert_array_equal(fins[0].tokens, ref[:plen + cut + 1])


# --------------------------------------------------------------- throughput


@pytest.mark.quick
def test_continuous_batching_beats_sequential_static_batch():
  """ISSUE 3 acceptance: with staggered arrivals and skewed decode
  lengths, continuous batching serves the workload in fewer device steps
  than sequential static-batch generate() calls — each static batch runs
  EVERY request to its batch's longest horizon, while the engine retires
  short requests and backfills their slots from the queue every
  iteration.  Steps are counted, not timed: a CPU clock is not a rate.
  Exactness rides along."""
  epl.init()
  cfg = GPTConfig(vocab_size=256, num_layers=4, num_heads=8, d_model=128,
                  d_ff=512, max_seq_len=128, dtype=jnp.float32)
  model, params = _model_and_params(cfg)
  B, plen, waves = 8, 8, 4
  wave_new = [48] + [8] * (B - 1)   # skew: one long request per wave
  max_new = wave_new * waves
  prompts = _prompts([plen] * (B * waves), vocab=256, seed=4)

  horizon = max(wave_new)
  gen = jax.jit(lambda p, ids: generate(model, p, ids, horizon))
  base_out = [gen(params, jnp.asarray(np.stack(prompts[w * B:(w + 1) * B])))
              for w in range(waves)]
  base_steps = waves * horizon      # one batch-wide forward per new token

  eng = ContinuousBatchingEngine(model, params, num_slots=B,
                                 prefill_chunk=1)
  steps = 0
  out = {}
  for w in range(waves):          # staggered: each wave joins mid-flight
    for i in range(w * B, (w + 1) * B):
      eng.submit(Request(uid=i, prompt=prompts[i],
                         max_new_tokens=max_new[i]))
    out.update({f.uid: f.tokens for f in eng.step()})
    steps += 1
  while eng.has_work:
    out.update({f.uid: f.tokens for f in eng.step()})
    steps += 1

  # Engine output == the baseline's own tokens truncated to each
  # request's budget.
  for i in range(B * waves):
    ref = np.asarray(base_out[i // B][i % B])[:plen + max_new[i]]
    np.testing.assert_array_equal(out[i], ref, err_msg=f"req {i}")
  assert steps < base_steps, (
      f"continuous batching took {steps} device steps, sequential "
      f"static batches {base_steps}")


# ----------------------------------------------------------------- sampling


def test_per_request_rng_streams_slot_independent():
  """A request's sample stream depends only on its seed and token index
  — not on which slot or iteration serves it: the same workload sampled
  under different slot counts (different schedules) yields identical
  tokens, different seeds yield different tokens."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 5, 3), seed=6)
  prompts[1] = prompts[0].copy()  # identical prompt for the seed test

  def run(num_slots, seeds):
    eng = ContinuousBatchingEngine(model, params, num_slots=num_slots,
                                   prefill_chunk=4)
    for i, p in enumerate(prompts):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=8,
                         temperature=0.9, top_k=12, seed=seeds[i]))
    return eng.run()

  a = run(1, seeds=[7, 7, 9])
  b = run(3, seeds=[7, 7, 9])
  for i in range(len(prompts)):
    np.testing.assert_array_equal(a[i], b[i], err_msg=f"req {i}")
  # Same prompt + same seed -> same stream; different seed -> differs.
  np.testing.assert_array_equal(a[0][5:], a[1][5:])
  c = run(3, seeds=[7, 8, 9])
  assert not np.array_equal(a[1][5:], c[1][5:])


def test_sample_token_slots_matches_sample_logits_semantics():
  """The traced-parameter sampler mirrors sample_logits: greedy at
  temperature<=0 regardless of filters, top-k support restriction, and
  tiny top-p collapsing to argmax."""
  r = np.random.RandomState(0)
  logits = jnp.asarray(r.randn(16, 32), jnp.float32)
  keys = np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(16)])
  greedy = np.asarray(sample_logits(logits, jax.random.PRNGKey(0),
                                    temperature=0.0))
  zeros, ones = np.zeros(16, np.float32), np.ones(16, np.float32)

  out = sample_token_slots(logits, keys, jnp.zeros(16),
                           jnp.full(16, 5, jnp.int32), jnp.asarray(ones))
  np.testing.assert_array_equal(np.asarray(out), greedy)
  # tiny top_p keeps only the top token at any temperature.
  out = sample_token_slots(logits, keys, jnp.full(16, 1.5),
                           jnp.zeros(16, jnp.int32),
                           jnp.full(16, 1e-6, jnp.float32))
  np.testing.assert_array_equal(np.asarray(out), greedy)
  # top_k=1 collapses to greedy; k=0 leaves full support.
  out = sample_token_slots(logits, keys, jnp.full(16, 2.0),
                           jnp.ones(16, jnp.int32), jnp.asarray(ones))
  np.testing.assert_array_equal(np.asarray(out), greedy)
  k = 4
  topk_sets = np.asarray(jax.lax.top_k(logits, k)[1])
  out = np.asarray(sample_token_slots(
      logits, keys, jnp.full(16, 1.0), jnp.full(16, k, jnp.int32),
      jnp.asarray(ones)))
  assert all(out[i] in topk_sets[i] for i in range(16))
  # Per-slot parameters really are per-slot: slot 0 greedy, slot 1 hot.
  temps = jnp.asarray([0.0] + [5.0] * 15)
  out = np.asarray(sample_token_slots(logits, keys, temps,
                                      jnp.zeros(16, jnp.int32),
                                      jnp.asarray(ones)))
  assert out[0] == greedy[0]


# ---------------------------------------------------------------- scheduler


def test_scheduler_admission_budget_max_batch_fcfs():
  """Host-only: FCFS admission gated by free slots, max_batch and the
  per-step prefill-token budget; budget-starved prefills resume on later
  steps; decode tokens are never budget-gated."""
  sched = FCFSScheduler(num_slots=4, prefill_chunk=4, max_seq_len=64,
                        prefill_token_budget=8, max_batch=3)
  for i in range(4):
    sched.submit(Request(uid=i, prompt=np.arange(1, 7, dtype=np.int32),
                         max_new_tokens=3))
  plan = sched.plan_step()
  # Budget 8 = two first-chunks of 4: requests 0 and 1 admitted;
  # max_batch=3 would allow a third but the budget does not.
  assert plan.active_slots == 2
  assert plan.prefill_tokens == 8 and plan.decode_tokens == 0
  assert list(plan.num_valid[:2]) == [4, 4] and plan.reset[:2].all()
  sched.commit(np.zeros(4, np.int32))
  plan = sched.plan_step()
  # Remaining 2-token prefills (0,1) cost 4; budget admits request 2
  # (first chunk 4); max_batch=3 blocks request 3.
  assert plan.active_slots == 3
  assert plan.prefill_tokens == 8
  sched.commit(np.zeros(4, np.int32))
  plan = sched.plan_step()
  # 0 and 1 finished prefill last step -> decoding now (not budgeted).
  assert plan.decode_tokens == 2
  assert sched.pending and sched.pending[0].uid == 3  # still FCFS-queued


def test_scheduler_requires_plan_before_commit_and_validates():
  sched = FCFSScheduler(num_slots=1, prefill_chunk=2, max_seq_len=8)
  with pytest.raises(RuntimeError):
    sched.commit(np.zeros(1, np.int32))
  with pytest.raises(ValueError, match="non-empty"):
    sched.submit(Request(uid=0, prompt=np.zeros(0, np.int32),
                         max_new_tokens=1))
  with pytest.raises(ValueError, match="max_seq_len"):
    sched.submit(Request(uid=0, prompt=np.zeros(6, np.int32),
                         max_new_tokens=4))
  with pytest.raises(ValueError, match="top_p"):
    sched.submit(Request(uid=0, prompt=np.zeros(2, np.int32),
                         max_new_tokens=1, top_p=0.0))
  assert sched.plan_step() is None  # idle


def test_slot_allocator_free_list():
  alloc = SlotAllocator(3)
  assert [alloc.alloc() for _ in range(3)] == [0, 1, 2]
  assert alloc.alloc() is None
  alloc.free(1)
  assert alloc.num_free == 1 and alloc.alloc() == 1
  with pytest.raises(ValueError, match="double free"):
    alloc.free(2), alloc.free(2)


def test_kv_cache_shapes_and_config_validation():
  kv, cursors = allocate_kv_cache(TINY, num_slots=3, chunk=4)
  Lc = cache_length(TINY, 4)
  assert Lc == TINY.max_seq_len + 4
  assert set(kv) == {f"block_{i}" for i in range(TINY.num_layers)}
  leaf = kv["block_0"]["attn"]["cached_key"]
  assert leaf.shape == (3, Lc, TINY.num_heads,
                        TINY.d_model // TINY.num_heads)
  assert cursors.shape == (3,) and cursors.dtype == jnp.int32
  with pytest.raises(ValueError, match="prefill_token_budget"):
    epl.Config({"serving.prefill_token_budget": 2,
                "serving.prefill_chunk": 4})
  with pytest.raises(ValueError, match="num_slots"):
    epl.Config({"serving.num_slots": 0})


# ------------------------------------------------------------------ metrics


def test_serving_stats_rollup():
  t = [0.0]
  clock = lambda: t[0]
  stats = ServingStats(clock=clock)
  stats.note_submitted("a")
  t[0] = 1.0
  stats.note_admitted("a")
  t[0] = 2.0
  stats.note_first_token("a")
  t[0] = 5.0
  stats.note_finished("a", new_tokens=4)
  stats.note_step(active_slots=2, num_slots=4, prefill_tokens=8,
                  decode_tokens=2, step_time_s=0.5)
  stats.note_step(active_slots=4, num_slots=4, prefill_tokens=0,
                  decode_tokens=4, step_time_s=0.5)
  s = stats.summary()
  assert s["finished_requests"] == 1 and s["generated_tokens"] == 4
  assert s["ttft_p50_s"] == pytest.approx(2.0)   # submit 0 -> first at 2
  assert s["itl_mean_s"] == pytest.approx(1.0)   # (5-2)/(4-1)
  assert s["slot_occupancy_mean"] == pytest.approx(0.75)
  assert s["tokens_per_s"] == pytest.approx(4.0)
  assert percentile([1.0, 2.0, 3.0], 50) == 2.0
  assert percentile([], 99) == 0.0


def test_serving_stats_finished_limit_windows_traces():
  """serving.finished_limit: finished per-request traces evict
  oldest-first (latency percentiles become a sliding window) while
  aggregate counters keep the full history and in-flight traces are
  never evicted."""
  t = [0.0]
  stats = ServingStats(clock=lambda: t[0], finished_limit=2)
  for i, uid in enumerate(["a", "b", "c"]):
    t[0] = float(i)
    stats.note_submitted(uid)
    stats.note_first_token(uid)
    t[0] = float(i) + 0.5
    stats.note_finished(uid, new_tokens=1)
  stats.note_submitted("inflight")
  assert stats.finished_requests == 3          # aggregates: full history
  assert set(stats._req) == {"b", "c", "inflight"}  # traces: windowed
  cfg = __import__("easyparallellibrary_tpu").Config
  with pytest.raises(ValueError, match="finished_limit"):
    cfg({"serving": {"finished_limit": -1}})


# ------------------------------------------------------- pipeline fallback


def test_pp_generate_fallback_logged_once(caplog):
  """Satellite: generate() on a pipelined config logs the full-forward
  fallback exactly once per process (same latch pattern as the smap
  advisory), saying why."""
  from easyparallellibrary_tpu.models import gpt as gpt_mod
  epl.init()
  cfg = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                  d_ff=64, max_seq_len=16, dtype=jnp.float32,
                  pipeline_stages=2, pipeline_debug_sequential=True)
  model = GPT(cfg)
  prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
  params = model.init(jax.random.PRNGKey(0), prompt)["params"]
  from easyparallellibrary_tpu.utils.logging import get_logger
  logger = get_logger()
  old_propagate = logger.propagate
  gpt_mod._PP_GENERATE_FALLBACK_LOGGED[0] = False
  try:
    logger.propagate = True  # the repo logger is handler-only by default
    with caplog.at_level(logging.WARNING, logger=logger.name):
      generate(model, params, prompt, 2)
      generate(model, params, prompt, 2)
    hits = [r for r in caplog.records
            if "full-forward-per-token" in r.getMessage()]
    assert len(hits) == 1
    assert "pipeline_stages" in hits[0].getMessage()
  finally:
    logger.propagate = old_propagate
    gpt_mod._PP_GENERATE_FALLBACK_LOGGED[0] = False


# ------------------------------------------------------------ restore_params


def test_restore_params_from_trainstate_checkpoint(tmp_path):
  """Satellite: params-only restore from a FULL TrainState checkpoint —
  no optimizer/sentinel leaves touched — with the PR-2 fallback chain
  (corrupt newest checkpoint is quarantined and the previous restores)."""
  from easyparallellibrary_tpu.parallel import TrainState
  from easyparallellibrary_tpu.runtime.saver import (
      restore_params, save_checkpoint)
  from easyparallellibrary_tpu.testing.chaos import corrupt_shard
  epl.init()
  model, params = _model_and_params(seed=8)
  state = TrainState.create(apply_fn=model.apply, params=params,
                            tx=optax.adam(1e-3))
  root = str(tmp_path / "ckpt")
  save_checkpoint(root, state, step=3)
  p2 = jax.tree_util.tree_map(lambda x: x + 1.0, params)
  state2 = state.replace(params=p2)
  newest = save_checkpoint(root, state2, step=5)

  restored, step = restore_params(root, target=params)
  assert step == 5
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                 np.asarray(b)),
      restored, p2)
  # Raw-dict mode returns ONLY params leaves, prefix stripped.
  raw, _ = restore_params(root)
  assert all(not k.startswith(("opt_state", "step")) for k in raw)
  assert any(k.startswith("wte") for k in raw)

  # Newest checkpoint rots -> fallback chain lands on step 3.
  corrupt_shard(newest, shard=0, mode="flip")
  restored3, step3 = restore_params(root, target=params)
  assert step3 == 3
  jax.tree_util.tree_map(
      lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                 np.asarray(b)),
      restored3, params)
  # The restored params drive the serving engine directly.
  (prompt,) = _prompts((4,), seed=9)
  eng = ContinuousBatchingEngine(model, restored3, num_slots=1,
                                 prefill_chunk=4)
  eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=3))
  out = eng.run()
  np.testing.assert_array_equal(out[0], _oracle(model, params, prompt, 3))


def test_engine_rejects_pipelined_and_moe_configs():
  epl.init()
  model_pp = GPT(GPTConfig(**{**TINY.__dict__, "pipeline_stages": 2}))
  with pytest.raises(ValueError, match="pipeline"):
    ContinuousBatchingEngine(model_pp, {}, num_slots=1)
  model_moe = GPT(GPTConfig(**{**TINY.__dict__, "num_experts": 2}))
  with pytest.raises(ValueError, match="MoE"):
    ContinuousBatchingEngine(model_moe, {}, num_slots=1)
