"""Speculative decoding: drafters, batched verification, accept/rollback.

The contracts under test (ISSUE 4 acceptance):

* greedy speculative output is BIT-EXACT vs non-speculative
  ``generate(use_cache=True)`` per request — drafting/verification is
  pure rebatching, including staggered admission on a TP=2 mesh, slot
  reuse, and stop tokens that appear mid-draft;
* sampled speculative output preserves the sampling DISTRIBUTION
  (rejection-sampling acceptance), and requests served without drafts
  keep the non-speculative engine's bitstream exactly;
* the fused speculative step compiles ONCE — draft lengths, joins and
  leaves are data, not shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate
from easyparallellibrary_tpu.models.slot_core import slot_step_logits
from easyparallellibrary_tpu.profiler import ServingStats, percentile
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, DraftModelDrafter, NgramDrafter, Request,
    allocate_kv_cache, check_draft_compatible, check_servable,
    ngram_propose, sample_token_slots, verify_tokens)

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


@pytest.mark.slow
def test_spec_ngram_greedy_exact_staggered_slot_reuse():
  """Greedy speculation with the n-gram drafter is bit-exact vs
  generate(use_cache=True) per request — staggered admission, slot
  reuse after retirement (num_slots < num requests) — and the fused
  speculative step compiles exactly once across all of it.  (slow: six
  oracle shapes = six generate() compiles; the quick TP=2 test carries
  the staggered contract in tier-1.)"""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 9, 1, 6, 2))
  max_new = (6, 7, 8, 4, 5, 9)
  eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                 prefill_chunk=4,
                                 drafter=NgramDrafter(k=3, ngram_max=3))
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
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(
        out[i], _oracle(model, params, p, max_new[i]), err_msg=f"req {i}")
  # Zero recompiles: joins/leaves and varying per-slot draft lengths
  # (n-gram proposals come and go) are data, not shapes.
  assert eng._step_fn._cache_size() == 1


@pytest.mark.quick
def test_spec_tp2_greedy_exact_staggered_vs_dense():
  """ISSUE 4 acceptance: speculative greedy decoding on a TP=2 virtual
  mesh (heads-sharded slot cache) with staggered admission — plus a
  stop-token retirement — is bit-exact per request vs the dense
  single-program NON-speculative engine (itself quick-pinned to
  generate(use_cache=True) in tests/test_serving.py), with the
  speculative step compiled once."""
  import flax.linen as nn
  import optax
  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  cfg = GPTConfig(**{**TINY.__dict__, "tensor_parallel": True})
  model = GPT(cfg)
  prompts = _prompts((4, 7, 2, 5), seed=1)
  max_new = (6, 6, 6, 8)

  def init_fn(rng):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(rng, jnp.asarray(prompts[0])[None])["params"],
        tx=optax.sgd(0.1))

  state, _ = create_sharded_train_state(init_fn, mesh,
                                        jax.random.PRNGKey(5))
  dense = GPT(TINY)
  host_params = jax.tree_util.tree_map(np.asarray,
                                       nn.meta.unbox(state.params))
  # Dense non-speculative oracle engine: one compiled step for every
  # request shape (vs one generate() compile per shape).
  oracle_eng = ContinuousBatchingEngine(dense, host_params, num_slots=4,
                                        prefill_chunk=4)
  for i, p in enumerate(prompts):
    oracle_eng.submit(Request(uid=i, prompt=p,
                              max_new_tokens=max_new[i]))
  ref = oracle_eng.run()
  # A stop token straight from the oracle: request 3 retires on its
  # second generated token instead of running to its budget.
  stop = int(ref[3][len(prompts[3]) + 1])

  eng = ContinuousBatchingEngine(model, state.params, mesh=mesh,
                                 num_slots=2, prefill_chunk=4,
                                 drafter=NgramDrafter(k=3, ngram_max=3))
  out = {}
  for i in range(2):
    eng.submit(Request(uid=i, prompt=prompts[i],
                       max_new_tokens=max_new[i]))
  for _ in range(2):  # requests 2/3 join a mid-flight batch
    for fin in eng.step():
      out[fin.uid] = fin.tokens
  eng.submit(Request(uid=2, prompt=prompts[2], max_new_tokens=6))
  eng.submit(Request(uid=3, prompt=prompts[3], max_new_tokens=8,
                     stop_token=stop))
  out.update(eng.run())
  for i in range(3):
    np.testing.assert_array_equal(out[i], ref[i], err_msg=f"req {i}")
  cut = list(ref[3][len(prompts[3]):]).index(stop)
  np.testing.assert_array_equal(out[3], ref[3][:len(prompts[3]) + cut + 1])
  assert eng._step_fn._cache_size() == 1


@pytest.mark.quick
def test_spec_stop_token_mid_draft_retires_exactly():
  """A stop token committed MID-DRAFT (inside an accepted burst) retires
  the request at the stop token and discards the rest of the burst —
  output equals the oracle truncated at the stop's first occurrence.
  A same-params draft model guarantees full acceptance, so the commit
  containing the stop is always a multi-token burst."""
  epl.init()
  model, params = _model_and_params(seed=3)
  (prompt,) = _prompts((5,), seed=4)
  plen = len(prompt)
  ref = _oracle(model, params, prompt, 8)
  gen = list(ref[plen:])
  stop = gen[2]                     # committed at generated index <= 2
  cut = gen.index(stop)
  eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4,
      drafter=DraftModelDrafter(model, params, k=2))
  eng.submit(Request(uid="s", prompt=prompt, max_new_tokens=20,
                     stop_token=int(stop)))
  fins = []
  steps = 0
  while eng.has_work:
    fins.extend(eng.step())
    steps += 1
  assert len(fins) == 1 and fins[0].finish_reason == "stop_token"
  np.testing.assert_array_equal(fins[0].tokens, ref[:plen + cut + 1])
  # Full acceptance => the engine needed fewer steps than tokens: the
  # retiring commit really was a multi-token (mid-draft) burst.
  assert steps < 2 + cut + 1


@pytest.mark.slow
def test_spec_draft_model_full_acceptance_and_exactness():
  """A draft model sharing the target's parameters must reach 100%
  acceptance (greedy drafts == greedy target by construction) — the
  lockstep oracle for the draft-side cache mirror — while outputs stay
  bit-exact, and stats report >1 accepted tokens per drafting step.
  (slow: six oracle shapes; the quick mid-draft stop test keeps the
  same-params draft mirror burst-committing in tier-1.)"""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 9, 1, 6, 2))
  max_new = (6, 7, 8, 4, 5, 9)
  stats = ServingStats()
  eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4,
      drafter=DraftModelDrafter(model, params, k=3), stats=stats)
  for i, p in enumerate(prompts):
    eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new[i]))
  out = eng.run()
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(
        out[i], _oracle(model, params, p, max_new[i]), err_msg=f"req {i}")
  s = stats.summary()
  assert s["acceptance_rate"] == 1.0
  assert s["accepted_per_step_mean"] > 1.0
  assert s["drafted_tokens"] == s["accepted_tokens"] > 0


@pytest.mark.slow
def test_spec_mismatched_draft_model_still_exact():
  """A draft model with DIFFERENT weights (low acceptance) cannot change
  greedy output — rejections fall back to the target's own argmax.
  (slow: the n-gram tier-1 tests already exercise heavy rejection.)"""
  epl.init()
  model, params = _model_and_params()
  draft_cfg = GPTConfig(**{**TINY.__dict__, "num_layers": 1,
                           "d_model": 16, "num_heads": 2, "d_ff": 32})
  draft_model, draft_params = _model_and_params(draft_cfg, seed=9)
  prompts = _prompts((5, 3), seed=5)
  eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4,
      drafter=DraftModelDrafter(draft_model, draft_params, k=3))
  for i, p in enumerate(prompts):
    eng.submit(Request(uid=i, prompt=p, max_new_tokens=8))
  out = eng.run()
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(out[i], _oracle(model, params, p, 8),
                                  err_msg=f"req {i}")


# ----------------------------------------------------------------- sampling


def test_sampled_request_without_drafts_keeps_plain_stream():
  """A request with speculative=False on a speculative engine — and any
  slot whose drafter proposed nothing — reproduces the non-speculative
  engine's sample stream BIT-exactly (the committed-index PRNG fold is
  untouched by speculation plumbing)."""
  epl.init()
  model, params = _model_and_params()
  (prompt,) = _prompts((5,), seed=6)

  def run(drafter):
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   prefill_chunk=4, drafter=drafter)
    eng.submit(Request(uid="s", prompt=prompt, max_new_tokens=8,
                       temperature=0.9, top_k=12, seed=7,
                       speculative=False))
    return eng.run()["s"]

  np.testing.assert_array_equal(run(None), run(NgramDrafter(k=3)))


def test_enabled_false_matches_pre_pr_stream_contract():
  """Satellite regression: with speculation disabled the engine's sample
  stream equals an INDEPENDENT replay of the documented contract —
  token i of a request is sampled from the filtered logits at its last
  committed position with fold_in(PRNGKey(seed), i) — pinning that the
  speculation plumbing changed nothing about pre-PR streams."""
  epl.init()
  model, params = _model_and_params()
  (prompt,) = _prompts((6,), seed=8)
  seed, max_new, C = 11, 5, 4
  temp = np.asarray([0.8], np.float32)
  top_k = np.asarray([10], np.int32)
  top_p = np.asarray([0.95], np.float32)

  kv, _ = allocate_kv_cache(TINY, 1, C)
  key = np.asarray(jax.random.PRNGKey(seed))
  cur, pos, last_tok = 0, 0, None
  out = []
  while len(out) < max_new:
    block = np.zeros((1, C), np.int32)
    if pos < len(prompt):
      grant = min(C, len(prompt) - pos)
      block[0, :grant] = prompt[pos:pos + grant]
      pos += grant
    else:
      block[0, 0] = last_tok
      grant = 1
    logits, kv = slot_step_logits(model, params, kv, jnp.asarray(block),
                                  jnp.asarray([cur], jnp.int32))
    cur += grant
    if pos < len(prompt):
      continue
    last = np.asarray(logits)[:, grant - 1].astype(np.float32)
    k_i = jax.vmap(jax.random.fold_in)(key[None],
                                       jnp.asarray([len(out)]))
    tok = int(np.asarray(sample_token_slots(
        jnp.asarray(last), k_i, jnp.asarray(temp), jnp.asarray(top_k),
        jnp.asarray(top_p)))[0])
    out.append(tok)
    last_tok = tok

  eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                 prefill_chunk=C, speculative=False)
  eng.submit(Request(uid="r", prompt=prompt, max_new_tokens=max_new,
                     temperature=0.8, top_k=10, top_p=0.95, seed=seed))
  got = eng.run()["r"]
  np.testing.assert_array_equal(got[len(prompt):], np.asarray(out))


def test_verify_tokens_preserves_sampling_distribution():
  """ISSUE 4 acceptance: rejection-sampling acceptance preserves the
  target distribution — over many PRNG streams the first committed
  token's empirical distribution matches the FILTERED target softmax,
  whether the (point-mass) draft is likely, unlikely, or filtered out
  entirely by top-k."""
  N, V = 6000, 8
  r = np.random.RandomState(0)
  base = (r.randn(V) * 1.5).astype(np.float32)
  tgt = jnp.broadcast_to(jnp.asarray(base), (N, 2, V)).astype(jnp.float32)
  keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N))
  ones, zeros = jnp.ones((N,)), jnp.zeros((N,), jnp.int32)

  def emitted(draft_tok, top_k=0):
    committed, ncom, accepted = verify_tokens(
        tgt, jnp.full((N, 1), draft_tok, jnp.int32),
        jnp.ones((N,), jnp.int32), keys, zeros, ones,
        jnp.full((N,), top_k, jnp.int32), ones.astype(jnp.float32))
    return np.asarray(committed)[:, 0], np.asarray(accepted)

  def expect(top_k=0):
    x = base.copy()
    if top_k:
      x[np.argsort(x)[:-top_k]] = -np.inf
    p = np.exp(x - np.nanmax(x))
    p[~np.isfinite(p)] = 0.0
    return p / p.sum()

  for draft_tok in (int(np.argmax(base)), int(np.argmin(base))):
    first, accepted = emitted(draft_tok)
    p = expect()
    freq = np.bincount(first, minlength=V) / N
    assert 0.5 * np.abs(freq - p).sum() < 0.035
    assert abs(accepted.mean() - p[draft_tok]) < 0.035
  # Draft outside the top-k filter: never accepted, distribution still
  # matches the filtered target.
  worst = int(np.argmin(base))
  first, accepted = emitted(worst, top_k=3)
  assert accepted.sum() == 0
  p = expect(top_k=3)
  freq = np.bincount(first, minlength=V) / N
  assert 0.5 * np.abs(freq - p).sum() < 0.035


def test_verify_tokens_greedy_semantics():
  """Greedy acceptance is exact-prefix-match: drafts equal to argmax are
  kept, the first mismatch truncates and commits the argmax correction,
  a full match commits the bonus argmax."""
  V, K = 16, 3
  r = np.random.RandomState(1)
  logits = r.randn(2, K + 1, V).astype(np.float32)
  am = logits.argmax(-1)
  drafts = np.stack([am[0, :K],                       # all match
                     [am[1, 0], (am[1, 1] + 1) % V, am[1, 2]]])  # miss @1
  keys = np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(2)])
  committed, ncom, accepted = verify_tokens(
      jnp.asarray(logits), jnp.asarray(drafts, jnp.int32),
      jnp.full((2,), K, jnp.int32), jnp.asarray(keys),
      jnp.zeros((2,), jnp.int32), jnp.zeros((2,)),
      jnp.zeros((2,), jnp.int32), jnp.ones((2,)))
  committed, ncom, accepted = (np.asarray(committed), np.asarray(ncom),
                               np.asarray(accepted))
  assert list(accepted) == [K, 1] and list(ncom) == [K + 1, 2]
  np.testing.assert_array_equal(committed[0], am[0])        # + bonus
  np.testing.assert_array_equal(committed[1][:2], am[1][:2])  # correction


# ----------------------------------------------------------------- drafters


def test_ngram_propose_lookup_semantics():
  h = np.asarray([1, 2, 3, 9, 9, 1, 2, 3, 7, 7, 1, 2, 3], np.int32)
  # Suffix [1,2,3]: most recent earlier occurrence ends at index 7 ->
  # continuation [7, 7, 1, ...], capped at k.
  np.testing.assert_array_equal(ngram_propose(h, 3, 3, 1), [7, 7, 1])
  np.testing.assert_array_equal(ngram_propose(h, 2, 3, 1), [7, 7])
  # No match at any n in [min, max] -> empty proposal.
  assert ngram_propose(np.asarray([1, 2, 3, 4]), 3, 3, 2).size == 0
  # ngram_min=1 falls back to the last unigram's continuation.
  np.testing.assert_array_equal(
      ngram_propose(np.asarray([5, 8, 5, 9, 5]), 2, 3, 1), [9, 5])
  # Degenerate short history never crashes.
  assert ngram_propose(np.asarray([4]), 3, 3, 1).size == 0


def test_scheduler_draft_cap_budget_and_opt_out():
  """draft_cap = min(k, remaining-1) for speculation-eligible decode
  slots; prefilling slots and opted-out requests get 0."""
  from easyparallellibrary_tpu.serving import FCFSScheduler
  sched = FCFSScheduler(num_slots=3, prefill_chunk=4, max_seq_len=64,
                        spec_k=3)
  sched.submit(Request(uid="a", prompt=np.arange(2, dtype=np.int32),
                       max_new_tokens=10))
  sched.submit(Request(uid="b", prompt=np.arange(2, dtype=np.int32),
                       max_new_tokens=10, speculative=False))
  sched.submit(Request(uid="c", prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=3))
  plan = sched.plan_step()
  assert list(plan.draft_cap) == [0, 0, 0]   # everyone still prefilling
  sched.commit(np.zeros(3, np.int32))
  plan = sched.plan_step()
  # a: decoding, remaining 9 -> cap 3; b: opted out; c: still prefilling.
  assert list(plan.draft_cap) == [3, 0, 0]
  assert set(sched.slot_histories(plan)) == {0}
  sched.commit(np.zeros(3, np.int32))
  plan = sched.plan_step()
  # c finished prefill last step: 1 committed, remaining 2 -> cap 1.
  assert plan.draft_cap[2] == 1
  # Multi-token commit: a commits 3 at once (2 accepted + bonus).
  toks = np.zeros((3, 4), np.int32)
  toks[0] = [41, 42, 43, 44]
  sched.commit(toks, np.asarray([3, 1, 1]))
  assert sched.active[0].generated[-3:] == [41, 42, 43]


# ------------------------------------------------------------- capabilities


def test_capability_guards_are_actionable():
  epl.init()
  pp = GPTConfig(**{**TINY.__dict__, "pipeline_stages": 2})
  with pytest.raises(ValueError, match="pipeline.*ROADMAP"):
    check_servable(pp)
  moe = GPTConfig(**{**TINY.__dict__, "num_experts": 2})
  with pytest.raises(ValueError, match="MoE.*ROADMAP"):
    check_servable(moe)
  # The engine rejects through the same guard (message parity with PR 3).
  model_pp = GPT(pp)
  with pytest.raises(ValueError, match="pipeline"):
    ContinuousBatchingEngine(model_pp, {}, num_slots=1)
  # Draft-model shape guards.
  other_vocab = GPTConfig(**{**TINY.__dict__, "vocab_size": 32})
  with pytest.raises(ValueError, match="vocab_size.*token ids"):
    check_draft_compatible(TINY, other_vocab)
  short = GPTConfig(**{**TINY.__dict__, "max_seq_len": 16})
  with pytest.raises(ValueError, match="max_seq_len"):
    check_draft_compatible(TINY, short)
  with pytest.raises(ValueError, match="pipeline"):
    check_draft_compatible(TINY, pp)
  # And end-to-end: binding an incompatible draft model fails the same way.
  model, params = _model_and_params()
  bad_model, bad_params = _model_and_params(other_vocab)
  with pytest.raises(ValueError, match="vocab_size"):
    ContinuousBatchingEngine(
        model, params, num_slots=1, prefill_chunk=4,
        drafter=DraftModelDrafter(bad_model, bad_params, k=2))
  # k must fit the fused step's chunk.
  with pytest.raises(ValueError, match="prefill_chunk >= k"):
    ContinuousBatchingEngine(model, params, num_slots=1, prefill_chunk=4,
                             drafter=NgramDrafter(k=4))
  # draft_model kind needs weights.
  with pytest.raises(ValueError, match="draft_model"):
    ContinuousBatchingEngine(
        model, params, num_slots=1, prefill_chunk=8,
        config=epl.Config({"serving.speculative.enabled": True,
                           "serving.speculative.kind": "draft_model"}))


def test_speculative_config_group_validation():
  conf = epl.Config({"serving.speculative.enabled": True,
                     "serving.speculative.k": 2,
                     "serving": {"speculative": {"ngram_max": 5}}})
  spec = conf.serving.speculative
  assert spec.enabled and spec.k == 2 and spec.ngram_max == 5
  conf.serving.speculative.k = 3          # writable through the view
  assert conf.serving.speculative.k == 3
  with pytest.raises(ValueError, match="speculative.k"):
    epl.Config({"serving.speculative.k": 0})
  with pytest.raises(ValueError, match="kind"):
    epl.Config({"serving.speculative.kind": "psychic"})
  with pytest.raises(ValueError, match="ngram_min"):
    epl.Config({"serving.speculative.ngram_min": 4,
                "serving.speculative.ngram_max": 2})
  with pytest.raises(ValueError, match="prefill_chunk"):
    epl.Config({"serving.speculative.enabled": True,
                "serving.speculative.k": 4,
                "serving.prefill_chunk": 4})
  # Disabled k=4 with chunk 4 is fine (nothing will draft).
  epl.Config({"serving.speculative.k": 4, "serving.prefill_chunk": 4})


def test_speculative_env_var_override(monkeypatch):
  monkeypatch.setenv("EPL_SERVING_SPECULATIVE_K", "6")
  assert epl.Config().serving.speculative.k == 6


def test_config_enabled_engine_uses_ngram_drafter():
  """serving.speculative.* alone (no explicit drafter object) turns the
  engine speculative: the configured n-gram drafter is resolved and the
  scheduler budgets drafts for it.  (Exactness of the resulting engine
  is pinned by the quick tests; this one checks only the config
  plumbing, host-side.)"""
  epl.init(epl.Config({"serving.speculative.enabled": True,
                       "serving.speculative.k": 3,
                       "serving.speculative.ngram_max": 2,
                       "serving.prefill_chunk": 4,
                       "serving.num_slots": 2}))
  model, params = _model_and_params()
  eng = ContinuousBatchingEngine(model, params)
  assert isinstance(eng.drafter, NgramDrafter)
  assert eng.drafter.k == 3 and eng.drafter.ngram_max == 2
  assert eng.scheduler.spec_k == 3
  # An engine-kwarg override beats the config group.
  eng_off = ContinuousBatchingEngine(model, params, speculative=False)
  assert eng_off.drafter is None and eng_off.scheduler.spec_k == 0
  # ...and beats even an explicit drafter object: the opt-out must be
  # trustworthy (it guards sampled requests' bitstreams).
  eng_off2 = ContinuousBatchingEngine(model, params, speculative=False,
                                      drafter=NgramDrafter(k=3))
  assert eng_off2.drafter is None


# ------------------------------------------------------------------ metrics


def test_serving_stats_speculation_counters_degrade_gracefully():
  """Satellite: acceptance-rate rollups over 0- and 1-sample windows —
  legitimately empty early in a run — degrade to 0.0 / the lone sample
  instead of raising, and percentile() clamps out-of-range q."""
  stats = ServingStats(clock=lambda: 0.0)
  s = stats.summary()                      # 0 samples everywhere
  assert s["acceptance_rate"] == 0.0
  assert s["accepted_per_step_p50"] == 0.0 == s["accepted_per_step_p99"]
  stats.note_step(active_slots=1, num_slots=2, prefill_tokens=4,
                  decode_tokens=0, step_time_s=0.1)   # prefill: no drafts
  assert stats.summary()["accepted_per_step_p50"] == 0.0
  stats.note_step(active_slots=1, num_slots=2, prefill_tokens=0,
                  decode_tokens=1, step_time_s=0.1, drafted_tokens=3,
                  accepted_tokens=2)                   # 1-sample window
  s = stats.summary()
  assert s["drafted_tokens"] == 3 and s["accepted_tokens"] == 2
  assert s["acceptance_rate"] == pytest.approx(2 / 3)
  assert s["accepted_per_step_p50"] == 2.0 == s["accepted_per_step_p99"]
  assert s["accepted_per_step_mean"] == 2.0
  assert percentile([], 50) == 0.0
  assert percentile([4.0], 0) == 4.0 == percentile([4.0], 100)
  assert percentile([1.0, 2.0], 150) == 2.0    # clamped, not IndexError
  assert percentile([1.0, 2.0], -5) == 1.0


# ------------------------------------------------------------- restore path


def test_draft_model_from_checkpoint_and_shape_peek(tmp_path):
  """Satellite: the draft-model restore path rides saver.restore_params
  (checksum-validated fallback chain) and validates the checkpoint's
  embedding shape from the index BEFORE loading shards."""
  from easyparallellibrary_tpu.runtime.saver import (
      peek_leaf_shapes, save_checkpoint)
  epl.init()
  model, params = _model_and_params(seed=12)
  root = str(tmp_path / "draft_ckpt")
  save_checkpoint(root, params, step=7)

  shapes, step = peek_leaf_shapes(root)
  assert step == 7
  assert shapes["wte/embedding"] == (TINY.vocab_size, TINY.d_model)

  drafter = DraftModelDrafter.from_checkpoint(root, model, k=2)
  eng = ContinuousBatchingEngine(model, params, num_slots=1,
                                 prefill_chunk=4, drafter=drafter)
  (prompt,) = _prompts((4,), seed=13)
  eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=3))
  out = eng.run()
  np.testing.assert_array_equal(out[0], _oracle(model, params, prompt, 3))

  # Wrong-vocabulary draft config fails from the index alone.
  wrong = GPT(GPTConfig(**{**TINY.__dict__, "vocab_size": 32}))
  with pytest.raises(ValueError, match="vocab-64.*vocab_size=32"):
    DraftModelDrafter.from_checkpoint(root, wrong, k=2)
  with pytest.raises(FileNotFoundError):
    peek_leaf_shapes(str(tmp_path / "nonexistent"))
