"""The fused step's sampling tail does the work its slots ask for.

``sample_token_slots`` / ``filtered_logits`` (serving/engine.py) choose
with ``lax.cond`` on the step's own parameter arrays: nothing but the
argmax when no slot samples, no sort when no filter is on, ONE sort
otherwise.  Held here: the tokens and distributions are bit for bit those
of the ungated two-sort functions (kept below, verbatim, as the
reference); the compiled step keeps its one sort inside a branch of a
conditional; the step still compiles once whatever mix it serves; and the
``serving/sampled_slots`` counter says which branch a step took.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.profiler import ServingStats
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, Request, filtered_logits, sample_token_slots)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter
from easyparallellibrary_tpu.testing.hlo import op_sites

TINY = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)
M, V = 12, 64


# ------------------------------------------------ the reference: two sorts


def filtered_logits_two_sorts(logits, temperature, top_k, top_p):
  """``filtered_logits`` as it stood before the gate (commit 60c852e),
  verbatim: two sorts of the whole vocabulary for every row."""
  V = logits.shape[-1]
  neg = jnp.asarray(-1e30, logits.dtype)
  t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
  scaled = logits / t.astype(logits.dtype)
  # top-k with a traced k: threshold at the k-th largest value (ties at
  # the threshold survive, exactly like sample_logits' `logits < kth`).
  sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
  kth = jnp.take_along_axis(
      sorted_desc, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
  k_off = (top_k[:, None] <= 0) | (top_k[:, None] >= V)
  scaled = jnp.where((scaled >= kth) | k_off, scaled, neg)
  # top-p over the survivors: keep entries whose PRECEDING mass is < p
  # (the crossing token survives; the top token always survives).
  sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
  probs = jax.nn.softmax(sorted_desc.astype(jnp.float32), axis=-1)
  cum = jnp.cumsum(probs, axis=-1)
  keep_sorted = (cum - probs) < top_p[:, None]
  thresh = jnp.min(jnp.where(keep_sorted, sorted_desc,
                             jnp.asarray(jnp.inf, scaled.dtype)),
                   axis=-1, keepdims=True)
  p_on = top_p[:, None] < 1.0
  return jnp.where(p_on & (scaled < thresh), neg, scaled)


def sample_token_slots_ungated(logits, keys, temperature, top_k, top_p):
  """``sample_token_slots`` as it stood before the gate, verbatim."""
  greedy = jnp.argmax(logits, axis=-1)
  scaled = filtered_logits_two_sorts(logits, temperature, top_k, top_p)
  sampled = jax.vmap(jax.random.categorical)(keys, scaled)
  return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)


# The parameters are traced, so every case of one dtype shares a compile.
_filtered = jax.jit(filtered_logits)
_filtered_ref = jax.jit(filtered_logits_two_sorts)
_sample = jax.jit(sample_token_slots)
_sample_ref = jax.jit(sample_token_slots_ungated)


def _tied_logits(kind):
  """``[M, V]`` logits on a coarse grid, so every row holds runs of equal
  values and a top-k or top-p threshold falls inside one."""
  r = np.random.RandomState(3)
  x = np.round(r.randn(M, V) * 2.0) / 2.0 + 0.01 * r.randn(M, 1)
  x[0] = 1.25                        # a row that is one long tie
  x[1, : V // 2] = x[1, V // 2:]     # every value at least twice
  x = jnp.asarray(x, jnp.float32)
  if kind == "bfloat16":
    return x.astype(jnp.bfloat16)
  if kind == "bfloat16_as_float32":   # what the step hands the sampler
    return x.astype(jnp.bfloat16).astype(jnp.float32)
  return x


# Mixed with <= 0: greedy rows ride through the filter at temperature 1.
_TEMPS = np.asarray([0.0, 0.7, -1.0, 1.0, 1.9, 0.0, 0.3, 5.0, 1.0, 0.0,
                     2.5, 0.9], np.float32)


# A filter of its own in every row: top-k alone, top-p alone, both,
# neither, and a k past the vocabulary.
_MIXED_K = np.asarray([0, 3, 0, 7, V, 1, 0, V + 3, 40, 0, 2, 63], np.int32)
_MIXED_P = np.asarray([1.0, 1.0, 0.5, 0.8, 0.3, 1.0, 1e-6, 0.95, 1.0, 0.999,
                       0.1, 0.7], np.float32)


@pytest.mark.parametrize("kind", ["float32", "bfloat16",
                                  "bfloat16_as_float32"])
@pytest.mark.parametrize("top_p", [1.0, 0.9, 0.1, 1e-6])
@pytest.mark.parametrize("top_k", [0, 1, 5, V, V + 3])
def test_filtered_logits_equals_the_two_sort_reference(top_k, top_p, kind):
  logits = _tied_logits(kind)
  k = jnp.full((M,), top_k, jnp.int32)
  p = jnp.full((M,), top_p, jnp.float32)
  got = _filtered(logits, _TEMPS, k, p)
  want = _filtered_ref(logits, _TEMPS, k, p)
  assert got.dtype == want.dtype == logits.dtype
  np.testing.assert_array_equal(np.asarray(got, np.float32),
                                np.asarray(want, np.float32))
  if top_k in (0, V, V + 3) and top_p == 1.0:
    # No filter on: the scaled logits themselves.
    t = jnp.where(_TEMPS > 0, _TEMPS, 1.0)[:, None].astype(logits.dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(logits / t, np.float32))


@pytest.mark.parametrize("kind", ["float32", "bfloat16_as_float32"])
def test_filtered_logits_with_a_filter_of_its_own_in_every_row(kind):
  logits = _tied_logits(kind)
  np.testing.assert_array_equal(
      np.asarray(_filtered(logits, _TEMPS, _MIXED_K, _MIXED_P)),
      np.asarray(_filtered_ref(logits, _TEMPS, _MIXED_K, _MIXED_P)))


_K0, _P1 = np.zeros(M, np.int32), np.ones(M, np.float32)
_BATCHES = {
    # name: (temperature, top_k, top_p)
    "all_greedy": (np.zeros(M, np.float32), _K0, _P1),
    "greedy_with_filters_set": (np.zeros(M, np.float32),
                                np.full(M, 5, np.int32),
                                np.full(M, 0.5, np.float32)),
    "temperature_only": (np.linspace(0.5, 2.0, M).astype(np.float32),
                         _K0, _P1),
    "filtered": (np.full(M, 1.3, np.float32), np.full(M, 7, np.int32),
                 np.full(M, 0.9, np.float32)),
    "one_sampled_among_greedy": (np.eye(1, M, 4, dtype=np.float32)[0],
                                 _K0, _P1),
    "one_filtered_among_greedy": (np.eye(1, M, 9, dtype=np.float32)[0] * 2,
                                  np.eye(1, M, 9, dtype=np.int32)[0] * 6,
                                  _P1),
    "mixed": (_TEMPS, _MIXED_K, _MIXED_P),
}


@pytest.mark.parametrize("batch", sorted(_BATCHES))
def test_sample_token_slots_equals_the_ungated_reference(batch):
  temperature, top_k, top_p = map(jnp.asarray, _BATCHES[batch])
  logits = _tied_logits("bfloat16_as_float32")
  for seed in range(3):
    keys = jax.vmap(lambda i: jax.random.fold_in(
        jax.random.PRNGKey(seed), i))(jnp.arange(M))
    got = _sample(logits, keys, temperature, top_k, top_p)
    want = _sample_ref(logits, keys, temperature, top_k, top_p)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------ the compiled step


def _model_and_params():
  model = GPT(TINY)
  params = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]
  return model, params


_ENGINES = {
    "contiguous": {},
    "paged": {"paged": True, "block_size": 4},
    "speculative": {"drafter": lambda: NgramDrafter(k=2)},
    "paged_speculative": {"paged": True, "block_size": 4,
                          "drafter": lambda: NgramDrafter(k=2)},
}


@pytest.mark.parametrize("kind", sorted(_ENGINES))
def test_the_compiled_step_sorts_once_and_only_inside_a_branch(kind):
  """All four fused steps: the backend's optimised program holds exactly
  one ``sort``, and it is reached through a branch of a ``conditional``
  alone, so a step whose slots do not ask for it does not run it."""
  epl.init()
  model, params = _model_and_params()
  opts = dict(_ENGINES[kind])
  if "drafter" in opts:
    opts["drafter"] = opts["drafter"]()
  eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                 prefill_chunk=4, **opts)
  eng.submit(Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                     max_new_tokens=4))
  plan = eng.scheduler.plan_step()
  num_draft = (None if eng.drafter is None
               else np.zeros((3,), np.int32))
  text = eng._step_fn.lower(
      *eng._step_args(plan, num_draft)).compile().as_text()
  unconditional, conditional = op_sites(text, "sort")
  assert unconditional == [], unconditional
  assert len(conditional) == 1, conditional
  assert op_sites(text, "conditional")[0], "the conditional was flattened"


def test_op_sites_tells_a_branch_from_the_entry():
  """The reader itself, on a program with a sort on both sides."""
  def f(x, flag):
    always = jnp.sort(x)
    return jax.lax.cond(flag, lambda: jnp.sort(-x) + always,
                        lambda: always)
  text = jax.jit(f).lower(jnp.arange(64.0), True).compile().as_text()
  unconditional, conditional = op_sites(text, "sort")
  assert len(unconditional) == 1 and len(conditional) == 1
  with pytest.raises(ValueError):
    op_sites("not a program", "sort")


# ------------------------------------------------- one engine, every mix


def _drive(eng):
  """Step ``eng`` dry; ``{uid: tokens}`` of what finished."""
  out = {}
  while eng.has_work:
    out.update({f.uid: f.tokens for f in eng.step()})
  return out


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_greedy_then_mixed_then_greedy_compiles_once(paged):
  """Greedy steps, then a sampled request among greedy ones, then greedy
  again: one compile; the sampled request's stream is the one it gets
  alone; the greedy streams are the ones they get without it."""
  epl.init()
  model, params = _model_and_params()
  opts = {"paged": True, "block_size": 4} if paged else {}
  r = np.random.RandomState(1)
  prompts = [r.randint(0, 64, (n,)).astype(np.int32) for n in (5, 3, 7, 4)]
  sampled = Request(uid="s", prompt=prompts[3], max_new_tokens=5,
                    temperature=0.9, top_k=12, top_p=0.95, seed=11)

  def greedy(i, n):
    return Request(uid=i, prompt=prompts[i], max_new_tokens=n)

  def engine():
    return ContinuousBatchingEngine(model, params, num_slots=3,
                                    prefill_chunk=4, stats=ServingStats(),
                                    **opts)

  alone = engine()
  alone.submit(sampled)
  want_sampled = _drive(alone)["s"]
  only_greedy = engine()
  for i, n in enumerate((12, 4, 9)):
    only_greedy.submit(greedy(i, n))
  want_greedy = _drive(only_greedy)
  assert only_greedy.stats.sampling_steps == 0
  assert only_greedy.stats.summary()["sampling_step_share"] == 0.0

  eng = engine()
  eng.submit(greedy(0, 12))
  eng.submit(greedy(1, 4))
  out = {}
  for _ in range(3):                                  # greedy
    out.update({f.uid: f.tokens for f in eng.step()})
  eng.submit(sampled)                                 # mixed
  eng.submit(greedy(2, 9))
  out.update(_drive(eng))                             # ... greedy again
  assert eng._step_fn._cache_size() == 1
  np.testing.assert_array_equal(out["s"], want_sampled)
  for i in range(3):
    np.testing.assert_array_equal(out[i], want_greedy[i])
  stats = eng.stats
  assert 0 < stats.sampling_steps < stats.steps
  assert stats.summary()["sampling_step_share"] == pytest.approx(
      stats.sampling_steps / stats.steps)


def test_sampled_slots_counts_the_slots_that_sample():
  """``serving/sampled_slots`` beside ``serving/active_slots``: 0 on a
  greedy step, the number of live slots at temperature > 0 otherwise.
  A 3-token prompt prefills in one step and yields its first token
  there, so a request of n tokens is live for n steps."""
  epl.init()
  model, params = _model_and_params()
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    eng = ContinuousBatchingEngine(model, params, num_slots=4,
                                   prefill_chunk=4)
    prompt = np.arange(1, 4, dtype=np.int32)
    eng.submit(Request(uid="g", prompt=prompt, max_new_tokens=9))
    eng.step()
    eng.step()
    eng.submit(Request(uid="a", prompt=prompt, max_new_tokens=2,
                       temperature=0.8, seed=1))
    eng.submit(Request(uid="b", prompt=prompt, max_new_tokens=4,
                       temperature=1.2, top_k=5, seed=2))
    _drive(eng)
    events = tracer.events()
  finally:
    trace_lib.install(None)
  counters = lambda name: [ev["args"]["value"] for ev in events
                           if ev["ph"] == "C" and ev["name"] == name]
  assert counters("serving/active_slots") == [1, 1, 3, 3, 2, 2, 1, 1, 1]
  assert counters("serving/sampled_slots") == [0, 0, 2, 2, 1, 1, 0, 0, 0]
