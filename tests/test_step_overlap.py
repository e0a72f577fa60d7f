"""The overlapped serving loop (serving/engine.py ``step``): step k+1 is
launched before step k's tokens are fetched, the sampled token handed on
inside the device, and the scheduler plans past one uncommitted step.

What must hold: every request's tokens and finish reason are what the
serial loop gives, bit for bit, greedy and sampled, on each of the four
decoders; a stop token or a cancellation seen one step late costs one
dropped position and nothing else; ``has_work`` covers a step in flight
and ``run`` / ``close`` / ``snapshot_requests`` drain it, ``evacuate``
drops it; the step stays one compiled program and crosses device -> host
once a step; the engines whose next plan needs this step's verdict keep
the serial loop and say why.

Toy widths, float32.  The serial loop is the same code with the fetch
before the next plan: ``_serial`` turns a fresh engine to it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import GPT, GPTConfig  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.observability import validate_trace  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, NgramDrafter, Request)
from easyparallellibrary_tpu.serving._capabilities import step_overlap  # noqa: E402
from easyparallellibrary_tpu.serving.scheduler import FCFSScheduler  # noqa: E402
from easyparallellibrary_tpu.testing import chaos  # noqa: E402
from perfbench.reference import glm4_moe_lite as glm_ref  # noqa: E402
from perfbench.reference import jamba as jamba_ref  # noqa: E402
from perfbench.reference import lfm2_moe as lfm2_ref  # noqa: E402
from perfbench.runners import epl_glm4_moe_lite as glm_glue  # noqa: E402
from perfbench.runners import epl_jamba as jamba_glue  # noqa: E402
from perfbench.runners import epl_lfm2_moe as lfm2_glue  # noqa: E402

VOCAB = 256
CHUNK = 4
F32 = {"dtype": "float32", "param_dtype": "float32"}
GPT_CFG = GPTConfig(vocab_size=VOCAB, num_layers=2, num_heads=4, d_model=32,
                    d_ff=64, max_seq_len=64, dtype=jnp.float32)
# The toy cuts of tests/test_jamba.py, test_glm_moe.py, test_lfm2_moe.py:
# weights wide enough (N(0, 0.1-0.2)) that the streams are no copy of
# the prompt.
JAMBA_CFG = jamba_ref.JambaConfig(
    num_hidden_layers=4, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=1, vocab_size=VOCAB,
    attn_layer_period=4, attn_layer_offset=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, n_positions=64,
    initializer_range=0.2)
GLM_CFG = glm_ref.Glm4MoeLiteConfig(
    num_hidden_layers=3, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, vocab_size=VOCAB, n_positions=64,
    initializer_range=0.2, bias_std=0.05)
LFM2_CFG = lfm2_ref.Lfm2MoeConfig(
    layer_types=("conv", "full_attention", "conv"), hidden_size=128,
    intermediate_size=128, moe_intermediate_size=64, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, vocab_size=VOCAB, n_positions=64,
    initializer_range=0.1, bias_std=0.05)
DECODERS = ("gpt2", "hybrid", "glm-experts", "lfm2")
RECURRENT = ("hybrid", "lfm2")


@pytest.fixture(autouse=True)
def _no_ambient_tracer():
  yield
  trace_lib.reset()


@pytest.fixture(scope="module")
def decoders():
  """``{name: (model, params)}``, built once."""
  epl.init()
  gpt = GPT(GPT_CFG)
  out = {"gpt2": (gpt, gpt.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))["params"])}
  for name, glue, ref, cfg in (("hybrid", jamba_glue, jamba_ref, JAMBA_CFG),
                               ("glm-experts", glm_glue, glm_ref, GLM_CFG),
                               ("lfm2", lfm2_glue, lfm2_ref, LFM2_CFG)):
    model, shell_of = glue.build_model(cfg, F32)
    out[name] = (model, glue.program_params(
        cfg, ref.seed_key(2 ** 31 + 5),
        shell_of(jnp.zeros((1, 8), jnp.int32))))
  return out


def _engine(decoders, name, serial=False, num_slots=2, **kwargs):
  epl.init()
  model, params = decoders[name]
  eng = ContinuousBatchingEngine(model, params, num_slots=num_slots,
                                 prefill_chunk=CHUNK, **kwargs)
  if serial:
    _serial(eng)
  return eng


def _serial(eng):
  """The serial loop on the same program: each step fetched and committed
  before the next is planned.  Only on an engine with nothing in flight."""
  assert eng._inflight is None and eng.step_overlap == "on"
  eng._overlap = False


def _prompt(n, seed):
  return np.random.RandomState(seed).randint(0, VOCAB, (n,)).astype(np.int32)


# Prompts shorter than, equal to and several times the chunk; seven
# requests over two slots, so every slot is used again and again.
LENGTHS = (2, CHUNK, 13, 5, 9, 3, CHUNK * 2)
NEW = (6, 5, 8, 12, 7, 4, 9)
STOPS, CANCELLED, CANCEL_AFTER = 3, 4, 3


def _requests(sampled, stop_token=-1):
  reqs = []
  for i, (n, new) in enumerate(zip(LENGTHS, NEW)):
    knobs = {}
    if sampled:
      knobs = dict(temperature=0.7 + 0.1 * (i % 3), seed=11 + i,
                   top_k=(0, 20, 0)[i % 3], top_p=(1.0, 1.0, 0.9)[i % 3])
    reqs.append(Request(uid=i, prompt=_prompt(n, 5 + i), max_new_tokens=new,
                        stop_token=stop_token if i == STOPS else -1, **knobs))
  return reqs


def _drive(eng, requests, cancel_uid=None):
  """Staggered: two requests at once, one more after every second call.
  ``cancel_uid`` is cancelled once ``CANCEL_AFTER`` of its tokens have
  reached ``on_tokens``: the same moment of ITS stream in either loop.
  Returns ``(finish records, on_tokens streams)``."""
  streams = {}
  eng.scheduler.on_tokens.append(
      lambda uid, toks: streams.setdefault(uid, []).extend(toks))
  waiting = list(requests)
  for _ in range(2):
    assert eng.submit(waiting.pop(0))
  calls, cancelled = 0, False
  while waiting or eng.has_work:
    eng.step()
    calls += 1
    if waiting and calls % 2 == 0:
      assert eng.submit(waiting.pop(0))
    if (cancel_uid is not None and not cancelled
        and len(streams.get(cancel_uid, ())) >= CANCEL_AFTER):
      assert eng.cancel(cancel_uid)
      cancelled = True
    assert calls < 500
  assert cancel_uid is None or cancelled
  return dict(eng.finished), streams


# ------------------------------------------ equal to the serial loop's --


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("name", DECODERS)
def test_overlapped_streams_equal_the_serial_loops(decoders, name, sampled):
  # What request STOPS generates third, unhindered, is its stop token.
  base, _ = _drive(_engine(decoders, name, serial=True), _requests(sampled))
  stop = int(base[STOPS].tokens[LENGTHS[STOPS] + 2])
  got = {}
  for loop in ("serial", "overlapped"):
    eng = _engine(decoders, name, serial=loop == "serial",
                  stats=ServingStats())
    fins, streams = _drive(eng, _requests(sampled, stop_token=stop),
                           cancel_uid=CANCELLED)
    assert eng._step_fn._cache_size() == 1
    assert eng._compile_sentinel.recompiles == 0
    got[loop] = (eng, fins, streams)
  (_, serial, s_streams), (eng, fins, streams) = got["serial"], got["overlapped"]
  assert sorted(fins) == sorted(serial) == list(range(len(LENGTHS)))
  for uid, fin in serial.items():
    np.testing.assert_array_equal(fins[uid].tokens, fin.tokens,
                                  err_msg=f"request {uid}")
    assert fins[uid].finish_reason == fin.finish_reason, uid
    # on_tokens saw exactly the generated tokens, in order, in both loops
    assert streams[uid] == s_streams[uid] == [
        int(t) for t in fin.tokens[LENGTHS[uid]:]], uid
  assert fins[STOPS].finish_reason == "stop_token"
  assert fins[STOPS].tokens[-1] == stop and fins[STOPS].new_tokens <= 3
  assert fins[CANCELLED].finish_reason == "cancelled"
  assert fins[CANCELLED].new_tokens == CANCEL_AFTER
  assert {f.finish_reason for u, f in fins.items()
          if u not in (STOPS, CANCELLED)} == {"length"}
  # the unhindered requests are the baseline's too
  for uid in set(fins) - {STOPS, CANCELLED}:
    np.testing.assert_array_equal(fins[uid].tokens, base[uid].tokens)
  # The stop token and the cancellation were seen one step late: two
  # positions ran for nothing.  The serial loop wastes none.
  assert eng.scheduler.wasted_positions == 2
  assert got["serial"][0].scheduler.wasted_positions == 0
  summary = eng.stats.summary()
  assert summary["wasted_positions"] == 2.0
  assert 0.5 < summary["step_overlap_share"] < 1.0
  assert got["serial"][0].stats.summary()["step_overlap_share"] == 0.0


def test_a_stop_token_costs_one_position_and_its_sample_goes_nowhere(
    decoders):
  """The request stops at commit k with step k+1 launched: that step's
  position for its slot is dropped, counted once, in the counter, the
  per-step record and the stats, and ``on_tokens`` never sees the sample."""
  prompt = _prompt(6, 1)
  base = _engine(decoders, "gpt2", serial=True)
  base.submit(Request(uid="r", prompt=prompt, max_new_tokens=10))
  free = base.run()["r"][len(prompt):]
  stop = int(free[4])
  want = [int(t) for t in free[:list(free).index(stop) + 1]]

  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  records = []

  class Writer:
    def write(self, step, record):
      records.append(record)

  stats = ServingStats()
  eng = _engine(decoders, "gpt2", stats=stats, metrics_writer=Writer())
  seen = []
  eng.scheduler.on_tokens.append(lambda uid, toks: seen.extend(toks))
  eng.submit(Request(uid="r", prompt=prompt, max_new_tokens=10,
                     stop_token=stop))
  out = eng.run()
  events = tracer.events()
  assert seen == want == [int(t) for t in out["r"][len(prompt):]]
  assert eng.finished["r"].finish_reason == "stop_token"
  counters = lambda n: [ev["args"]["value"] for ev in events
                        if ev["ph"] == "C" and ev["name"] == n]
  wasted = counters("serving/wasted_positions")
  assert sum(wasted) == 1 and wasted[-1] == 1
  assert len(wasted) == len(counters("serving/active_slots")) == eng._steps
  # every step but the first was launched with its predecessor in flight
  assert counters("serving/overlapped_steps") == [0] + [1] * (eng._steps - 1)
  assert [r["wasted_positions"] for r in records] == wasted
  assert [r["overlapped_steps"] for r in records] == (
      counters("serving/overlapped_steps"))
  assert stats.summary()["wasted_positions"] == 1.0
  assert stats.summary()["step_overlap_share"] == pytest.approx(
      (eng._steps - 1) / eng._steps)
  assert eng.scheduler.wasted_positions == 1
  meta = {ev["name"]: ev["args"] for ev in events if ev["ph"] == "M"}
  assert meta["serving/step_overlap"] == {"mode": "on"}
  ctx = eng._capture_context()["serving"]
  assert ctx["step_overlap"] == "on" and ctx["wasted_positions"] == 1
  # the dropped position's span is not drawn past its request's end
  validate_trace(events)


# ----------------------------------------------- in flight, and drained --


def _one_in_flight(decoders, name, **kwargs):
  """An engine with request "r" decoding and a step in flight."""
  eng = _engine(decoders, name, **kwargs)
  eng.submit(Request(uid="r", prompt=_prompt(5, 2), max_new_tokens=8))
  for _ in range(4):
    assert eng.step() == []
  assert eng._inflight is not None and eng._steps == 3
  return eng


def _alone(decoders, name, request):
  eng = _engine(decoders, name, serial=True)
  eng.submit(request)
  return eng.run()[request.uid]


@pytest.mark.parametrize("name", DECODERS)
def test_has_work_covers_the_step_in_flight_and_run_drains_it(decoders, name):
  eng = _one_in_flight(decoders, name)
  want = _alone(decoders, name, Request(uid="r", prompt=_prompt(5, 2),
                                        max_new_tokens=8))
  # run(max_steps) ends with nothing in flight: the step that was, the
  # two it launched
  out = eng.run(max_steps=2)
  assert eng._inflight is None and eng._steps == 6 and out == {}
  assert eng.has_work
  # the first step after a drain is launched at once, and returns nothing
  assert eng.step() == [] and eng._inflight is not None
  # cancelled with a step in flight: the scheduler is empty, the engine
  # is not, and the next call fetches, commits nothing and goes idle
  n = len(eng.scheduler.active[0].generated)
  assert eng.cancel("r") and not eng.scheduler.has_work and eng.has_work
  fins = eng.step()
  assert [f.finish_reason for f in fins] == ["cancelled"]
  assert not eng.has_work and eng._inflight is None
  assert eng.scheduler.wasted_positions == 1
  np.testing.assert_array_equal(fins[0].tokens, want[:5 + n])


@pytest.mark.parametrize("how", ["close", "snapshot_requests"])
def test_close_and_snapshot_commit_the_step_in_flight(decoders, how):
  eng = _one_in_flight(decoders, "gpt2")
  state = eng.scheduler.active[0]
  n = len(state.generated)
  if how == "close":
    eng.close()
  else:
    (snap,) = eng.snapshot_requests()
    assert len(snap["generated"]) == n + 1
  assert eng._inflight is None and len(state.generated) == n + 1
  assert eng._steps == 4
  # the engine goes on from there, to the serial loop's tokens
  out = eng.run()
  np.testing.assert_array_equal(out["r"], _alone(
      decoders, "gpt2", Request(uid="r", prompt=_prompt(5, 2),
                                max_new_tokens=8)))


def test_a_drains_retirements_come_back_with_the_next_step(decoders):
  eng = _engine(decoders, "gpt2")
  eng.submit(Request(uid="r", prompt=_prompt(3, 2), max_new_tokens=2))
  eng.step(), eng.step()
  assert eng._inflight is not None and eng.scheduler.has_work
  assert eng.snapshot_requests() == []      # the drain retired it
  assert eng.finished["r"].finish_reason == "length"
  assert eng.has_work and not eng.scheduler.has_work
  assert [f.uid for f in eng.step()] == ["r"]
  assert not eng.has_work and eng.step() == []


@pytest.mark.parametrize("name", DECODERS)
def test_evacuate_drops_the_step_in_flight_and_the_replay_is_exact(
    decoders, name):
  eng = _one_in_flight(decoders, name)
  n = len(eng.scheduler.active[0].generated)
  (snap,) = eng.evacuate()
  # dropped, not committed: nothing finishes here, the snapshot holds the
  # committed prefix, and the engine is empty and warm
  assert eng._inflight is None and not eng.has_work and eng.finished == {}
  assert len(snap["generated"]) == n and eng._steps == 3
  assert not eng.scheduler._plans
  want = _alone(decoders, name, Request(uid="r", prompt=_prompt(5, 2),
                                        max_new_tokens=8))
  for target in (eng, _engine(decoders, name)):
    target.restore_request(snap)
    np.testing.assert_array_equal(target.run()["r"], want)
  assert eng._step_fn._cache_size() == 1


def test_a_launch_that_raises_is_planned_again_beside_the_step_in_flight(
    decoders):
  """The launch of step k+1 fails with step k in flight (a replica killed
  mid-step, testing/chaos.py): k stays in flight, the plan made past it is
  abandoned, and the next call plans and launches the same work."""
  request = Request(uid="r", prompt=_prompt(6, 7), max_new_tokens=9)
  eng = _engine(decoders, "gpt2")
  killer = chaos.ReplicaKiller(eng, kill_calls=[3])
  eng.submit(request)
  out, raised = {}, 0
  while eng.has_work:
    try:
      out.update((f.uid, f.tokens) for f in eng.step())
    except RuntimeError:
      raised += 1
      assert eng._inflight is not None and len(eng.scheduler._plans) == 1
  assert raised == killer.kills == 1 and eng._step_fn._cache_size() == 1
  np.testing.assert_array_equal(out["r"], _alone(decoders, "gpt2", request))
  assert eng.scheduler.wasted_positions == 0


@pytest.mark.parametrize("name", RECURRENT)
def test_a_slot_taken_again_after_a_late_retirement_starts_from_zero(
    decoders, name):
  """One slot.  "a" stops by its stop token, seen one step late: the
  step already launched runs one more position through the slot's
  recurrence.  "b" is admitted to that slot next and must read none of
  it."""
  pa, pb = _prompt(7, 3), _prompt(6, 4)
  free = _alone(decoders, name, Request(uid="a", prompt=pa,
                                        max_new_tokens=9))
  stop = int(free[len(pa) + 3])
  want = _alone(decoders, name, Request(uid="b", prompt=pb,
                                        max_new_tokens=7))
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  eng = _engine(decoders, name, num_slots=1)
  eng.submit(Request(uid="a", prompt=pa, max_new_tokens=9, stop_token=stop))
  eng.submit(Request(uid="b", prompt=pb, max_new_tokens=7))
  out = eng.run()
  assert eng.finished["a"].finish_reason == "stop_token"
  assert eng.scheduler.wasted_positions == 1
  np.testing.assert_array_equal(out["b"], want)
  resets = [ev["args"]["value"] for ev in tracer.events()
            if ev["ph"] == "C" and ev["name"] == "serving/state_resets"]
  assert sum(resets) == 2


# --------------------------------------- one program, one fetch a step --


@pytest.mark.parametrize("name", DECODERS)
def test_one_program_and_one_crossing_a_step(decoders, name, monkeypatch):
  """The whole drive under the device -> host guard: what the loop fetches
  it fetches explicitly, once a step (an expert model's load rides beside
  its tokens), and the two arguments more compiled nothing more."""
  fetched = []
  real = jax.device_get
  monkeypatch.setattr(jax, "device_get",
                      lambda x: fetched.append(np.shape(x)) or real(x))
  eng = _engine(decoders, name, num_slots=3)
  with jax.transfer_guard_device_to_host("disallow"):
    fins, _ = _drive(eng, _requests(sampled=False))
  assert len(fins) == len(LENGTHS)
  per_step = [(3,), (2,)] if "experts" in name or name == "lfm2" else [(3,)]
  assert fetched == per_step * eng._steps
  assert eng._step_fn._cache_size() == 1
  assert eng._compile_sentinel.recompiles == 0


# ------------------------------------------ the engines that stay serial --


def test_the_rule_gives_one_reason_string():
  assert step_overlap(paged=False, speculative=False, resilient=False) == "on"
  for kw, word in (({"paged": True}, "block tables"),
                   ({"speculative": True}, "drafts"),
                   ({"resilient": True}, "verdict")):
    got = step_overlap(**{"paged": False, "speculative": False,
                          "resilient": False, **kw})
    assert got.startswith("off: ") and word in got
  every = step_overlap(paged=True, speculative=True, resilient=True)
  assert all(w in every for w in ("block tables", "drafts", "verdict"))


@pytest.mark.parametrize("kwargs", [
    {"paged": True, "block_size": 4}, {"drafter": NgramDrafter(k=2)},
    {"resilience": True}], ids=["paged", "speculative", "guarded"])
def test_engines_that_need_the_verdict_keep_the_serial_loop(decoders, kwargs):
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  eng = _engine(decoders, "gpt2", **kwargs)
  assert eng.step_overlap.startswith("off: the next plan needs")
  assert not eng._overlap
  meta = {ev["name"]: ev["args"] for ev in tracer.events()
          if ev["ph"] == "M"}
  assert meta["serving/step_overlap"] == {"mode": eng.step_overlap}
  eng.submit(Request(uid="r", prompt=_prompt(3, 2), max_new_tokens=1))
  # fetched and committed in the call that launched it, as before
  assert [f.uid for f in eng.step()] == ["r"]
  assert eng._inflight is None and not eng.has_work
  fins, _ = _drive(eng, _requests(sampled=False))
  plain, _ = _drive(_engine(decoders, "gpt2"), _requests(sampled=False))
  for uid, fin in plain.items():
    np.testing.assert_array_equal(fins[uid].tokens, fin.tokens)
  assert eng.scheduler.wasted_positions == 0
  assert eng._step_fn._cache_size() == 1
  assert eng._capture_context()["serving"]["step_overlap"] == (
      eng.step_overlap)
  eng.close()


# -------------------------------------------------- the scheduler alone --


def _scheduler(**kwargs):
  return FCFSScheduler(num_slots=2, prefill_chunk=CHUNK, max_seq_len=64,
                       **kwargs)


def test_a_plan_past_an_uncommitted_step_goes_on_from_where_it_leaves():
  s = _scheduler()
  s.submit(Request(uid="a", prompt=np.arange(1, 7, dtype=np.int32),
                   max_new_tokens=2))
  p1 = s.plan_step()
  assert p1.num_valid.tolist() == [CHUNK, 0] and p1.reset.tolist() == [True, False]
  p2 = s.plan_step(ahead=True)          # the prompt's other two tokens
  assert p2.tokens[0, :2].tolist() == [5, 6] and p2.num_valid[0] == 2
  assert not p2.reset.any() and not p2.from_prev.any()
  assert [(slot, fed, sampled) for slot, _, fed, sampled in p2.fed] == [
      (0, 2, True)]
  with pytest.raises(RuntimeError, match="ONE uncommitted step"):
    s.plan_step(ahead=True)
  assert s.commit(np.asarray([9, 0])) == []          # mid-prompt sample
  state = s.active[0]
  assert state.prompt_pos == CHUNK and state.generated == []
  p3 = s.plan_step(ahead=True)          # decodes from p2's unseen sample
  assert p3.from_prev.tolist() == [True, False] and p3.tokens[0, 0] == 0
  assert p3.tok_index[0] == 1 and p3.num_valid[0] == 1
  assert s.commit(np.asarray([40, 0])) == []
  assert state.generated == [40]
  # p3 brings "a" to its two tokens: nothing more to feed, known now
  assert s.plan_step(ahead=True) is None
  (fin,) = s.commit(np.asarray([41, 0]))
  assert fin.tokens.tolist() == [1, 2, 3, 4, 5, 6, 40, 41]
  assert fin.finish_reason == "length" and s.wasted_positions == 0


def test_an_abandoned_plan_is_planned_again():
  s = _scheduler()
  s.submit(Request(uid="a", prompt=np.arange(1, 4, dtype=np.int32),
                   max_new_tokens=3))
  first = s.plan_step()
  again = s.plan_step()                 # the first never ran
  np.testing.assert_array_equal(first.tokens, again.tokens)
  assert again.reset[0] and len(s._plans) == 1
  ahead = s.plan_step(ahead=True)
  assert ahead.from_prev[0]
  s.abandon(ahead)                      # its launch failed
  assert s.active[0].samples_ahead == 1 and len(s._plans) == 1
  s.commit(np.asarray([7, 0]))
  state = s.active[0]
  assert (state.fed_ahead, state.samples_ahead) == (0, 0)
  plan = s.plan_step()
  assert plan.tokens[0, 0] == 7 and not plan.from_prev.any()
  s.abandon()
  assert not s._plans and (state.fed_ahead, state.samples_ahead) == (0, 0)
  with pytest.raises(RuntimeError, match="without a preceding plan_step"):
    s.commit(np.asarray([0, 0]))


def test_a_late_stop_wastes_the_position_planned_past_it():
  s = _scheduler()
  s.submit(Request(uid="a", prompt=np.asarray([1, 2], np.int32),
                   max_new_tokens=5, stop_token=50))
  s.submit(Request(uid="b", prompt=np.asarray([3], np.int32),
                   max_new_tokens=5))
  s.plan_step()
  s.plan_step(ahead=True)
  (fin,) = s.commit(np.asarray([50, 8]))
  assert fin.uid == "a" and fin.finish_reason == "stop_token"
  # slot 0 is free for the plan after the one already made ...
  s.submit(Request(uid="c", prompt=np.asarray([4, 5], np.int32),
                   max_new_tokens=2))
  p3 = s.plan_step(ahead=True)
  assert p3.reset.tolist() == [True, False] and p3.tokens[0, :2].tolist() == [4, 5]
  assert p3.from_prev.tolist() == [False, True]
  # ... whose sample for "a" goes nowhere
  assert s.commit(np.asarray([33, 9])) == []
  assert s.wasted_positions == 1 and s.active[1].generated == [8, 9]
  assert s.active[0].req.uid == "c" and s.active[0].generated == []
