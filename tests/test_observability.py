"""Unified tracing & telemetry (ISSUE 5): span tracer, Perfetto export,
per-request serving timelines, one metric schema.

The acceptance contract: a staggered-admission serving run plus a short
``fit()`` with tracing enabled yield (a) Perfetto-loadable JSON that
passes the schema validator (required keys, monotonic ts, paired B/E),
(b) one complete lifecycle track per request — admit/prefill/decode/
retire spans, speculation accepted-count events when drafting — and
(c) no observability tax: zero change in jit cache size, no added
per-step host syncs (the tracer runs under a device-to-host transfer
guard), and traced step time within 5% of untraced on the CPU mesh.

One module-scoped traced run (fit + speculative serving + interleaved
on/off timing episodes) feeds the acceptance assertions so the compile
budget is paid once.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu import ops
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.observability import (
    MetricRegistry, validate_trace)
from easyparallellibrary_tpu.observability import report, trace as trace_lib
from easyparallellibrary_tpu.parallel import (
    TrainState, create_sharded_train_state, make_train_step, parallelize)
from easyparallellibrary_tpu.profiler import ServingStats
from easyparallellibrary_tpu.profiler.flops import FlopsProfiler
from easyparallellibrary_tpu.runtime.loop import fit
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, DraftModelDrafter, Request)
from easyparallellibrary_tpu.utils.metrics_writer import MetricsWriter

TINY = GPTConfig(vocab_size=64, num_layers=1, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)


class Net(nn.Module):
  @nn.compact
  def __call__(self, x):
    return ops.Dense(1, parallel="none")(jnp.tanh(
        ops.Dense(8, parallel="none")(x)))


@pytest.fixture(scope="module", autouse=True)
def _drop_ambient_tracer():
  """The ambient tracer outlives the per-test Env reset; drop it after
  this module so later test files run untraced."""
  yield
  trace_lib.reset()


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
  """One traced staggered speculative serving episode + interleaved
  tracer-on/off timing episodes on the SAME compiled engine, then a
  short traced fit().  Everything the acceptance tests assert on is
  produced here, so the jit compile budget is paid once for the module.

  Serving runs BEFORE fit on purpose: running fit first makes the
  engine's fused step recompile once on its second call — a
  pre-existing fit/engine interplay present on the seed tree and
  independent of tracing (verified by replaying this sequence on the
  pre-PR tree; ROADMAP notes it) — which would confound the zero-
  recompile and overhead measurements below.
  """
  work = tmp_path_factory.mktemp("obs")
  ckpt = str(work / "ck")
  trace_path = str(work / "trace.json")
  epl.init(epl.Config({"observability": {"enabled": True}}))
  tracer = trace_lib.ensure_configured()

  # ---- serving: staggered admission, same-params draft model ----------
  # (a drafter sharing the target's params always proposes and always
  # gets accepted under greedy — guaranteed `speculate` spans with
  # accepted counts, the acceptance criterion's "when drafting").
  gpt = GPT(TINY)
  params = gpt.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 4), jnp.int32))["params"]
  eng = ContinuousBatchingEngine(
      gpt, params, num_slots=2, prefill_chunk=4,
      drafter=DraftModelDrafter(gpt, params, k=2), stats=ServingStats())
  rp = np.random.RandomState(1)
  prompts = [rp.randint(0, 64, (n,)).astype(np.int32)
             for n in (5, 3, 6, 2)]

  def submit(i):
    eng.submit(Request(uid=f"req{i}", prompt=prompts[i],
                       max_new_tokens=5 + i))

  outputs = {}
  submit(0), submit(1)
  for _ in range(2):           # the second wave joins mid-flight
    for fin in eng.step():
      outputs[fin.uid] = fin.tokens
  submit(2), submit(3)
  outputs.update(eng.run())
  engine_step_cache = eng._step_fn._cache_size()

  # ---- overhead guard: interleaved on/off episodes, same engine -------
  # The engine is compiled and warm; each episode re-serves the same
  # request mix, alternating the tracer switch, so both sides run the
  # identical step sequence.  The toggle only flips BETWEEN episodes
  # (each drains its queue), so recorded lifecycles stay B/E-balanced.
  # Per-STEP durations are collected: the acceptance compares minimum
  # achievable step time, which ~70 samples per side pin tightly while
  # episode-level wall clock stays hostage to the shared box.
  def episode():
    import time
    for i in range(4):
      submit(i)
    steps = []
    while eng.has_work:
      t0 = time.perf_counter()
      eng.step()
      steps.append(time.perf_counter() - t0)
    return steps

  episode()                    # warm the slot-reuse paths either side
  times = {True: [], False: []}
  # GC held off during the measurement: traced episodes allocate ring
  # events that SURVIVE the episode, so collection pauses (tens of ms in
  # an object-heavy pytest process) land disproportionately on the
  # traced side and would measure the collector, not the tracer.
  import gc
  gc.collect()
  gc.disable()
  try:
    # ABBA order: a monotone warm-up or load trend lands equally on
    # both sides (a plain alternation hands every colder slot to one
    # side, which a min-compare amplifies).
    for on in [True, False, False, True] * 4:
      tracer.enabled = on
      times[on].extend(episode())
  finally:
    gc.enable()
  tracer.enabled = True
  engine_step_cache_after = eng._step_fn._cache_size()

  # ---- short fit(): phase spans, checkpoint spans, auto JSONL sink ----
  mesh = epl.current_plan().build_mesh()
  model = Net()
  r = np.random.RandomState(0)
  batch = {"x": jnp.asarray(r.randn(16, 4), jnp.float32),
           "y": jnp.asarray(r.randn(16, 1), jnp.float32)}

  def init_fn(rng):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(rng, batch["x"])["params"],
                             tx=optax.adam(1e-2))

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0))

  def loss_fn(params, b, rng):
    pred = model.apply({"params": params}, b["x"])
    return jnp.mean((pred - b["y"]) ** 2), {}

  step = parallelize(make_train_step(loss_fn), mesh, shardings)
  fit(step, state, [batch], num_steps=6, checkpoint_dir=ckpt,
      checkpoint_every=3, log_every=2, shardings=shardings)
  fit_step_cache = step.jitted._cache_size()

  exported = tracer.export(trace_path)

  return {
      "trace_path": exported,
      "fit_trace_path": str(work / "ck" / "trace.json"),
      "metrics_path": str(work / "ck" / "metrics.jsonl"),
      "uids": [f"req{i}" for i in range(4)],
      "outputs": outputs,
      "fit_step_cache": fit_step_cache,
      "engine_step_cache": engine_step_cache,
      "engine_step_cache_after_timing": engine_step_cache_after,
      "times_on": times[True],
      "times_off": times[False],
  }


# ------------------------------------------------------------ acceptance


@pytest.mark.quick
def test_trace_schema_valid(traced_run):
  """Acceptance: the emitted Chrome-trace
  JSON is schema-valid — traceEvents list, required keys per event,
  monotonic ts, strictly paired B/E — and Perfetto-loadable in shape."""
  events = validate_trace(traced_run["trace_path"])
  assert events, "empty trace"
  with open(traced_run["trace_path"]) as f:
    doc = json.load(f)
  assert isinstance(doc["traceEvents"], list)
  # Re-assert the schema independently of the validator's internals.
  last = None
  for ev in doc["traceEvents"]:
    assert {"ph", "name", "pid", "tid"} <= set(ev), ev
    if ev["ph"] == "M":
      continue
    assert "ts" in ev, ev
    if last is not None:
      assert ev["ts"] >= last, "non-monotonic ts"
    last = ev["ts"]
  # fit() auto-exported its own trace under the checkpoint dir too.
  validate_trace(traced_run["fit_trace_path"])


@pytest.mark.quick
def test_request_lifecycle_tracks_complete(traced_run):
  """Acceptance: every request has one complete lifecycle — submit
  instant, an admit->retire span carrying the finish reason, tiled on
  the same slot track by ONE ``serving/prefill`` and ONE
  ``serving/decode`` phase span that carry its uid, the steps that fed it
  and the tokens they fed, a first-token instant, and (since the
  same-params drafter always drafts) per-step ``speculate`` spans with
  accepted counts nested in the decode phase, which sums them."""
  events = validate_trace(traced_run["trace_path"])
  spans, unmatched = report.pair_spans(events)
  assert unmatched == 0
  by_uid = {s["args"]["uid"]: s for s in spans
            if s["cat"] == "serving.request"}
  submits = {e["args"]["uid"] for e in events
             if e.get("ph") == "i" and e["name"] == "serving/submit"}
  firsts = {e["args"]["uid"] for e in events
            if e.get("ph") == "i" and e["name"] == "serving/first_token"}
  assert set(traced_run["uids"]) <= set(by_uid)
  assert set(traced_run["uids"]) <= submits
  assert set(traced_run["uids"]) <= firsts
  speculated = 0
  for i, uid in enumerate(traced_run["uids"]):
    req = by_uid[uid]
    t0, t1 = req["ts"], req["ts"] + req["dur"]
    inner = [s for s in spans if s["tid"] == req["tid"]
             and s["name"] in ("serving/prefill", "serving/decode",
                               "speculate")
             and t0 <= s["ts"] and s["ts"] + s["dur"] <= t1 + 1e-9]
    (prefill,) = [s for s in inner if s["name"] == "serving/prefill"]
    (decode,) = [s for s in inner if s["name"] == "serving/decode"]
    assert prefill["args"]["uid"] == decode["args"]["uid"] == uid
    assert prefill["ts"] + prefill["dur"] == decode["ts"]
    assert prefill["args"]["steps"] >= 1
    assert prefill["args"]["tokens"] == len(traced_run["outputs"][uid]) \
        - (5 + i)
    # the first token is the prefill's; the rest are the decode's
    assert decode["args"]["tokens"] == 5 + i - 1
    assert 1 <= decode["args"]["steps"] <= decode["args"]["tokens"]
    assert req["args"]["finish_reason"] == "length"
    assert req["args"]["new_tokens"] >= 1
    drafted = accepted = 0
    for s in inner:
      if s["name"] == "speculate":
        assert s["args"]["drafted"] >= 1
        assert 0 <= s["args"]["accepted"] <= s["args"]["drafted"]
        assert decode["ts"] <= s["ts"]
        assert s["ts"] + s["dur"] <= decode["ts"] + decode["dur"]
        drafted += s["args"]["drafted"]
        accepted += s["args"]["accepted"]
        speculated += 1
    assert decode["args"]["drafted"] == drafted
    assert decode["args"]["accepted"] == accepted
  assert speculated > 0, "no speculate spans despite a drafting engine"
  # The per-request report rolls the same events up without error.
  timelines = {t["uid"]: t for t in report.request_timelines(events)}
  assert set(traced_run["uids"]) <= set(timelines)
  assert all(t["ttft_us"] is not None and t["prefill_chunks"] >= 1
             and t["decode_steps"] >= 1 and t["queue_wait_us"] is not None
             for t in timelines.values())


@pytest.mark.quick
def test_tracing_overhead_and_zero_recompile(traced_run):
  """Acceptance: tracing changes nothing the runtime can feel — the
  fused serving step and the fit train step each stay at ONE compiled
  program with tracing on, and traced step time is within 5% of
  untraced on the CPU mesh, judged over ~70 identical interleaved
  per-step samples per side.  Real tracing overhead taxes EVERY traced
  step, so it must show up in both the median and the floor; a shared
  2-core box instead perturbs one estimator at a time (a load phase
  shifts the median, one lucky scheduler slot shifts the min), so the
  guard passes when EITHER estimator is within budget."""
  assert traced_run["fit_step_cache"] == 1
  assert traced_run["engine_step_cache"] == 1
  assert traced_run["engine_step_cache_after_timing"] == 1
  assert len(traced_run["times_on"]) >= 50
  assert len(traced_run["times_off"]) >= 50
  on_med = statistics.median(traced_run["times_on"])
  off_med = statistics.median(traced_run["times_off"])
  on_min = min(traced_run["times_on"])
  off_min = min(traced_run["times_off"])
  within = lambda a, b: a <= b * 1.05 + 1e-4  # noqa: E731
  assert within(on_med, off_med) or within(on_min, off_min), (
      f"traced step med/min {on_med * 1e6:.0f}/{on_min * 1e6:.0f}us vs "
      f"untraced {off_med * 1e6:.0f}/{off_min * 1e6:.0f}us")


@pytest.mark.quick
def test_fit_phase_spans_and_namespaced_auto_metrics(traced_run):
  """The train loop's phases and the checkpoint stage/commit appear as
  spans, and fit() auto-built the namespaced JSONL sink (satellite:
  runs are never silently unlogged)."""
  events = validate_trace(traced_run["fit_trace_path"])
  names = {e["name"] for e in events}
  for expected in ("train/data_next", "train/step_dispatch",
                   "train/metrics_flush", "train/host_sync",
                   "checkpoint/stage", "checkpoint/commit"):
    assert expected in names, expected
  lines = [json.loads(l) for l in open(traced_run["metrics_path"])]
  assert lines, "auto metrics sink wrote nothing"
  assert all("train/loss" in l for l in lines)
  assert all(k in ("step", "time") or k.split("/")[0] in
             ("train", "serving", "comm", "resilience")
             for l in lines for k in l)


# -------------------------------------------------- request-phase spans


@pytest.fixture(scope="module")
def tiny_gpt():
  gpt = GPT(TINY)
  return gpt, gpt.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32))["params"]


@pytest.fixture
def phase_tracer():
  epl.init()
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  yield tracer
  trace_lib.reset()


def _phase_engine(tiny_gpt, *, serial=False, **kw):
  model, params = tiny_gpt
  eng = ContinuousBatchingEngine(model, params, prefill_chunk=4,
                                 **{"num_slots": 2, **kw})
  if serial:
    eng._overlap = False          # the serial loop, same compiled step
  return eng


def _request(i, n=5, new=4, **kw):
  prompt = np.random.RandomState(i).randint(0, 64, (n,)).astype(np.int32)
  return Request(uid=f"r{i}", prompt=prompt, max_new_tokens=new, **kw)


def _phases(events):
  """``{uid: {name: [span, ...]}}`` of the phase spans, and the request
  spans by uid, after the schema check."""
  spans, unmatched = report.pair_spans(validate_trace(events))
  assert unmatched == 0
  out, requests = {}, {}
  for s in spans:
    if s["name"] in ("serving/queued", "serving/prefill", "serving/decode"):
      assert s["cat"] == "serving"
      out.setdefault(s["args"]["uid"], {}).setdefault(
          s["name"], []).append(s)
    elif s["cat"] == "serving.request":
      requests.setdefault(s["args"]["uid"], []).append(s)
  return out, requests


def _end(span):
  """Where a paired span ends, to the rounding of ``ts + (end - ts)``."""
  return pytest.approx(span["ts"] + span["dur"], abs=1e-6)


@pytest.mark.parametrize("serial", [False, True],
                         ids=["overlapped", "serial"])
def test_phases_tile_submit_to_retire_under_one_uid(tiny_gpt, phase_tracer,
                                                    serial):
  """Three times more requests than slots: every request's queued,
  prefill and decode spans share its uid and flow id and tile submit ->
  retire with shared stamps, the two on the slot inside ``request
  <uid>``; their args count the steps and the tokens."""
  eng = _phase_engine(tiny_gpt, serial=serial)
  assert eng.step_overlap == "on"
  reqs = [_request(i, n=3 + 2 * i, new=3 + i) for i in range(6)]
  for r in reqs[:4]:
    eng.submit(r)
  for _ in range(3):
    eng.step()
  for r in reqs[4:]:
    eng.submit(r)
  eng.run()
  events = phase_tracer.events()
  phases, requests = _phases(events)
  submits = {e["args"]["uid"]: e["ts"] for e in events
             if e["ph"] == "i" and e["name"] == "serving/submit"}
  tracks = {e["tid"]: e["args"]["name"] for e in events
            if e["ph"] == "M" and e["name"] == "thread_name"}
  for r in reqs:
    mine = phases[r.uid]
    (queued,), (prefill,), (decode,) = (
        mine["serving/queued"], mine["serving/prefill"],
        mine["serving/decode"])
    (req,) = requests[r.uid]
    flow = {s["args"]["flow_id"] for s in (queued, prefill, decode)}
    assert len(flow) == 1
    # stamped before the submit instant, in the same call
    assert queued["ts"] <= submits[r.uid] < queued["ts"] + 1e3
    assert _end(queued) == prefill["ts"]
    assert _end(prefill) == decode["ts"]
    assert req["ts"] <= prefill["ts"]
    assert decode["ts"] + decode["dur"] <= req["ts"] + req["dur"] + 1e-6
    assert prefill["tid"] == decode["tid"] == req["tid"]
    assert tracks[queued["tid"]].startswith("serving/queue/")
    assert queued["args"]["requeues"] == 0
    assert prefill["args"]["tokens"] == len(r.prompt)
    assert prefill["args"]["steps"] == -(-len(r.prompt) // 4)
    assert decode["args"]["tokens"] == r.max_new_tokens - 1
    assert decode["args"]["steps"] == r.max_new_tokens - 1
    assert "drafted" not in decode["args"]
  # four queued at once at most: the pool is as deep as the queue was
  lanes = {n for n in tracks.values() if n.startswith("serving/queue/")}
  assert lanes == {f"serving/queue/{i}" for i in range(len(lanes))}
  assert 2 <= len(lanes) <= 4
  rows = {t["uid"]: t for t in report.request_timelines(events)}
  for r in reqs:
    row = rows[r.uid]
    assert row["queue_wait_us"] == phases[r.uid]["serving/queued"][0]["dur"]
    assert row["prefill_tokens"] == len(r.prompt)
    assert row["decode_steps"] == r.max_new_tokens - 1
  assert "wait" in report.format_report(events)


def _case_cancelled_and_expired_in_queue(tiny_gpt):
  eng = _phase_engine(tiny_gpt)
  for i in range(6):
    eng.submit(_request(i, deadline_s=1e-4 if i == 5 else 0.0))
  assert eng.cancel("r4")
  time.sleep(0.002)
  eng.run()
  return eng, {"r4": "cancelled", "r5": "deadline"}


def _case_paged_requeue(tiny_gpt):
  eng = _phase_engine(tiny_gpt, paged=True, block_size=4)
  for i in range(4):
    eng.submit(_request(i, n=6, new=6))
  for _ in range(4):
    eng.step()
  slot = next(iter(eng.scheduler.active))
  uid = eng.scheduler.requeue_slot(slot, reason="preempted")
  eng.run()
  return eng, {uid: "requeued"}


def _case_evacuated(tiny_gpt):
  eng = _phase_engine(tiny_gpt)
  for i in range(5):
    eng.submit(_request(i, new=6))
  for _ in range(4):
    eng.step()
  snaps = eng.evacuate()
  assert len(snaps) == 5 and not eng.has_work
  for snap in snaps:                      # and it serves them again
    eng.restore_request(snap)
  eng.run()
  return eng, {f"r{i}": "migrated" for i in range(5)}


@pytest.mark.parametrize("case", [
    _case_cancelled_and_expired_in_queue, _case_paged_requeue,
    _case_evacuated], ids=["cancelled-expired-in-queue", "paged-requeue",
                           "evacuated"])
def test_trace_stays_valid_through_the_lifecycle(tiny_gpt, phase_tracer,
                                                 case):
  """Queueing behind two slots with a request cancelled and one expired
  in the queue, a paged requeue, an ``evacuate()``: spans of one name
  never overlap on a track, every span closes, and the phase that ended
  early says why."""
  eng, reasons = case(tiny_gpt)
  events = phase_tracer.events()
  phases, requests = _phases(events)      # validate_trace inside
  # what the benchmark's runners assume: B/E of one (name, tid) alternate
  open_now = set()
  for ev in events:
    if ev["ph"] in "BE" and ev["name"].startswith("serving/"):
      key = (ev["name"], ev["tid"])
      assert (key in open_now) == (ev["ph"] == "E"), ev
      (open_now.remove if ev["ph"] == "E" else open_now.add)(key)
  assert not open_now
  assert sorted(eng.scheduler._free_lanes) == list(
      range(len(eng.scheduler._queue_tracks)))      # every lane given back
  for uid, why in reasons.items():
    mine = phases[uid]
    closed = [s for name in mine for s in mine[name]
              if s["args"].get("finish_reason") == why]
    assert len(closed) == 1, (uid, mine)
    if why in ("cancelled", "deadline"):
      assert closed[0]["name"] == "serving/queued" and uid not in requests
      continue
    # left its slot (or the queue) and came back: a second wait that
    # starts where the phase on the slot ended, then a second prefill
    assert len(mine["serving/queued"]) == 2
    again = mine["serving/queued"][1]
    if closed[0]["name"] != "serving/queued":
      assert len(mine["serving/prefill"]) == 2
      assert len(requests[uid]) == 2
    if why == "requeued":
      assert again["ts"] == _end(closed[0])
      assert again["args"]["requeues"] == 1
      assert closed[0]["args"]["kv_blocks"] >= 1
    assert eng.finished[uid].finish_reason == "length"


def _events_of_one_decode_step(tiny_gpt, tracer, live):
  eng = _phase_engine(tiny_gpt, num_slots=16)
  for i in range(live):
    eng.submit(_request(i, n=3, new=12))
  for _ in range(4):
    eng.step()
  assert eng.scheduler.num_active == live
  assert not any(s.prefilling for s in eng.scheduler.active.values())
  before = tracer._n_appended
  eng.step()
  return tracer._n_appended - before


def test_events_a_step_do_not_depend_on_live_slots(tiny_gpt, phase_tracer):
  two = _events_of_one_decode_step(tiny_gpt, phase_tracer, 2)
  sixteen = _events_of_one_decode_step(tiny_gpt, phase_tracer, 16)
  # plan, device_step + dispatch + fetch, commit, publish: 12 B/E events;
  # active_slots, overlapped_steps, wasted_positions, sampled_slots,
  # live_kv_rows, flat_positions, flat_trimmed, flat_narrow: 8 counters
  assert two == sixteen == 20


def test_disabled_tracer_stamps_and_keeps_nothing(tiny_gpt, monkeypatch):
  """Off, the scheduler reads the tracer's clock nowhere, takes no lane
  and makes no phase (the engine's launch stamps are what they were)."""
  epl.init()
  tracer = trace_lib.install(trace_lib.Tracer(enabled=False))
  now_us = tracer.now_us

  def stamped():
    caller = sys._getframe(1).f_code.co_filename
    assert not caller.endswith("scheduler.py"), "stamped while off"
    return now_us()

  monkeypatch.setattr(tracer, "now_us", stamped)
  try:
    eng = _phase_engine(tiny_gpt)
    for i in range(5):
      eng.submit(_request(i))
    assert all(e.queued_us is None and e.lane is None
               for e in eng.scheduler.pending)
    eng.step(), eng.step()
    assert eng.scheduler.active
    assert all(s.phase is None for s in eng.scheduler.active.values())
    eng.scheduler.requeue_slot(next(iter(eng.scheduler.active)))
    eng.cancel("r4")
    eng.run()
    assert eng.scheduler._queue_tracks == [] == eng.scheduler._free_lanes
    assert tracer.pending == 0
  finally:
    trace_lib.reset()


def test_xla_trace_anchor_is_stamped_inside_its_span(tmp_path):
  """A device capture made by the program carries one annotation whose
  start is also in the bracketing span's args, on the tracer's clock."""
  tracer = trace_lib.Tracer(enabled=True)
  with tracer.xla_trace(str(tmp_path / "xla")):
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
  (b,) = [e for e in tracer.events() if e["ph"] == "B"]
  (e,) = [e for e in tracer.events() if e["ph"] == "E"]
  assert b["name"] == "xla_trace" and b["cat"] == "xla"
  assert b["args"]["anchor"] == trace_lib.XLA_ANCHOR
  assert b["ts"] <= b["args"]["anchor_us"] <= e["ts"]
  # ... and the capture holds the annotation under that name
  (pb,) = (tmp_path / "xla").rglob("*.xplane.pb")
  data = jax.profiler.ProfileData.from_file(str(pb))
  names = {ev.name for plane in data.planes for line in plane.lines
           for ev in line.events}
  assert trace_lib.XLA_ANCHOR in names


def test_tracer_is_sync_free_under_transfer_guard():
  """No added per-step host syncs: every tracer primitive runs inside a
  device->host transfer-guard disallow region around jitted steps."""
  tracer = trace_lib.Tracer(enabled=True, ring_capacity=4096)
  f = jax.jit(lambda x: x * 2 + 1)
  y = f(jnp.ones((8, 8)))  # compile + one result outside the guard
  with jax.transfer_guard_device_to_host("disallow"):
    for i in range(20):
      with tracer.span("step", cat="train", track="train"):
        y = f(y)
      tracer.instant("tick", args={"i": i})
      tracer.counter("depth", i)
  assert f._cache_size() == 1
  assert float(y[0, 0]) != 0.0  # sync deferred past the guard


# ------------------------------------------------------------- tracer unit


def test_tracer_ring_capacity_and_dropped_count():
  tracer = trace_lib.Tracer(enabled=True, ring_capacity=4)
  for i in range(10):
    tracer.instant(f"e{i}")
  events = [e for e in tracer.events() if e["ph"] == "i"]
  assert [e["name"] for e in events] == ["e6", "e7", "e8", "e9"]
  assert tracer.dropped == 6


def test_tracer_concurrent_recording_is_consistent():
  # The watchdog monitor thread records instants while the main thread
  # records spans: track registration must never hand out a duplicate
  # tid, and the eviction accounting must not lose increments (`+=` is
  # not GIL-atomic).
  import threading
  tracer = trace_lib.Tracer(enabled=True, ring_capacity=64)
  n = 2000

  def monitor():
    for i in range(n):
      tracer.instant("timeout", track=f"watchdog {i % 7}")

  t = threading.Thread(target=monitor)
  t.start()
  for i in range(n):
    with tracer.span("step", track=f"slot {i % 7}"):
      pass
  t.join()
  total = n + 2 * n  # instants + B/E pairs
  assert tracer._n_appended == total
  assert tracer.dropped == total - len(tracer._events)
  tids = list(tracer._tracks.values())
  assert len(tids) == len(set(tids))  # no duplicate tid handed out


def test_tracer_sampling_is_deterministic():
  tracer = trace_lib.Tracer(enabled=True, sample_rate=0.5)
  kept = 0
  for _ in range(10):
    with tracer.span("s", sample=True):
      kept = sum(1 for e in tracer.events() if e["ph"] == "B")
  assert kept == 5  # exactly every other sampled span
  # Unsampled spans and a rate of 1.0 record everything.
  with tracer.span("always"):
    pass
  assert sum(1 for e in tracer.events()
             if e["ph"] == "B" and e["name"] == "always") == 1


def test_tracer_sampling_keeps_whole_steps_together():
  # fit() makes ONE sampling decision per step (sample_tick) and gates
  # every train/* phase span on it (record=) — so a sampled step keeps
  # its FULL phase set, including phases only some steps reach (host
  # sync runs on log boundaries only), instead of each span's sampling
  # aliasing against fit's fixed phase sequence.
  tracer = trace_lib.Tracer(enabled=True, sample_rate=0.25)
  all_phases = {"data_next", "step_dispatch", "host_sync"}
  recorded = []  # (step, phase) pairs that made it into the ring
  for step in range(8):
    rec = tracer.sample_tick("train")
    phases = ["data_next", "step_dispatch"]
    if step % 2 == 1:  # log-boundary-only phase
      phases.append("host_sync")
    for phase in phases:
      before = len(tracer._events)
      with tracer.span(phase, record=rec):
        pass
      if len(tracer._events) > before:
        recorded.append((step, phase))
  steps = {s for s, _ in recorded}
  assert steps == {3, 7}  # every 4th step, deterministically
  for s in steps:  # and each sampled step kept all of its phases
    assert {p for st, p in recorded if st == s} == all_phases


def test_tracer_disabled_is_noop_and_null_span_shared():
  tracer = trace_lib.Tracer(enabled=False, ring_capacity=8)
  s1 = tracer.span("a")
  s2 = tracer.span("b", sample=True)
  assert s1 is s2  # the shared null context manager: no allocation
  with s1:
    tracer.instant("x")
    tracer.counter("c", 1)
  assert not list(tracer._events)


def test_validate_trace_catches_malformed():
  with pytest.raises(ValueError, match="monotonic"):
    validate_trace([
        {"ph": "B", "name": "a", "pid": 0, "tid": 0, "ts": 2.0},
        {"ph": "E", "name": "a", "pid": 0, "tid": 0, "ts": 1.0}])
  with pytest.raises(ValueError, match="unclosed"):
    validate_trace([{"ph": "B", "name": "a", "pid": 0, "tid": 0,
                     "ts": 1.0}])
  with pytest.raises(ValueError, match="no open B"):
    validate_trace([{"ph": "E", "name": "a", "pid": 0, "tid": 0,
                     "ts": 1.0}])
  with pytest.raises(ValueError, match="missing"):
    validate_trace([{"ph": "B", "name": "a", "ts": 1.0}])
  with pytest.raises(ValueError, match="traceEvents"):
    validate_trace({"foo": []})


def test_ensure_configured_follows_config_and_explicit_install_wins():
  trace_lib.reset()
  epl.init(epl.Config({"observability.enabled": True,
                       "observability.ring_capacity": 128}))
  t1 = trace_lib.ensure_configured()
  assert t1.enabled and t1.ring_capacity == 128
  assert trace_lib.ensure_configured() is t1  # same config -> same tracer
  epl.init()  # observability off again
  assert not trace_lib.ensure_configured().enabled
  mine = trace_lib.Tracer(enabled=True, ring_capacity=16)
  trace_lib.install(mine)
  epl.init()
  assert trace_lib.ensure_configured() is mine  # explicit install wins
  trace_lib.reset()


def test_ensure_configured_foreign_config_cannot_drop_tracer():
  # A component constructed mid-run with its own explicit config (an
  # engine built with serving knobs, observability default-off there)
  # must not tear down or rebuild the run's tracer — either would
  # silently discard the recorded ring and stop every other site's
  # instrumentation.
  trace_lib.reset()
  epl.init(epl.Config({"observability.enabled": True}))
  t1 = trace_lib.ensure_configured()
  with t1.span("train/step"):
    pass
  foreign_off = epl.Config({"serving.num_slots": 2})
  assert trace_lib.ensure_configured(foreign_off) is t1
  foreign_differs = epl.Config({"observability.enabled": True,
                                "observability.ring_capacity": 32})
  assert trace_lib.ensure_configured(foreign_differs) is t1  # no rebuild
  assert len(t1._events) == 2  # the ring survived both
  # The ambient Env config still reconciles destructively as documented.
  epl.init()
  assert not trace_lib.ensure_configured().enabled
  trace_lib.reset()


# ----------------------------------------------------------- registry unit


class _ListSink:
  def __init__(self):
    self.records = []
    self.closed = False

  def write(self, step, metrics):
    self.records.append((step, dict(metrics)))

  def flush(self):
    pass

  def close(self):
    self.closed = True


def test_metric_registry_namespaces_and_schema():
  sink = _ListSink()
  reg = MetricRegistry(sink)
  reg.publish(1, {"loss": 0.5}, "train")
  reg.publish(1, {"tokens_per_s": 10.0}, "serving")
  reg.publish_many(2, {"train": {"loss": 0.4},
                       "resilience": {"bad_steps": 1},
                       "comm": {}})
  assert sink.records[0] == (1, {"train/loss": 0.5})
  assert sink.records[1] == (1, {"serving/tokens_per_s": 10.0})
  # publish_many merges namespaces into ONE record; empty ones vanish.
  assert sink.records[2] == (2, {"train/loss": 0.4,
                                 "resilience/bad_steps": 1})
  assert reg.latest()["train/loss"] == 0.4
  with pytest.raises(ValueError, match="namespace"):
    reg.publish(3, {"x": 1}, "bogus")
  # Sub-namespaces validate by their root.
  reg.publish(3, {"x": 1}, "serving/slot0")
  assert sink.records[-1] == (3, {"serving/slot0/x": 1})
  reg.close()
  assert sink.closed


def test_registry_feeds_metrics_writer_and_serving_stats(tmp_path):
  path = str(tmp_path / "m.jsonl")
  stats = ServingStats(clock=iter(range(100)).__next__)
  stats.note_submitted("a")
  stats.note_admitted("a")
  stats.note_first_token("a")
  stats.note_finished("a", 3)
  stats.note_step(1, 2, 4, 1, 0.5)
  with MetricsWriter(path) as w:
    reg = MetricRegistry(w)
    stats.publish(reg, step=7)
  (line,) = [json.loads(l) for l in open(path)]
  assert line["step"] == 7
  assert line["serving/finished_requests"] == 1.0
  assert line["serving/tokens_per_s"] > 0


def test_flops_profiler_publishes_split_namespaces():
  sink = _ListSink()
  prof = FlopsProfiler(flops_per_step=1e9, every_n_steps=1,
                       comm_bytes_per_step=1e6,
                       registry=MetricRegistry(sink))
  prof.note_bad_step(2)
  prof.step()          # first call only arms the timer
  stats = prof.step()
  assert stats is not None
  (_, record), = sink.records[-1:]
  assert "train/step_time_s" in record
  assert "comm/comm_share" in record
  assert record["resilience/bad_steps"] == 2.0


# ----------------------------------------------------- satellite coverage


def test_metrics_writer_array_summary_not_repr(tmp_path):
  """Satellite: multi-element device/np arrays flush as a compact
  {shape, dtype, mean} summary, not a multi-kilobyte str() dump."""
  path = str(tmp_path / "m.jsonl")
  big = np.arange(2048, dtype=np.float32).reshape(32, 64)
  with MetricsWriter(path) as w:
    w.write(1, {"loss": jnp.float32(0.5), "grads_debug": big,
                "device_vec": jnp.arange(3.0), "note": "hello"})
  (line,) = [json.loads(l) for l in open(path)]
  assert line["loss"] == 0.5
  assert line["grads_debug"] == {"shape": [32, 64], "dtype": "float32",
                                 "mean": pytest.approx(1023.5)}
  assert line["device_vec"]["shape"] == [3]
  assert line["note"] == "hello"
  # The compact record is ~60 bytes; the old repr was thousands.
  assert len(json.dumps(line["grads_debug"])) < 200


def test_tensorboard_writer_missing_dep_actionable(monkeypatch):
  """Satellite: absent tensorboardX raises at CONSTRUCTION with
  install guidance, instead of silently dropping metrics later."""
  monkeypatch.setitem(sys.modules, "tensorboardX", None)
  from easyparallellibrary_tpu.utils.metrics_writer import (
      TensorBoardWriter)
  with pytest.raises(ImportError, match="tensorboardX"):
    TensorBoardWriter(logdir="/tmp/unused_tb")


def test_serving_stats_empty_and_reset_windows():
  """Satellite: summary() on a fresh or reset window never raises and
  degrades every rollup to 0.0."""
  stats = ServingStats()
  empty = stats.summary()
  assert empty["steps"] == 0.0
  assert empty["tokens_per_s"] == 0.0
  assert empty["ttft_p99_s"] == 0.0
  assert empty["acceptance_rate"] == 0.0
  assert all(isinstance(v, float) for v in empty.values())
  stats.note_submitted("a")
  stats.note_finished("a", 2)
  stats.note_step(1, 2, 0, 1, 0.1, drafted_tokens=2, accepted_tokens=1)
  assert stats.summary()["generated_tokens"] == 2.0
  stats.reset()
  assert stats.summary() == empty


def test_report_cli_prints_breakdown(traced_run, capsys):
  """`python -m easyparallellibrary_tpu.observability.report <trace>`
  prints the span table and per-request timelines."""
  assert report.main([traced_run["trace_path"]]) == 0
  out = capsys.readouterr().out
  assert "prefill" in out
  assert "req0" in out
  assert "finish" in out
  assert "serving/device_step" in out
