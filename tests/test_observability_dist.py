"""Cross-process distributed tracing (ISSUE 20): child trace harvest,
clock-aligned fleet timelines, end-to-end latency decomposition.

The acceptance contract: with two ProcessTransport
replicas — each recording into its OWN tracer ring — SIGKILL of one
mid-decode still yields ONE merged schema-valid Perfetto trace in which
the failed-over request is a single connected flow spanning the parent
and BOTH child pids, with per-pid monotonic rebased timestamps.  The
fault-free guard: harvest fully enabled changes nothing — streams stay
bit-exact vs the oracle, every fused step compiled once — and a CLEANLY
drained replica's spans ALL appear in the merged trace (the satellite
bugfix: child replicas used to exit without exporting a single span).

The units pin the harvest substrate (drain_wire byte bounds and
delivered-vs-dropped accounting, ingest_remote rebase + per-pid
monotonic clamp + malformed-event tolerance, per-pid export metadata),
the validator's new multi-process negatives, and report.py's hop
decomposition columns.
"""

import json
import os
import signal
import time

import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.observability import report
from easyparallellibrary_tpu.observability import slo as slo_lib
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.observability.trace import (
    Tracer, validate_trace)
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, Request, Router)
from easyparallellibrary_tpu.testing import chaos
from easyparallellibrary_tpu.testing.factories import tiny_gpt

FACTORY = {"fn": "easyparallellibrary_tpu.testing.factories:tiny_gpt"}


@pytest.fixture(autouse=True)
def _drop_ambient_observability():
  yield
  trace_lib.reset()
  slo_lib.reset()


def _prompts(n, plen=6, vocab=64, seed=0):
  r = np.random.RandomState(seed)
  return [r.randint(0, vocab, (plen,)).astype(np.int32)
          for _ in range(n)]


def _oracle_outputs(prompts, max_new=10):
  model, params = tiny_gpt()
  eng = ContinuousBatchingEngine(model, params, num_slots=4,
                                 prefill_chunk=4)
  for i, p in enumerate(prompts):
    eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
  out = eng.run()
  eng.close()
  return out


def _dist_config(**router):
  conf = {"transport": "process", "rpc_timeout_s": 60.0,
          "rpc_retries": 2, "rpc_backoff_s": 0.05}
  conf.update(router)
  return epl.Config({"serving": {"router": conf},
                     "observability": {"enabled": True}})


def _assert_no_orphans(pids):
  time.sleep(0.1)
  for pid in pids:
    if pid is None:
      continue
    try:
      os.kill(pid, 0)
    except ProcessLookupError:
      continue
    pytest.fail(f"orphan replica child still alive: pid {pid}")


def _flows(events):
  out = {}
  for ev in events:
    if ev.get("ph") in ("s", "t", "f"):
      out.setdefault(ev["id"], []).append(ev)
  return out


# ------------------------------------------------- harvest substrate


def test_drain_wire_bounded_sweeps_and_accounting():
  """drain_wire consumes OLDEST-first within a byte budget; drained
  events count as delivered (not dropped), the remainder rides later
  sweeps, and ``None`` empties the ring."""
  t = Tracer(ring_capacity=1024)
  for i in range(50):
    t.instant(f"ev{i}", cat="x", args={"i": i})
  assert t.pending == 50 and t.dropped == 0
  chunk = t.drain_wire(256)
  assert chunk["events"], "a sweep within budget must make progress"
  assert len(chunk["events"]) < 50, "256 bytes cannot hold 50 events"
  names = [w[1] for w in chunk["events"]]
  assert names[0] == "ev0", "oldest events leave first"
  assert sum(len(json.dumps(w, separators=(",", ":"), default=str))
             for w in chunk["events"]) <= 256
  assert t.dropped == 0, "drained events were delivered, not dropped"
  rest = t.drain_wire(None)
  assert [w[1] for w in rest["events"]][-1] == "ev49"
  assert t.pending == 0
  assert len(chunk["events"]) + len(rest["events"]) == 50


def test_drain_wire_first_event_always_fits():
  """An event larger than the sweep budget still drains (one per
  sweep) — a single oversized args blob must not wedge the harvest."""
  t = Tracer(ring_capacity=16)
  t.instant("big", args={"blob": "x" * 4096})
  t.instant("after")
  chunk = t.drain_wire(64)
  assert [w[1] for w in chunk["events"]] == ["big"]
  assert [w[1] for w in t.drain_wire(64)["events"]] == ["after"]


def test_ingest_remote_rebases_and_clamps_monotonic():
  """Rebased child timestamps stay per-pid monotonic even when the
  re-estimated clock offset steps BACKWARDS between chunks."""
  parent = Tracer(ring_capacity=64)
  parent.ingest_remote(7, [["i", "a", "", 100.0, "main", None]],
                       offset_us=1000.0)
  # Offset re-estimated 500us lower: a naive rebase would send ts
  # backwards on pid 7; the clamp pins it at the high-water mark.
  parent.ingest_remote(7, [["i", "b", "", 110.0, "main", None]],
                       offset_us=500.0)
  parent.ingest_remote(7, [["i", "c", "", 2000.0, "main", None]],
                       offset_us=500.0)
  ts = [e["ts"] for e in parent.events()
        if e.get("ph") == "i" and e["pid"] == 7]
  assert ts == [1100.0, 1100.0, 2500.0]
  validate_trace(parent.events())


def test_ingest_remote_skips_malformed_events():
  parent = Tracer(ring_capacity=64)
  n = parent.ingest_remote(
      7, [["i", "good", "", 1.0, "main", None],
          ["i", "short"],                      # wrong arity
          "not-a-list",
          ["i", "good2", "", 2.0, "main", None]],
      offset_us=0.0)
  assert n == 2
  assert parent.remote_summary()[7]["events"] == 2


def test_merged_export_per_pid_tracks_and_metadata():
  """A drained child ring re-emerges in the parent export under the
  child's pid with its OWN track table (names preserved, tids
  re-assigned per pid) plus process_name metadata — and the merged
  trace passes the validator."""
  child = Tracer(ring_capacity=64)
  with child.span("serving/prefill", cat="serving", track="serving/slot0"):
    child.instant("serving/first_token", cat="serving",
                  args={"uid": "7"})
  child.flow("t", 42, track="serving/requests")
  child.flow("f", 42, track="serving/requests")
  parent = Tracer(ring_capacity=64)
  parent.flow("s", 42, track="serving/requests")
  moved = 0
  while child.pending:  # tiny budget: force multi-sweep reassembly
    moved += parent.ingest_remote(
        4242, child.drain_wire(150)["events"], offset_us=1e6,
        label="replica0 worker (pid 4242)")
  assert moved == 5 and child.pending == 0
  events = validate_trace(parent.events())
  proc_names = {e["pid"]: e["args"]["name"] for e in events
                if e.get("ph") == "M" and e["name"] == "process_name"}
  assert proc_names[4242] == "replica0 worker (pid 4242)"
  remote_tracks = {e["args"]["name"] for e in events
                   if e.get("ph") == "M" and e["name"] == "thread_name"
                   and e["pid"] == 4242}
  assert {"serving/slot0", "serving/requests"} <= remote_tracks
  # The flow arcs across the process boundary: s on the parent pid,
  # t/f on the child pid, one shared id.
  (evs,) = _flows(events).values()
  assert [e["ph"] for e in evs] == ["s", "t", "f"]
  assert evs[0]["pid"] != evs[1]["pid"]


def test_close_remote_ends_dangling_spans_at_death():
  """A SIGKILLed child's harvested ring ends in open ``B`` events;
  close_remote synthesizes their ``E`` at the pid's last rebased
  timestamp (LIFO, tagged with the death reason), idempotently — so
  the merged trace validates and renders the victim's work ending at
  the kill."""
  parent = Tracer(ring_capacity=64)
  parent.ingest_remote(7, [
      ["B", "request 3", "serving.request", 100.0, "slot0", None],
      ["B", "serving/decode", "serving", 120.0, "slot0", None],
      ["i", "tick", "", 130.0, "slot0", None],
  ], offset_us=0.0)
  with pytest.raises(ValueError, match="unclosed span"):
    validate_trace(parent.events())
  assert parent.close_remote(7, reason="killed") == 2
  events = validate_trace(parent.events())
  ends = [e for e in events if e["ph"] == "E"]
  assert [e["name"] for e in ends] == ["serving/decode", "request 3"]
  assert all(e["ts"] == 130.0 for e in ends)
  assert all(e["args"]["finish_reason"] == "killed" for e in ends)
  assert parent.close_remote(7) == 0, "idempotent"


# ------------------------------------- validator: multi-process rules


def _base(pid, ts, ph="i", name="x", tid=0, **extra):
  ev = {"ph": ph, "name": name, "pid": pid, "tid": tid, "ts": ts}
  ev.update(extra)
  return ev


def test_validator_accepts_interleaved_pids_each_monotonic():
  """A merged trace interleaves processes whose clocks are only
  offset-aligned: global ts order across pids is NOT required, only
  per-pid monotonicity."""
  validate_trace([
      _base(0, 100.0), _base(7, 50.0), _base(0, 200.0),
      _base(7, 60.0)])  # pid0: 100,200; pid7: 50,60 — unsorted, valid


def test_validator_flags_per_pid_nonmonotonic():
  with pytest.raises(ValueError, match=r"not monotonic"):
    validate_trace([_base(7, 100.0), _base(0, 10.0), _base(7, 90.0)])


def test_validator_flags_flow_step_without_start():
  """A child pid's harvested ``t`` whose ``s`` never made it (or was
  emitted with a different id) is a broken arc, not a valid trace."""
  with pytest.raises(ValueError, match=r"no open flow start"):
    validate_trace([
        _base(0, 1.0, ph="s", name="flow", cat="serving", id=5),
        _base(7, 2.0, ph="t", name="flow", cat="serving", id=6),
        _base(0, 3.0, ph="f", name="flow", cat="serving", id=5)])


def test_validator_flags_flow_cat_mismatch():
  """Viewers match flows by category + id: a cross-process step that
  disagrees on cat silently severs the arc, so the validator names it."""
  with pytest.raises(ValueError, match=r"flows bind by cat \+ id"):
    validate_trace([
        _base(0, 1.0, ph="s", name="flow", cat="serving", id=5),
        _base(7, 2.0, ph="t", name="flow", cat="other", id=5),
        _base(0, 3.0, ph="f", name="flow", cat="serving", id=5)])


def test_validator_flags_duplicate_pid_track_metadata():
  """A merge bug that emits one pid's track table twice corrupts
  Perfetto's row labels."""
  meta = {"ph": "M", "name": "thread_name", "pid": 7, "tid": 3,
          "args": {"name": "serving/slot0"}}
  with pytest.raises(ValueError, match=r"duplicate thread_name"):
    validate_trace([meta, dict(meta)])
  # Same tid on DIFFERENT pids is two distinct tracks — fine.
  validate_trace([meta, {**meta, "pid": 8}])


# ------------------------------------------- report: hop decomposition


def test_report_hop_breakdown_columns():
  """Front-door instants turn into the hop columns: client-observed
  TTFT (request -> first byte), ingress (request -> router submit) and
  wire (engine first token -> first byte) — and traces WITHOUT them
  keep the old table shape."""
  uid = "r1"
  events = [
      _base(0, 100.0, name="frontdoor/request", args={"uid": uid}),
      _base(0, 200.0, name="serving/submit", args={"uid": uid}),
      _base(7, 300.0, ph="B", name="req r1", tid=5,
            cat="serving.request", args={"uid": uid}),
      _base(7, 310.0, ph="B", name="serving/prefill", tid=5, cat="serving",
            args={"uid": uid, "steps": 2, "tokens": 7}),
      _base(7, 350.0, ph="E", name="serving/prefill", tid=5, cat="serving"),
      _base(7, 350.0, name="serving/first_token", args={"uid": uid}),
      _base(7, 350.0, ph="B", name="serving/decode", tid=5, cat="serving",
            args={"uid": uid, "steps": 3, "tokens": 3}),
      _base(7, 400.0, ph="E", name="serving/decode", tid=5, cat="serving"),
      _base(7, 400.0, ph="E", name="req r1", tid=5,
            cat="serving.request", args={"finish_reason": "stop"}),
      _base(0, 460.0, name="frontdoor/first_byte", args={"uid": uid}),
  ]
  (row,) = report.request_timelines(events)
  assert row["queue_wait_us"] == 100.0
  assert row["ingress_us"] == 100.0
  assert row["client_ttft_us"] == 360.0
  assert row["wire_us"] == 110.0
  assert row["prefill_us"] == 40.0 and row["decode_us"] == 50.0
  assert (row["prefill_chunks"], row["prefill_tokens"]) == (2, 7)
  assert (row["decode_steps"], row["decode_tokens"]) == (3, 3)
  text = report.format_report(events)
  assert "fd-ttft" in text and "wire" in text
  assert "360us" in text
  # Engine-only trace: hop columns stay hidden.
  plain = report.format_report(events[2:-1])
  assert "fd-ttft" not in plain and "wire" not in plain


def test_report_inner_spans_keyed_by_pid_and_tid():
  """Two processes reuse the same tid for different tracks; a request's
  inner phase spans must only match within its OWN pid."""
  events = [
      _base(7, 100.0, ph="B", name="req a", tid=5,
            cat="serving.request", args={"uid": "a"}),
      # Same tid, same window, DIFFERENT pid: must not be attributed
      # to request "a".
      _base(8, 110.0, ph="B", name="serving/prefill", tid=5, cat="serving",
            args={"uid": "a", "steps": 1, "tokens": 4}),
      _base(8, 150.0, ph="E", name="serving/prefill", tid=5, cat="serving"),
      _base(7, 200.0, ph="E", name="req a", tid=5,
            cat="serving.request", args={"finish_reason": "stop"}),
  ]
  (row,) = report.request_timelines(events)
  assert row["prefill_us"] == 0.0 and row["prefill_chunks"] == 0


# --------------------------------------- the acceptance: real processes


@pytest.mark.quick
def test_process_sigkill_merged_trace_single_connected_flow(tmp_path):
  """ISSUE 20 acceptance: SIGKILL one of two process replicas
  mid-decode, then export ONE merged Perfetto trace — schema-valid
  with per-pid monotonic rebased timestamps — in which a failed-over
  request is a single connected flow spanning the parent and BOTH
  child pids."""
  config = _dist_config()
  epl.init(config)
  tracer = trace_lib.ensure_configured()
  prompts = _prompts(6)
  router = Router(num_replicas=2, config=config, factory=FACTORY,
                  num_slots=4, prefill_chunk=4)
  pids = [rep.child_pid for rep in router.replicas]
  for i, p in enumerate(prompts):
    assert router.submit(Request(uid=i, prompt=p, max_new_tokens=10))
  for _ in range(3):            # let decode get going on both children
    router.step()
  victim = router.replicas[0]
  assert victim.has_work, "victim must die MID-decode, not idle"
  victim_pid, survivor_pid = pids
  chaos.ProcessKiller(victim).kill()
  router.run()
  assert router.failovers >= 1
  assert victim.exit_signal == signal.SIGKILL
  assert set(router.finished) == set(range(len(prompts)))
  # Explicit drain of the survivor's ring remainder, then export.
  router.harvest_traces()
  assert router.router_counters()["trace_events_harvested"] > 0
  router.close()
  trace_path = str(tmp_path / "trace.json")
  assert tracer.export(trace_path)
  events = validate_trace(trace_path)

  event_pids = {e["pid"] for e in events if e.get("ph") != "M"}
  assert {0, victim_pid, survivor_pid} <= event_pids, \
      "merged trace must carry the parent and BOTH children"
  # The SIGKILL lost at most the victim's un-harvested tail: its admit
  # window DID ride earlier step-reply piggybacks.
  spanning = [fid for fid, evs in _flows(events).items()
              if {0, victim_pid, survivor_pid}
              <= {e["pid"] for e in evs}]
  assert spanning, "no failed-over flow touches parent + both children"
  for fid in spanning:
    phases = [e["ph"] for e in _flows(events)[fid]]
    assert phases[0] == "s" and phases[-1] == "f", (fid, phases)
  _assert_no_orphans(pids)


@pytest.mark.quick
def test_process_fault_free_harvest_bit_exact_clean_drain(tmp_path):
  """The fault-free guard + the satellite bugfix pin: with harvest
  fully enabled on ``transport=process``, streams are bit-identical to
  the fault-free oracle and the fused step compiled once — and a
  cleanly closed replica's spans ALL appear in the merged trace (the
  shutdown reply carries the ring remainder; no explicit harvest call
  needed)."""
  prompts = _prompts(4)
  oracle = _oracle_outputs(prompts)
  config = _dist_config()
  epl.init(config)
  tracer = trace_lib.ensure_configured()
  router = Router(num_replicas=1, config=config, factory=FACTORY,
                  num_slots=4, prefill_chunk=4)
  pid = router.replicas[0].child_pid
  for i, p in enumerate(prompts):
    assert router.submit(Request(uid=i, prompt=p, max_new_tokens=10))
  out = router.run()
  assert router.replicas[0].compile_count == 1, \
      "harvest must add zero recompiles"
  assert set(out) == set(oracle)
  for uid in oracle:
    np.testing.assert_array_equal(np.asarray(out[uid]), oracle[uid],
                                  err_msg=f"req {uid}")
  router.close()               # clean exit: shutdown reply flushes all
  trace_path = str(tmp_path / "trace.json")
  assert tracer.export(trace_path)
  events = validate_trace(trace_path)
  child_request_spans = {
      (e["args"] or {}).get("uid") for e in events
      if e.get("ph") == "B" and e.get("cat") == "serving.request"
      and e["pid"] == pid}
  assert child_request_spans == {str(i) for i in range(len(prompts))}, \
      "every request's child-side span must reach the merged trace"
  # Every started flow terminated — and each request's arc touches
  # both processes (s at the router, t/f on the child).
  for fid, evs in _flows(events).items():
    assert {e["pid"] for e in evs} == {0, pid}, fid
  _assert_no_orphans([pid])
