"""Event-driven front door (ISSUE 19): reactor router core + streaming
HTTP/SSE surface with backpressure and cancel-on-disconnect.

The acceptance contract (`make chaos-frontdoor`):

* the reactor (serving/reactor.py) is BIT-EXACT with the sweep — an
  in-process N=1 fleet produces identical token streams under either
  driver with zero added recompiles, and kill-one-of-two under the
  reactor fails over bit-exactly with the survivor's fused step still
  compiled once;
* the HTTP/SSE stream byte-assembles to exactly what a direct
  ``submit()`` returns — tokens surface per engine iteration via the
  scheduler's ``on_tokens`` push (never by polling ``finished``);
* a client that disconnects mid-stream cancels its request (reason
  ``"cancelled"``, slot and blocks freed, trace flow finalized, no
  stats double-count), and a reader too slow for its bounded queue
  sheds ONLY its own flow;
* under real process faults (SIGKILL / SIGSTOP) behind the reactor,
  zero requests are lost and none double-served.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.serving import Request, Router
from easyparallellibrary_tpu.serving.frontdoor import (
    FrontDoor, generate as fd_generate, healthz, stream_generate)
from easyparallellibrary_tpu.serving.frontdoor.server import _StreamState
from easyparallellibrary_tpu.serving.reactor import RouterReactor
from easyparallellibrary_tpu.serving.scheduler import FinishedRequest
from easyparallellibrary_tpu.testing import chaos

TINY = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)
FACTORY = {"fn": "easyparallellibrary_tpu.testing.factories:tiny_gpt"}


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


def _config(reactor=True, **frontdoor):
  conf = {"serving": {"router": {"reactor": reactor}}}
  if frontdoor:
    conf["serving"]["frontdoor"] = frontdoor
  return epl.Config(conf)


def _wait_for(predicate, timeout_s=15.0, interval_s=0.02):
  deadline = time.monotonic() + timeout_s
  while time.monotonic() < deadline:
    if predicate():
      return True
    time.sleep(interval_s)
  return predicate()


# ------------------------------------------- reactor: sweep equivalence


@pytest.mark.quick
def test_reactor_inproc_n1_bit_exact_with_sweep_zero_recompile():
  """Tentpole pin 1: the reactor over an in-process N=1 fleet is a pure
  re-cadencing of the SAME engine steps — token streams bit-identical
  to the sweep driver (and the generate() oracle) with the one fused
  step still compiled ONCE under either driver."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 9, 2))
  max_new = (6, 7, 4, 5)

  def drive(router, step_once, run):
    for i in range(2):
      assert router.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=max_new[i]))
    out = {}
    for _ in range(2):
      for fin in step_once():
        out[fin.uid] = fin.tokens
    for i in range(2, 4):                       # staggered second wave
      assert router.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=max_new[i]))
    out.update(run())
    return out

  sweep = Router(model, params, num_replicas=1, num_slots=2,
                 prefill_chunk=4, config=_config(reactor=False))
  swept = drive(sweep, sweep.step, sweep.run)

  rrouter = Router(model, params, num_replicas=1, num_slots=2,
                   prefill_chunk=4, config=_config(reactor=True))
  reactor = rrouter.reactor()
  assert isinstance(reactor, RouterReactor)
  assert rrouter.reactor() is reactor            # cached, one per router
  reacted = drive(rrouter, reactor.cycle, rrouter.run)

  for router in (sweep, rrouter):
    assert router.replicas[0].engine._step_fn._cache_size() == 1, \
        "the reactor must add ZERO recompiles"
    assert router.failovers == 0 and router.states() == ["healthy"]
  assert reactor.cycles > 0 and reactor.dispatched > 0
  assert sorted(swept) == sorted(reacted) == list(range(4))
  for i in range(4):
    np.testing.assert_array_equal(reacted[i], swept[i],
                                  err_msg=f"req {i}")
    np.testing.assert_array_equal(
        reacted[i], _oracle(model, params, prompts[i], max_new[i]))
    assert rrouter.finished[i].finish_reason == "length"


@pytest.mark.quick
def test_replica_kill_under_reactor_bit_exact_failover():
  """Tentpole pin 2: kill one of two in-process replicas mid-decode
  UNDER THE REACTOR — failover runs the same unmodified router
  machinery, every request finishes with the exact oracle stream, and
  the survivor's fused step stays compiled once."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 9, 2), seed=8)
  router = Router(model, params, num_replicas=2, num_slots=2,
                  prefill_chunk=4, config=_config(reactor=True))
  killer = chaos.ReplicaKiller(router.replicas[0].engine,
                               kill_calls=(3,))
  for i, p in enumerate(prompts):
    assert router.submit(Request(uid=i, prompt=p, max_new_tokens=6))
  assert {router.placement[i] for i in range(4)} == {0, 1}
  out = router.run()                       # delegates to the reactor
  assert router.reactor().cycles > 0
  assert killer.kills == 1
  assert router.failovers == 1 and router.migrated_requests == 2
  assert router.states() == ["down", "healthy"]
  assert router.replicas[1].engine._step_fn._cache_size() == 1, \
      "failover under the reactor must not recompile the survivor"
  assert len(router.finished) == 4
  for i, p in enumerate(prompts):
    assert router.finished[i].finish_reason == "length"
    np.testing.assert_array_equal(out[i], _oracle(model, params, p, 6),
                                  err_msg=f"req {i}")
  fleet = router.fleet_summary()
  assert fleet["finished_requests"] == 4.0      # nothing double-counted
  assert fleet["failovers"] == 1.0


# ------------------------------------------------ HTTP/SSE equivalence


@pytest.mark.quick
def test_http_sse_stream_assembles_to_direct_submit():
  """Tentpole pin 3: the HTTP/SSE stream byte-assembles to exactly the
  tokens a direct ``submit()`` produces — per-iteration push events
  (the on_tokens feed), then one ``done`` — over the real socket."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 7), seed=3)
  max_new = (8, 6, 5)

  direct = Router(model, params, num_replicas=1, num_slots=2,
                  prefill_chunk=4, config=_config(reactor=False))
  for i, p in enumerate(prompts):
    assert direct.submit(Request(uid=i, prompt=p, max_new_tokens=max_new[i]))
  direct_out = direct.run()

  router = Router(model, params, num_replicas=1, num_slots=2,
                  prefill_chunk=4, config=_config(reactor=True))
  with FrontDoor(router) as fd:
    assert healthz(fd.address)["states"] == ["healthy"]
    for i, p in enumerate(prompts):
      events = list(stream_generate(
          fd.address, {"uid": f"h{i}", "prompt": [int(t) for t in p],
                       "max_new_tokens": max_new[i]}))
      token_events = [d for e, d in events if e == "token"]
      dones = [d for e, d in events if e == "done"]
      assert len(dones) == 1, "exactly one done event per stream"
      assert dones[0]["finish_reason"] == "length"
      assert dones[0]["new_tokens"] == max_new[i]
      assert not dones[0]["truncated"]
      # Per-iteration push: one token event per engine iteration that
      # committed for this request — never one big final batch.
      assert len(token_events) > 1
      streamed = [t for d in token_events for t in d["tokens"]]
      assembled = [int(t) for t in p] + streamed
      np.testing.assert_array_equal(
          assembled, direct_out[i],
          err_msg=f"stream h{i} must byte-assemble to direct submit")
    assert fd.streamed_events >= sum(max_new) - len(max_new)
  assert router.replicas[0].engine._step_fn._cache_size() == 1


def test_header_mapping_and_request_validation():
  """X-Deadline-S / X-TTFT-Budget-S / X-Priority map onto the
  scheduler's Request fields (headers win over body fields), malformed
  requests get 400s, and a shed admission surfaces as a ``done`` with
  reason ``"shed"`` — all over the real socket."""

  class FakeRouter:
    def __init__(self):
      self.on_tokens = []
      self.finished = {}
      self.captured = []
      self.steps = 0
      self.has_work = False

    def submit(self, request):
      self.captured.append(request)
      prompt = np.asarray(request.prompt, np.int32)
      self.finished[request.uid] = FinishedRequest(
          uid=request.uid, tokens=prompt, new_tokens=0,
          finish_reason="shed")
      return False

    def cancel(self, uid):
      return False

    def step(self):
      return []

    def states(self):
      return ["healthy"]

  router = FakeRouter()
  with FrontDoor(router, config=_config(reactor=False)) as fd:
    toks, done = fd_generate(
        fd.address,
        {"prompt": [1, 2, 3], "max_new_tokens": 4, "deadline_s": 9.0,
         "temperature": 0.5, "top_k": 7, "seed": 11},
        headers={"X-Deadline-S": "2.5", "X-TTFT-Budget-S": "0.75",
                 "X-Priority": "latency"})
    assert toks == [] and done["finish_reason"] == "shed"
    (req,) = router.captured
    assert req.deadline_s == 2.5          # header wins over body's 9.0
    assert req.ttft_budget_s == 0.75
    assert req.priority == "latency"
    assert req.max_new_tokens == 4 and req.temperature == 0.5
    assert req.top_k == 7 and req.seed == 11
    np.testing.assert_array_equal(req.prompt, [1, 2, 3])

    for body, hdrs in [
        ({"prompt": [], "max_new_tokens": 4}, None),
        ({"prompt": "not-ids"}, None),
        ({"prompt": [1, 2]}, {"X-Priority": "urgent"}),
        ({"prompt": [1, 2]}, {"X-Deadline-S": "soon"}),
    ]:
      with pytest.raises(RuntimeError, match="HTTP 400"):
        list(stream_generate(fd.address, body, headers=hdrs))


# ------------------------------------- cancel-on-disconnect + shedding


def test_cancel_on_disconnect_frees_slot_and_finalizes_flow():
  """Satellite 3: a client that drops mid-stream cancels its request —
  retired with reason ``"cancelled"``, slot and blocks freed, trace
  flow finalized with the cancel reason, and the fleet counts the
  request exactly once."""
  epl.init()
  tracer = trace_lib.install(
      trace_lib.Tracer(enabled=True, ring_capacity=4096))
  try:
    model, params = _model_and_params()
    (prompt,) = _prompts((6,), seed=5)
    router = Router(model, params, num_replicas=1, num_slots=2,
                    prefill_chunk=4,
                    config=_config(reactor=True, keepalive_s=0.1,
                                   write_timeout_s=2.0))
    engine = router.replicas[0].engine
    # Pace the engine (~25ms/step) so the drop lands MID-stream.
    chaos.HangingStepInjector(engine, hang_calls=range(1, 500),
                              hang_s=0.025)
    with FrontDoor(router) as fd:
      client = chaos.DisconnectingClient(
          fd.address,
          {"uid": "gone", "prompt": [int(t) for t in prompt],
           "max_new_tokens": 24},
          after_events=2, rst=True)
      client.start()
      client.join(timeout=30.0)
      assert client.dropped and client.error is None
      assert 2 <= client.events_seen < 24
      assert _wait_for(
          lambda: router.finished.get("gone") is not None
          and router.finished["gone"].finish_reason == "cancelled"), \
          "disconnect must cancel the request within a keepalive beat"
      fin = router.finished["gone"]
      assert fin.finish_reason == "cancelled"
      assert 0 < fin.new_tokens < 24
      assert _wait_for(lambda: not engine.has_work)
      assert engine.scheduler.active == {}, "slot must be freed"
      assert fd.disconnect_cancels == 1
    assert router.fleet_summary()["finished_requests"] == 1.0, \
        "a cancelled stream must not double-count"
    finishes = [e for e in tracer.events() if e.get("ph") == "f"
                and e.get("args", {}).get("uid") == "gone"]
    assert finishes, "the request's trace flow must be finalized"
    assert finishes[-1]["args"]["reason"] == "cancelled"
  finally:
    trace_lib.reset()


def test_slow_reader_overflow_sheds_only_its_flow():
  """Satellite 2 core invariant: a reader that never drains its bounded
  queue overflows it; the front door cancels THAT uid after the cycle
  (never reentrantly inside commit) while a concurrently streaming
  neighbour finishes bit-exactly."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((6, 5), seed=7)
  oracle = _oracle(model, params, prompts[1], 8)
  # The front door's own bound stays at its default: the NEIGHBOUR's
  # reader is a real client thread, and under a loaded machine a bound of
  # 2 batches sheds it too (it then never streamed bit-exactly, and
  # before the shed path kicked a cycle, never got its ``done`` event:
  # the intermittent hang of ROADMAP D9).  Only the stuck flow is bounded
  # at 2, by hand.
  router = Router(model, params, num_replicas=1, num_slots=2,
                  prefill_chunk=4, config=_config(reactor=True))
  with FrontDoor(router) as fd:
    # An infinitely slow reader, as the server sees one: its stream
    # state exists but nothing ever drains the queue.
    stuck = _StreamState("stuck", prompt_len=len(prompts[0]), buffer=2)
    with fd._streams_lock:
      fd._streams["stuck"] = stuck
    fd._commands.put(("submit", Request(
        uid="stuck", prompt=prompts[0], max_new_tokens=16), stuck))
    assert stuck.admitted.wait(timeout=30.0) and stuck.accepted

    toks, done = fd_generate(
        fd.address, {"uid": "ok", "prompt": [int(t) for t in prompts[1]],
                     "max_new_tokens": 8})
    assert done["finish_reason"] == "length"
    np.testing.assert_array_equal(
        [int(t) for t in prompts[1]] + toks, oracle,
        err_msg="the neighbour of a shed flow must stream bit-exactly")

    assert _wait_for(
        lambda: router.finished.get("stuck") is not None
        and router.finished["stuck"].finish_reason == "cancelled"), \
        "queue overflow must shed the slow flow"
    assert stuck.overflow
    assert fd.overflow_sheds == 1
    assert stuck.final is not None
    assert stuck.final["finish_reason"] == "cancelled"
    # The bound held: never more batches buffered than configured.
    assert stuck.queue.qsize() <= 2
  assert router.fleet_summary()["finished_requests"] == 2.0


# ------------------------------------ chaos suite (make chaos-frontdoor)


def _serve_clients(fd, prompts, max_new, start=0):
  """Drive one HTTP generate() per prompt from its own thread; returns
  uid -> (streamed_tokens, done) plus any per-thread error."""
  results, errors = {}, {}

  def one(i, p):
    uid = f"c{start + i}"
    try:
      results[uid] = fd_generate(
          fd.address, {"uid": uid, "prompt": [int(t) for t in p],
                       "max_new_tokens": max_new}, timeout=120.0)
    except Exception as e:      # noqa: BLE001 — recorded for the assert
      errors[uid] = e

  threads = [threading.Thread(target=one, args=(i, p), daemon=True)
             for i, p in enumerate(prompts)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=120.0)
  return results, errors


def _process_config(**over):
  conf = {"serving": {"router": {
      "transport": "process", "reactor": True, "rpc_timeout_s": 60.0,
      "rpc_retries": 2, "rpc_backoff_s": 0.05}}}
  conf["serving"]["router"].update(over)
  return epl.Config(conf)


@pytest.mark.slow
def test_chaos_frontdoor_sigkill_under_reactor_zero_lost():
  """`make chaos-frontdoor` headline: SIGKILL one of two process
  replicas mid-episode behind the reactor-driven front door — every
  connected client still byte-assembles its exact oracle stream (zero
  lost), each stream resolves exactly once (zero double-served), and a
  disconnecting client's request is cancelled, not resurrected."""
  from easyparallellibrary_tpu.testing.factories import tiny_gpt
  model, params = tiny_gpt()
  prompts = _prompts((6, 6, 6, 6), seed=11)
  oracle = {f"c{i}": _oracle(model, params, p, 10)
            for i, p in enumerate(prompts)}
  router = Router(num_replicas=2, config=_process_config(),
                  factory=FACTORY, num_slots=4, prefill_chunk=4)
  victim = router.replicas[0]
  with FrontDoor(router) as fd:
    killer_fired = threading.Event()

    def kill_soon():
      _wait_for(lambda: victim.has_work, timeout_s=60.0)
      chaos.ProcessKiller(victim).kill()
      killer_fired.set()

    threading.Thread(target=kill_soon, daemon=True).start()
    results, errors = _serve_clients(fd, prompts, max_new=10)
    assert killer_fired.wait(timeout=60.0)
    assert not errors, f"no client may error through the kill: {errors}"
    assert set(results) == set(oracle), "zero lost requests"
    for uid, (toks, done) in results.items():
      assert done["finish_reason"] == "length", uid
      prompt = [int(t) for t in prompts[int(uid[1:])]]
      np.testing.assert_array_equal(prompt + toks, oracle[uid],
                                    err_msg=uid)
    assert router.failovers >= 1
  # Exactly-once fleet-wide: one resolution per uid, none double-served.
  assert sorted(router.finished) == sorted(oracle)
  assert router.fleet_summary()["finished_requests"] == float(len(oracle))
  router.close()


@pytest.mark.slow
def test_chaos_frontdoor_sigstop_hang_under_reactor_heals():
  """SIGSTOP (a genuinely frozen child — the straggler case the
  reactor's wire deadline must surface): the condemned replica is
  fenced and failed over, every client still completes bit-exactly,
  and a SlowReader trickling its own stream harms no neighbour."""
  from easyparallellibrary_tpu.testing.factories import tiny_gpt
  model, params = tiny_gpt()
  prompts = _prompts((6, 6, 6), seed=13)
  oracle = {f"c{i}": _oracle(model, params, p, 8)
            for i, p in enumerate(prompts)}
  router = Router(num_replicas=2,
                  config=_process_config(rpc_timeout_s=3.0),
                  factory=FACTORY, num_slots=4, prefill_chunk=4)
  victim = router.replicas[0]
  with FrontDoor(router) as fd:
    (slow_prompt,) = _prompts((5,), seed=14)
    slow = chaos.SlowReader(
        fd.address, {"uid": "slow", "prompt": [int(t) for t in slow_prompt],
                     "max_new_tokens": 4},
        read_bytes=16, interval_s=0.05, duration_s=60.0)
    slow.start()

    def stall_soon():
      _wait_for(lambda: victim.has_work, timeout_s=60.0)
      staller = chaos.ProcessStaller(victim)
      staller.stall()

    threading.Thread(target=stall_soon, daemon=True).start()
    results, errors = _serve_clients(fd, prompts, max_new=8)
    assert not errors, f"no client may error through the stall: {errors}"
    for uid, (toks, done) in results.items():
      prompt = [int(t) for t in prompts[int(uid[1:])]]
      np.testing.assert_array_equal(prompt + toks, oracle[uid],
                                    err_msg=uid)
    # The slow reader's own flow resolves too — served or shed, never
    # lost, never harming the neighbours asserted above.
    assert _wait_for(lambda: "slow" in router.finished, timeout_s=60.0)
    assert router.finished["slow"].finish_reason in ("length",
                                                     "cancelled")
    assert _wait_for(lambda: router.failovers >= 1, timeout_s=60.0), \
        "the frozen child must be condemned and failed over"
  router.close()


# ------------------------------------ trace-context propagation (W3C)


def test_traceparent_parse_and_mint_units():
  """Strict W3C parsing: a valid header decomposes, a minted header
  round-trips, and every malformed shape is a ValueError (the 400
  path) — never a silently broken trace."""
  from easyparallellibrary_tpu.serving.frontdoor.server import (
      flow_id_from_trace_id, mint_traceparent, parse_traceparent)
  tid, pid, flags = parse_traceparent(
      "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01")
  assert tid == "4bf92f3577b34da6a3ce929d0e0e4736"
  assert pid == "00f067aa0ba902b7" and flags == "01"
  # flow_id keeps the trace-id's low 53 bits (exact as a JSON number).
  assert flow_id_from_trace_id(tid) == int(tid, 16) & ((1 << 53) - 1)
  minted = mint_traceparent(12345)
  tid2, _, _ = parse_traceparent(minted)
  assert flow_id_from_trace_id(tid2) == 12345
  for bad in [
      "",                                                  # empty
      "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7",  # 3 parts
      "zz-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",
      "ff-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",
      "00-4bf92f3577b34da6-00f067aa0ba902b7-01",           # short tid
      "00-4BF92F3577B34DA6A3CE929D0E0E4736-00f067aa0ba902b7-01",
      "00-" + "0" * 32 + "-00f067aa0ba902b7-01",           # zero tid
      "00-4bf92f3577b34da6a3ce929d0e0e4736-" + "0" * 16 + "-01",
      "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-0g",
  ]:
    with pytest.raises(ValueError):
      parse_traceparent(bad)


def test_traceparent_propagation_echo_and_400_over_socket():
  """Over the real socket: a caller's ``traceparent`` maps onto the
  submitted Request's flow_id and is echoed back verbatim beside
  ``X-Request-Id``; an absent header gets a minted one carrying the
  flow id; a malformed header is a 400, not a broken trace."""
  from easyparallellibrary_tpu.serving.frontdoor.client import _post
  from easyparallellibrary_tpu.serving.frontdoor.server import (
      flow_id_from_trace_id, parse_traceparent)

  class FakeRouter:
    def __init__(self):
      self.on_tokens = []
      self.finished = {}
      self.captured = []
      self.has_work = False

    def submit(self, request):
      self.captured.append(request)
      self.finished[request.uid] = FinishedRequest(
          uid=request.uid, tokens=np.asarray(request.prompt, np.int32),
          new_tokens=0, finish_reason="shed")
      return False

    def cancel(self, uid):
      return False

    def step(self):
      return []

    def states(self):
      return ["healthy"]

  header = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"
  want_flow = flow_id_from_trace_id("4bf92f3577b34da6a3ce929d0e0e4736")
  router = FakeRouter()
  with FrontDoor(router, config=_config(reactor=False)) as fd:
    resp = _post(fd.address, {"uid": "tp-1", "prompt": [1, 2, 3],
                              "max_new_tokens": 2},
                 {"traceparent": header}, timeout=30.0)
    assert resp.status == 200
    assert resp.getheader("X-Request-Id") == "tp-1"
    assert resp.getheader("traceparent") == header
    resp.read()
    resp.close()
    (req,) = router.captured
    assert req.flow_id == want_flow

    # Absent header: the front door mints one carrying the flow id it
    # assigned, so the caller can still join its logs to the trace.
    resp = _post(fd.address, {"uid": "tp-2", "prompt": [4, 5],
                              "max_new_tokens": 2}, None, timeout=30.0)
    assert resp.status == 200
    minted = resp.getheader("traceparent")
    resp.read()
    resp.close()
    tid, _, _ = parse_traceparent(minted)
    assert flow_id_from_trace_id(tid) == router.captured[-1].flow_id
    assert router.captured[-1].flow_id  # really minted, non-zero

    for bad in ["garbage", "00-dead-beef-01",
                "ff-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"]:
      with pytest.raises(RuntimeError, match="HTTP 400"):
        list(stream_generate(fd.address, {"prompt": [1]},
                             headers={"traceparent": bad}))
    assert len(router.captured) == 2, "malformed headers never submit"
