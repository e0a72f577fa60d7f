"""One serving replica: an engine plus the host-side plumbing the
router needs to treat it as a fleet member.

A replica is a :class:`~easyparallellibrary_tpu.serving.engine.
ContinuousBatchingEngine` with its own scheduler, KV cache, compiled
fused step, watchdog and :class:`~easyparallellibrary_tpu.profiler.
serving.ServingStats` — replicas share NOTHING but the params source
(the same sharded arrays; params are read-only in serving, so N engines
can hold the same reference).  On top of the engine this class adds:

* **heartbeat material** — every :meth:`step` returns normally or
  raises; the router converts the former into a health beat carrying
  the live signals the step already produced on the host (cumulative
  watchdog-timeout and bad-step counters, the ITL EWMA) and the latter
  into ``mark_down`` + failover.  The replica itself holds no health
  state — policy lives in :class:`serving.resilience.ReplicaHealth`,
  mechanics here.
* **load signals** — ``queue_depth`` / ``num_active`` / ``load`` for
  least-loaded dispatch (the same occupancy/queue gauges the engine
  already publishes through the metric registry).
* **a per-replica metric namespace** — the engine's ``serving/*``
  registry records are re-rooted to ``serving/replica<i>/*`` via a thin
  proxy, so one registry shows every replica side by side plus the
  router's ``serving/fleet/*`` rollup (docs/observability.md).
* **migration endpoints** — :meth:`snapshot_requests` /
  :meth:`restore_request` / :meth:`evacuate` delegate to the engine's
  bit-exact prefix-replay machinery (scheduler.snapshot_requests).

Hosting note: by default the router drives replicas in-process and
synchronously (one ``step()`` sweep per router step — deterministic and
test-friendly), via :class:`serving.transport.InprocTransport`.  With
``serving.router.transport = "process"`` the SAME class runs inside a
spawned worker process that owns its own JAX runtime — the
:func:`replica_worker_main` serve loop at the bottom of this module
answers the parent's :class:`serving.transport.ProcessTransport` over a
length-prefixed-JSON socketpair, which is the real fault domain: a
SIGKILL takes exactly one replica's memory, and failover recovers from
the router-side journal, not from this process.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.serving.engine import ContinuousBatchingEngine
from easyparallellibrary_tpu.serving.scheduler import (
    FinishedRequest, Request)


class _ReplicaRegistry:
  """Registry proxy re-rooting ``serving`` → ``serving/replica<i>``.

  The engine and its ServingStats publish under the ``serving``
  namespace unconditionally; wrapping the registry (instead of teaching
  them a prefix parameter) keeps every existing producer untouched while
  per-replica records land under their own sub-namespace — the schema
  already allows sub-namespaces (observability/registry.py)."""

  def __init__(self, inner, index: int):
    self._inner = inner
    self._prefix = f"serving/replica{index}"

  def publish(self, step: int, metrics, namespace: str = "train"):
    if namespace == "serving":
      namespace = self._prefix
    elif namespace.startswith("serving/"):
      namespace = self._prefix + namespace[len("serving"):]
    self._inner.publish(step, metrics, namespace)

  def __getattr__(self, name):
    return getattr(self._inner, name)


class EngineReplica:
  """One fleet member: engine + stats + migration endpoints.

  ``engine_kwargs`` pass through to :class:`ContinuousBatchingEngine`
  (num_slots, prefill_chunk, drafter, resilience, paged, ...).  A
  ``stats`` object is always attached (built here when the caller
  passes none) — the router's health beats and the fleet rollup read
  it.  ``registry`` (optional) is wrapped per-replica; pass the SAME
  registry to every replica and the router.
  """

  def __init__(self, index: int, model, params, *, mesh=None,
               registry=None, config=None, stats=None, **engine_kwargs):
    self.index = index
    if stats is None and engine_kwargs.get("stats") is None:
      from easyparallellibrary_tpu.profiler.serving import ServingStats
      stats = ServingStats()
    if stats is not None:
      engine_kwargs["stats"] = stats
    # Per-replica Perfetto tracks (serving/replica<i>/slot N) so a
    # failed-over request's flow arc visibly crosses replica tracks
    # instead of two replicas' slot 0 sharing one row.
    engine_kwargs.setdefault("track_prefix", f"serving/replica{index}")
    self.engine = ContinuousBatchingEngine(
        model, params, mesh=mesh, config=config,
        registry=(_ReplicaRegistry(registry, index)
                  if registry is not None else None),
        **engine_kwargs)
    self.stats = self.engine.stats
    self.steps = 0

  # ------------------------------------------------------------- serving

  def submit(self, request: Request) -> bool:
    return self.engine.submit(request)

  def cancel(self, uid: Any) -> bool:
    return self.engine.cancel(uid)

  def step(self) -> List[FinishedRequest]:
    """One engine iteration (cheap when idle).  Raises whatever the
    engine raises — the router treats an escaping exception as this
    replica dying mid-step."""
    fins = self.engine.step()
    self.steps += 1
    return fins

  @property
  def has_work(self) -> bool:
    return self.engine.has_work

  @property
  def scheduler(self):
    """The engine's scheduler — the subscriber-list hook point
    (``on_admit``/``on_first_token``/``on_tokens``/``on_finish``) the
    router's stream fanout and the sim fleet both attach to."""
    return self.engine.scheduler

  @property
  def finished(self) -> Dict[Any, FinishedRequest]:
    return self.engine.finished

  # -------------------------------------------------------- load signals

  @property
  def queue_depth(self) -> int:
    return self.engine.scheduler.queue_depth

  @property
  def num_active(self) -> int:
    return self.engine.scheduler.num_active

  @property
  def num_slots(self) -> int:
    return self.engine.num_slots

  @property
  def load(self) -> int:
    """Requests this replica is responsible for (active + queued) — the
    least-loaded dispatch key."""
    return self.num_active + self.queue_depth

  @property
  def checkpoint_version(self) -> int:
    """The checkpoint version this replica's params came from
    (blue/green rollout, serving/rollout.py; 0 pre-rollout).  The
    router reads it for version-aware dispatch and version-gated
    failover placement."""
    return self.engine.checkpoint_version

  # ------------------------------------------------------ health signals

  @property
  def watchdog_timeouts(self) -> int:
    return self.stats.watchdog_timeouts if self.stats is not None else 0

  @property
  def bad_steps(self) -> int:
    return self.stats.bad_steps if self.stats is not None else 0

  @property
  def itl_ewma_s(self) -> float:
    return self.stats.itl_ewma_s if self.stats is not None else 0.0

  # ---------------------------------------------------------- migration

  def snapshot_requests(self) -> List[Dict[str, Any]]:
    return self.engine.snapshot_requests()

  def restore_request(self, snap: Dict[str, Any],
                      front: bool = False) -> Any:
    return self.engine.restore_request(snap, front=front)

  def evacuate(self) -> List[Dict[str, Any]]:
    return self.engine.evacuate()

  # ----------------------------------------------------------- lifecycle

  def close(self):
    self.engine.close()

  def __repr__(self):
    return (f"EngineReplica({self.index}, active={self.num_active}, "
            f"queued={self.queue_depth})")


# ---------------------------------------------------------- worker main --
#
# `python -m easyparallellibrary_tpu.serving.replica --worker-fd N` is
# the child half of serving/transport.py's ProcessTransport: a spawned
# process owning its own JAX runtime, answering length-prefixed JSON
# frames over the socketpair fd it inherited.  Pure host plumbing — the
# engine underneath is byte-for-byte the in-process one.


def _install_pdeathsig() -> None:
  """Ask Linux to SIGKILL this worker the instant its parent dies
  (PR_SET_PDEATHSIG) — the kernel-level half of orphan prevention; the
  pipe-EOF exit below is the portable half."""
  try:
    import ctypes
    libc = ctypes.CDLL("libc.so.6", use_errno=True)
    PR_SET_PDEATHSIG = 1
    libc.prctl(PR_SET_PDEATHSIG, 9)  # SIGKILL
  except Exception:  # pragma: no cover - non-Linux / no libc
    pass


class _WorkerServer:
  """Dispatch loop state for one worker process."""

  def __init__(self, sock):
    from easyparallellibrary_tpu.serving import transport as transport_lib
    self._t = transport_lib
    self.sock = sock
    self.reader = transport_lib.FrameReader(sock)
    self.replica: Optional[EngineReplica] = None
    self._first_tokens: List[Any] = []
    # Cross-process trace harvest (docs/observability.md "Distributed
    # tracing"): when the parent's config enables the tracer, this
    # child records into its OWN ring and the parent drains it in
    # bounded chunks riding step replies, plus a final flush on the
    # shutdown/evacuate paths.  0 bytes = harvest off.
    self.tracer: Optional[Any] = None
    self._harvest_bytes = 0
    # Idempotency dedup: uid -> recorded reply result.  A submit or
    # restore retried after an ambiguous timeout (the reply was lost
    # AFTER this process applied the call) returns the recorded
    # verdict instead of admitting the request twice.
    self._applied: Dict[Any, Dict[str, Any]] = {}

  # ------------------------------------------------------------- handlers

  def _beat(self) -> Dict[str, Any]:
    rep = self.replica
    if rep is None:
      return {}
    try:
      compiles = int(rep.engine._step_fn._cache_size())
    except Exception:
      compiles = 0
    beat = {
        "watchdog_timeouts": int(rep.watchdog_timeouts),
        "bad_steps": int(rep.bad_steps),
        "itl_ewma_s": float(rep.itl_ewma_s),
        "queue_depth": int(rep.queue_depth),
        "num_active": int(rep.num_active),
        "num_slots": int(rep.num_slots),
        "load": int(rep.load),
        "has_work": bool(rep.has_work),
        "compiles": compiles,
        "checkpoint_version": int(rep.checkpoint_version),
        "pid": os.getpid(),
    }
    if self.tracer is not None and self.tracer.enabled:
      # The parent pairs this with its send/recv perf_counter_ns stamps
      # to estimate the cross-process clock offset (midpoint method) —
      # every reply is a fresh sample, re-sampled on the heartbeat
      # cadence parent-side.
      beat["trace_now_us"] = self.tracer.now_us()
    return beat

  def do_init(self, p: Dict[str, Any]) -> Dict[str, Any]:
    wire = int(p.get("wire_version", -1))
    if wire != self._t.WIRE_VERSION:
      raise ValueError(
          f"wire version mismatch: parent speaks v{wire}, this worker "
          f"speaks v{self._t.WIRE_VERSION} — parent and child must run "
          f"the same build")
    import jax
    import easyparallellibrary_tpu as epl
    config = epl.Config(p.get("config") or {})
    epl.init(config)
    # The parent's observability config crossed the wire inside the
    # init frame: configure this child's OWN tracer ring from it, so
    # child-side spans exist for the parent to harvest.  flow_id rides
    # every Request snapshot (scheduler wire shape v2+), so the spans
    # recorded here join the SAME request flow the parent started.
    tracer = trace_lib.ensure_configured(config)
    obs = config.observability
    if tracer.enabled and obs.harvest.enabled:
      self.tracer = tracer
      self._harvest_bytes = int(obs.harvest.max_bytes_per_sweep)
    fn, kwargs = self._t.resolve_factory(p["factory"])
    model, params = fn(**kwargs)
    checkpoint = p.get("checkpoint")
    if checkpoint:
      # Blue/green rollout (serving/rollout.py): this child serves a
      # SPECIFIC checkpoint, not the factory's params.  restore_params
      # walks the checksum-validated chain and verifies the stored
      # params fingerprint/geometry against the factory tree, so a
      # half-written or mismatched checkpoint fails the init RPC with a
      # clear error instead of an XLA shape crash mid-decode.
      from easyparallellibrary_tpu.runtime.saver import restore_params
      params, _ = restore_params(checkpoint, target=params)
    self.replica = EngineReplica(
        int(p.get("index", 0)), model, params, config=config,
        **(p.get("engine_kwargs") or {}))
    self.replica.engine.scheduler.on_first_token.append(
        self._first_tokens.append)
    return {"pid": os.getpid(),
            "platform": jax.devices()[0].platform}

  def do_submit(self, p: Dict[str, Any]) -> Dict[str, Any]:
    req = Request.restore(p["snap"])
    if req.uid in self._applied:
      return self._applied[req.uid]
    accepted = self.replica.submit(req)
    result: Dict[str, Any] = {"accepted": bool(accepted)}
    if not accepted:
      fin = self.replica.finished.get(req.uid)
      if fin is not None:
        result["finished"] = self._t.encode_finished(fin)
    self._applied[req.uid] = result
    return result

  def do_restore(self, p: Dict[str, Any]) -> Dict[str, Any]:
    uid = p["snap"]["request"]["uid"]
    if uid in self._applied and self._applied[uid].get("restored"):
      return self._applied[uid]
    self.replica.restore_request(p["snap"], front=bool(p.get("front")))
    result = {"accepted": True, "restored": True, "uid": uid}
    self._applied[uid] = result
    return result

  def do_cancel(self, p: Dict[str, Any]) -> Dict[str, Any]:
    return {"cancelled": bool(self.replica.cancel(p["uid"]))}

  def do_step(self, p: Dict[str, Any]) -> Dict[str, Any]:
    acked = {uid: int(n) for uid, n in p.get("acked", ())}
    fins = self.replica.step()
    progress = []
    order = []
    for uid, gen in self.replica.engine.scheduler.progress():
      order.append(uid)
      start = min(acked.get(uid, 0), len(gen))
      progress.append([uid, start, [int(t) for t in gen[start:]]])
    # A finished request frees its dedup slot — uids may be reused
    # across episodes, and the dedup map must not grow unboundedly.
    for fin in fins:
      self._applied.pop(fin.uid, None)
    # Shed verdicts free at the NEXT step: the parent is synchronous —
    # by the time it sends a step, every earlier submit's retry loop
    # has resolved — so the retry window is over, and keeping the
    # verdict would permanently reject a legitimately reused uid (and
    # leak one entry per shed under sustained overload).
    for uid in [u for u, v in self._applied.items()
                if not v.get("accepted")]:
      self._applied.pop(uid, None)
    # Drain IN PLACE: the scheduler hook holds this exact list object.
    first = list(self._first_tokens)
    self._first_tokens.clear()
    out = {"finished": [self._t.encode_finished(f) for f in fins],
           "progress": progress, "order": order, "first": first}
    if self._harvest_bytes:
      # Incremental trace harvest piggybacks on the step reply, bounded
      # bytes per sweep so it can never stall dispatch; the ring
      # remainder rides later sweeps or the final flush.
      chunk = self.tracer.drain_wire(self._harvest_bytes)
      if chunk["events"]:
        out["trace"] = chunk
    return out

  def do_snapshot(self, p: Dict[str, Any]) -> Dict[str, Any]:
    return {"snaps": self.replica.snapshot_requests()}

  def do_evacuate(self, p: Dict[str, Any]) -> Dict[str, Any]:
    snaps = self.replica.evacuate()
    for snap in snaps:
      self._applied.pop(snap["request"]["uid"], None)
    result: Dict[str, Any] = {"snaps": snaps}
    # A graceful evacuation usually precedes a fence: flush the whole
    # ring now so a drained replica's spans all reach the merged trace.
    chunk = self._final_flush()
    if chunk is not None:
      result["trace"] = chunk
    return result

  def do_stats(self, p: Dict[str, Any]) -> Dict[str, Any]:
    stats = self.replica.stats
    return {"stats": stats.state_dict() if stats is not None else None}

  def do_ping(self, p: Dict[str, Any]) -> Dict[str, Any]:
    return {"pong": True}

  def do_harvest(self, p: Dict[str, Any]) -> Dict[str, Any]:
    """Explicit low-priority harvest sweep: drain up to ``max_bytes``
    of the tracer ring (the configured sweep bound when unspecified;
    ``drain=True`` empties it)."""
    if self.tracer is None:
      return {"done": True}
    if p.get("drain"):
      max_bytes = None
    else:
      max_bytes = int(p.get("max_bytes") or self._harvest_bytes or 65536)
    chunk = self.tracer.drain_wire(max_bytes)
    out: Dict[str, Any] = {"done": not self.tracer.pending}
    if chunk["events"]:
      out["trace"] = chunk
    return out

  def _final_flush(self) -> Optional[Dict[str, Any]]:
    """The whole ring remainder, for the shutdown/evacuate replies —
    a cleanly exiting worker loses nothing (the satellite bugfix: child
    replicas used to exit without exporting a single span)."""
    if self.tracer is None:
      return None
    chunk = self.tracer.drain_wire(None)
    return chunk if chunk["events"] else None

  # ----------------------------------------------------------- serve loop

  def serve(self) -> int:
    handlers = {
        "init": self.do_init, "submit": self.do_submit,
        "restore": self.do_restore, "cancel": self.do_cancel,
        "step": self.do_step, "snapshot": self.do_snapshot,
        "evacuate": self.do_evacuate, "stats": self.do_stats,
        "ping": self.do_ping, "harvest": self.do_harvest,
    }
    while True:
      try:
        frame = self.reader.read(None)
      except self._t.ReplicaDeadError:
        # Parent gone (pipe EOF): exit now rather than orphan — the
        # prctl death signal is the backstop, this is the portable path.
        # Best-effort final trace flush: the socket is usually fully
        # dead here, but a parent that only shut down its write side
        # can still receive the ring remainder.
        chunk = self._final_flush()
        if chunk is not None:
          try:
            self._t.send_frame(self.sock, {
                "id": None, "m": "trace_flush", "ok": True,
                "result": {"trace": chunk}, "beat": self._beat()})
          except OSError:
            pass
        break
      rid, method = frame.get("id"), frame.get("m")
      if method == "shutdown":
        # Clean exit loses no trace events: the shutdown reply carries
        # the whole ring remainder (the parent's close() ingests it
        # before reaping this process).
        result: Dict[str, Any] = {}
        chunk = self._final_flush()
        if chunk is not None:
          result["trace"] = chunk
        self._reply(rid, method, {"ok": True, "result": result})
        break
      handler = handlers.get(method)
      try:
        if handler is None:
          raise ValueError(f"unknown transport method {method!r}")
        result = handler(frame.get("p") or {})
        self._reply(rid, method, {"ok": True, "result": result})
      except Exception as e:  # noqa: BLE001 — report, don't die: the
        # parent decides whether an error is fatal (its router treats a
        # step error as replica death and evacuates gracefully).
        self._reply(rid, method,
                    {"ok": False, "error": str(e),
                     "etype": type(e).__name__})
    if self.replica is not None:
      self.replica.close()
    return 0

  def _reply(self, rid, method, body: Dict[str, Any]) -> None:
    body["id"] = rid
    body["m"] = method
    body["beat"] = self._beat()
    try:
      self._t.send_frame(self.sock, body)
    except OSError:
      raise self._t.ReplicaDeadError("parent went away mid-reply")


def replica_worker_main(fd: int) -> int:
  """Entry point for the spawned replica worker (transport child)."""
  _install_pdeathsig()
  import socket as socket_lib
  sock = socket_lib.socket(fileno=fd)
  try:
    return _WorkerServer(sock).serve()
  finally:
    try:
      sock.close()
    except OSError:
      pass


if __name__ == "__main__":
  import argparse
  parser = argparse.ArgumentParser(
      description="serving replica worker (spawned by ProcessTransport; "
                  "not a user-facing CLI)")
  parser.add_argument("--worker-fd", type=int, required=True)
  raise SystemExit(replica_worker_main(parser.parse_args().worker_fd))
