"""Fault-injection harness — the adversary the resilience layer is
tested against.

Every fault class the resilience subsystem claims to survive has an
injector here, so `tests/test_resilience.py` (and `make chaos`) can
exercise the real recovery paths instead of mocking them:

* checkpoint corruption — :func:`corrupt_shard`, :func:`corrupt_index`
  (bit-flip / truncate / delete, after the save committed);
* numeric poison — :class:`NaNInjector` (NaN batches at chosen steps),
  :func:`nan_batch`;
* transient IO — :class:`FlakyIterator` (data `next()` raising
  `IOError` N times before succeeding), :func:`flaky` (same for any
  callable);
* preemption — :class:`SigtermInjector` (deliver SIGTERM to the current
  process mid-`fit`, from inside the data stream).

Serving-side faults (`tests/test_serving_resilience.py`, `make
chaos-serve`) — the adversaries of serving/resilience.py:

* NaN logits — :class:`NaNLogitsInjector` wraps a serving engine's
  fused step and swaps in fully-NaN params for chosen device calls, so
  the model GENUINELY produces non-finite logits (the in-jit finiteness
  verdict sees the real thing, not a mock) with identical
  shapes/dtypes/shardings — no recompile;
* hung steps — :class:`HangingStepInjector` (sleep before chosen
  dispatches, tripping the serving watchdog);
* flaky drafters — :class:`FlakyDrafter` (a Drafter wrapper raising or
  proposing garbage on chosen calls — the engine must degrade, and
  verification must keep outputs exact);
* overload — :func:`poisson_trace` (Poisson arrival offsets for
  admission-control / shedding episodes).

Router-fleet faults (`tests/test_serving_router.py`, `make
chaos-router`) — the adversaries of serving/router.py's control plane.
:class:`ReplicaKiller`, :class:`ReplicaHang` and
:class:`FlappingHealth` are **in-process simulations** (they poison the
fused step of a thread-hosted replica — fast, deterministic, GIL-bound);
their real-process counterparts below deliver actual signals:

* replica death — :class:`ReplicaKiller` (in-process simulation: a
  fused-step dispatch raises mid-decode; the router must fail the
  replica's queued + in-flight requests over to survivors bit-exactly
  via prefix replay);
* replica hangs — :class:`ReplicaHang` (in-process simulation: stalled
  dispatches age the heartbeat; the health machine must mark the
  replica suspect, route around it, and recover on a clean beat);
* flapping health — :class:`FlappingHealth` (in-process simulation:
  periodic death/recovery; the circuit breaker must double its
  hold-out per trip instead of bouncing requests through endless
  failovers).

Process-transport faults (`tests/test_serving_transport.py`, `make
chaos-proc`) — the REAL fault domain, against
serving/transport.py's process-isolated replicas:

* process death — :class:`ProcessKiller` (``os.kill(pid, SIGKILL)`` on
  a replica's child: one replica's memory genuinely vanishes; recovery
  must come from the router-side journal, bit-exactly);
* process stalls — :class:`ProcessStaller` (``SIGSTOP``/``SIGCONT``: a
  genuinely frozen child — no GIL sharing — that must trip the wire
  deadline, be condemned, fenced and failed over);
* lost replies — :class:`ReplyDropper` (reads a reply frame off the
  wire and discards it: the ambiguous-timeout case — the child applied
  the call but the parent never heard — that uid dedup and journal
  watermark resync must make exactly-once).

Front-door client faults (`tests/test_serving_frontdoor.py`, `make
chaos-frontdoor`) — the adversaries of serving/frontdoor/'s streaming
HTTP surface, driven over REAL sockets against a live listener:

* slow readers — :class:`SlowReader` (drains its SSE stream a byte at
  a time with long pauses: the bounded per-connection queue must
  overflow and shed ONLY that flow, never a neighbour's);
* vanishing clients — :class:`DisconnectingClient` (consumes a few
  token events then drops the connection — optionally with an RST
  instead of a FIN: the front door must cancel the request, freeing
  its slot and cache blocks, within one keepalive interval).

These mutate real files, deliver real signals and poison real device
calls; none of them are imported by library code.
"""

from __future__ import annotations

import os
import signal as _signal
import socket as _socket
import struct
import threading
import time
from typing import Any, Callable, Dict, Iterable, Iterator, Optional, \
    Sequence, Tuple

import jax
import numpy as np


# -------------------------------------------------- checkpoint corruption --


def _shard_files(ckpt_dir: str) -> list:
  names = sorted(f for f in os.listdir(ckpt_dir) if f.endswith(".npz"))
  if not names:
    raise FileNotFoundError(f"no shard files under {ckpt_dir}")
  return names


def corrupt_shard(ckpt_dir: str, shard: int = 0, mode: str = "flip",
                  offset: int = -64) -> str:
  """Damage one committed shard file.  `mode`:

  * ``"flip"`` — XOR a byte at `offset` (bit-rot; size unchanged, so
    only the checksum can catch it),
  * ``"truncate"`` — drop the trailing half (crash mid-write on a
    non-atomic filesystem),
  * ``"delete"`` — remove the file.

  Returns the path of the damaged shard.
  """
  path = os.path.join(ckpt_dir, _shard_files(ckpt_dir)[shard])
  if mode == "delete":
    os.remove(path)
    return path
  size = os.path.getsize(path)
  if mode == "truncate":
    with open(path, "r+b") as f:
      f.truncate(max(1, size // 2))
    return path
  if mode == "flip":
    pos = offset % size
    with open(path, "r+b") as f:
      f.seek(pos)
      byte = f.read(1)
      f.seek(pos)
      f.write(bytes([byte[0] ^ 0xFF]))
    return path
  raise ValueError(f"unknown corruption mode {mode!r}")


def corrupt_index(ckpt_dir: str, mode: str = "truncate") -> str:
  """Damage a checkpoint's ``index.json``: ``"truncate"`` (the classic
  crash-mid-write artifact), ``"garbage"`` (unparsable bytes), or
  ``"delete"``."""
  path = os.path.join(ckpt_dir, "index.json")
  if mode == "delete":
    os.remove(path)
  elif mode == "truncate":
    with open(path, "r+b") as f:
      f.truncate(max(1, os.path.getsize(path) // 3))
  elif mode == "garbage":
    with open(path, "wb") as f:
      f.write(b"\x00not json\xff")
  else:
    raise ValueError(f"unknown corruption mode {mode!r}")
  return path


# ------------------------------------------------------- numeric poison --


def nan_batch(batch):
  """A copy of `batch` with every floating leaf fully NaN."""
  def poison(x):
    arr = np.asarray(x)
    if np.issubdtype(arr.dtype, np.floating):
      return np.full_like(arr, np.nan)
    return x
  return jax.tree_util.tree_map(poison, batch)


class NaNInjector:
  """Wrap a per-step batch source, poisoning chosen steps with NaNs.

  ``batch_fn(step) -> batch`` provides the clean stream; steps listed in
  `bad_steps` come out poisoned.  With ``once=True`` (default) each bad
  step is poisoned only the FIRST time it is drawn — a replay after a
  rollback sees clean data, modeling a transient corruption upstream.
  Use as a `fit` data factory: it accepts ``start_step`` so resume and
  rollback replays line the stream up with the step index.
  """

  def __init__(self, batch_fn: Callable[[int], Any],
               bad_steps: Sequence[int], num_steps: int,
               once: bool = True):
    self.batch_fn = batch_fn
    self.bad_steps = set(bad_steps)
    self.num_steps = num_steps
    self.once = once
    self.poisoned: list = []

  def __call__(self, start_step: int = 0) -> Iterator[Any]:
    def gen():
      for step in range(start_step, self.num_steps):
        batch = self.batch_fn(step)
        if step in self.bad_steps:
          if self.once:
            self.bad_steps.discard(step)
          self.poisoned.append(step)
          batch = nan_batch(batch)
        yield batch
    return gen()


# -------------------------------------------------------- transient IO --


class FlakyIterator:
  """Iterator raising a transient exception `failures` times at position
  `fail_at` before yielding that element — the data-side fault
  `fit`'s retrying `next()` must absorb."""

  def __init__(self, items: Iterable[Any], fail_at: int = 0,
               failures: int = 1,
               exc_factory: Callable[[], BaseException] = lambda:
               IOError("chaos: transient read failure")):
    self._items = list(items)
    self.fail_at = fail_at
    self.failures_left = failures
    self.exc_factory = exc_factory
    self._pos = 0

  def __iter__(self):
    return self

  def __next__(self):
    if self._pos >= len(self._items):
      raise StopIteration
    if self._pos == self.fail_at and self.failures_left > 0:
      self.failures_left -= 1
      raise self.exc_factory()
    item = self._items[self._pos]
    self._pos += 1
    return item


def flaky(fn: Callable, failures: int = 1,
          exc_factory: Callable[[], BaseException] = lambda:
          IOError("chaos: transient failure")) -> Callable:
  """Wrap `fn` to raise a transient exception on its first `failures`
  calls, then behave normally — for driving utils/retry paths."""
  state = {"left": failures}

  def wrapped(*args, **kwargs):
    if state["left"] > 0:
      state["left"] -= 1
      raise exc_factory()
    return fn(*args, **kwargs)

  wrapped.chaos_state = state
  return wrapped


# ---------------------------------------------------------- preemption --


class SigtermInjector:
  """Iterable delivering SIGTERM to the current process when batch
  `at_batch` (0-based) is drawn, then continuing to yield — so `fit`
  observes the preemption flag on its next loop iteration, finishes the
  in-flight step, checkpoints, and exits, exactly like a scheduler
  preemption."""

  def __init__(self, batch: Any, at_batch: int = 3,
               max_batches: int = 10_000):
    self.batch = batch
    self.at_batch = at_batch
    self.max_batches = max_batches
    self._drawn = 0

  def __iter__(self):
    return self

  def __next__(self):
    if self._drawn >= self.max_batches:
      raise StopIteration
    if self._drawn == self.at_batch:
      os.kill(os.getpid(), _signal.SIGTERM)
    self._drawn += 1
    return self.batch


# ------------------------------------------------------- serving faults --


class _StepFnWrapper:
  """Base for fused-step interceptors: installs itself over
  ``engine._step_fn``, counts device calls, and forwards compile-cache
  introspection so the chaos tests' ``_cache_size() == 1`` acceptance
  assertions see THROUGH the wrapper to the one jitted program."""

  def __init__(self, engine):
    self.engine = engine
    self.inner = engine._step_fn
    self.calls = 0
    engine._step_fn = self

  def _cache_size(self) -> int:
    return self.inner._cache_size()

  def uninstall(self):
    self.engine._step_fn = self.inner


class NaNLogitsInjector(_StepFnWrapper):
  """Poison chosen fused-step calls so the model GENUINELY computes
  non-finite logits — the in-jit finiteness verdict judges real device
  output, not a mock.

  Mechanism: for device-call indices in `bad_calls` (0-based, counting
  every fused-step dispatch), the params argument is swapped for a
  fully-NaN copy with identical tree structure, shapes, dtypes and
  shardings (each floating leaf times NaN — an eager elementwise op
  preserves placement), so the ONE compiled step is reused — a
  recompile would void the engine's compile-once contract mid-chaos.
  A retry of the poisoned work arrives as a LATER call index and sees
  clean params, modeling a transient device/memory fault; list an index
  twice-adjacent (e.g. ``(3, 4)``) to model a persistent one that must
  escalate from retry to quarantine."""

  def __init__(self, engine, bad_calls: Sequence[int]):
    super().__init__(engine)
    self.bad_calls = set(bad_calls)
    self.poisoned: list = []
    self._nan_params = None

  def _poison(self, params):
    if self._nan_params is None:
      nan = np.float32(np.nan)

      def leaf(x):
        if np.issubdtype(np.dtype(x.dtype), np.floating):
          return x * nan
        return x

      self._nan_params = jax.tree_util.tree_map(leaf, params)
    return self._nan_params

  def __call__(self, params, *args):
    call, self.calls = self.calls, self.calls + 1
    if call in self.bad_calls:
      self.poisoned.append(call)
      params = self._poison(params)
    return self.inner(params, *args)


class HangingStepInjector(_StepFnWrapper):
  """Stall chosen fused-step dispatches by ``hang_s`` of host sleep —
  from the engine's point of view the device call went silent, which is
  exactly what the serving watchdog (``serving.resilience.
  step_timeout_s``) exists to surface.  The step then completes
  normally: a hang is a latency fault, not a correctness fault, and
  outputs must stay exact through it."""

  def __init__(self, engine, hang_calls: Sequence[int],
               hang_s: float = 0.05):
    super().__init__(engine)
    self.hang_calls = set(hang_calls)
    self.hang_s = hang_s
    self.hangs = 0

  def __call__(self, params, *args):
    call, self.calls = self.calls, self.calls + 1
    if call in self.hang_calls:
      self.hangs += 1
      time.sleep(self.hang_s)
    return self.inner(params, *args)


class FlakyDrafter:
  """Drafter wrapper that raises (``mode="raise"``) or proposes
  uniformly random garbage (``mode="garbage"``) on chosen ``propose``
  calls — the two ways a real drafter fails.  The engine must degrade
  a raising drafter to zero drafts for the step, and verification must
  reject garbage proposals; either way committed output stays exact
  (a flaky drafter may cost speed, never correctness)."""

  def __init__(self, inner, bad_calls: Sequence[int],
               mode: str = "raise", seed: int = 0):
    if mode not in ("raise", "garbage"):
      raise ValueError(f"unknown FlakyDrafter mode {mode!r}")
    self.inner = inner
    self.bad_calls = set(bad_calls)
    self.mode = mode
    self.calls = 0
    self.faults = 0
    self._rng = np.random.RandomState(seed)
    self._vocab: Optional[int] = None

  @property
  def k(self) -> int:
    return self.inner.k

  def bind(self, engine) -> None:
    self._vocab = engine.model.cfg.vocab_size
    self.inner.bind(engine)

  def propose(self, plan, histories):
    call, self.calls = self.calls, self.calls + 1
    if call in self.bad_calls:
      self.faults += 1
      if self.mode == "raise":
        raise RuntimeError("chaos: drafter failure")
      N = plan.tokens.shape[0]
      drafts = self._rng.randint(
          0, self._vocab or 2, (N, self.k)).astype(np.int32)
      return drafts, np.asarray(plan.draft_cap, np.int32)
    return self.inner.propose(plan, histories)

  def observe_commit(self, new_cursors) -> None:
    self.inner.observe_commit(new_cursors)

  def observe_skip(self, plan) -> None:
    self.inner.observe_skip(plan)


class ReplicaKiller(_StepFnWrapper):
  """Kill a serving replica mid-decode — **in-process simulation**:
  chosen fused-step dispatches raise instead of returning, so from the
  router's point of view the replica died with requests in flight.  It
  is a single-process STAND-IN for SIGKILL, not the real thing: the
  replica shares this process's memory and GIL, the "kill" is an
  exception unwinding its step, and its host state survives intact for
  evacuation.  For the real fault domain — a subprocess whose memory
  genuinely vanishes under ``os.kill(pid, SIGKILL)`` — use
  :class:`ProcessKiller` against a ProcessTransport replica.  Either
  way the router must mark the replica down, recover its queued +
  in-flight requests, and resume every one on a survivor bit-exactly
  via prefix replay (serving/router.py; `make chaos-router` /
  `make chaos-proc`).

  ``kill_calls`` are 0-based device-call indices; each listed call
  raises ONCE (so a later probe/rejoin of the same replica finds a
  working engine — the transient-fault model; pass a long run of
  indices for a persistent corpse, or use :class:`FlappingHealth` for
  the periodic version)."""

  def __init__(self, engine, kill_calls: Sequence[int]):
    super().__init__(engine)
    self.kill_calls = set(kill_calls)
    self.kills = 0

  def __call__(self, params, *args):
    call, self.calls = self.calls, self.calls + 1
    if call in self.kill_calls:
      self.kill_calls.discard(call)
      self.kills += 1
      raise RuntimeError(f"chaos: replica killed mid-step "
                         f"(device call {call})")
    return self.inner(params, *args)


class ReplicaHang(HangingStepInjector):
  """Stall a replica's fused-step dispatches — **in-process
  simulation** (same mechanism as :class:`HangingStepInjector`, named
  for the router suite): the "hang" is a host ``sleep`` sharing this
  process's GIL, not a frozen process — for the real thing
  (``SIGSTOP`` on a child that then genuinely cannot answer the wire)
  use :class:`ProcessStaller`.  The
  detector is the per-replica StepWatchdog — its monitor THREAD fires
  during the stall (the synchronous router can't observe a hang it is
  blocked inside), the timeout count rides the replica's next
  heartbeat, and the health machine must mark the replica suspect (no
  new dispatch; in-flight work keeps running and stays bit-exact),
  recovering on the next clean beat.  A hang is a latency fault:
  nothing is killed, nothing migrates, nothing may change in any
  output stream."""


class FlappingHealth(_StepFnWrapper):
  """A replica that keeps dying and recovering: every ``fail_every``-th
  fused-step dispatch raises (the rest succeed), so the router sees
  down -> probe -> healthy -> down -> ... in a loop.  The circuit
  breaker is the defense under test: each trip must DOUBLE the
  hold-out before the next probe, so a flapping replica converges to
  parked instead of bouncing its requests through endless failovers —
  while every migrated request still finishes bit-exactly on the stable
  survivors."""

  def __init__(self, engine, fail_every: int = 4, start_at: int = 0):
    if fail_every < 2:
      raise ValueError(f"fail_every must be >= 2: {fail_every}")
    super().__init__(engine)
    self.fail_every = fail_every
    self.start_at = start_at
    self.faults = 0

  def __call__(self, params, *args):
    call, self.calls = self.calls, self.calls + 1
    if call >= self.start_at and (call - self.start_at) \
        % self.fail_every == self.fail_every - 1:
      self.faults += 1
      raise RuntimeError(f"chaos: flapping replica failed again "
                         f"(device call {call})")
    return self.inner(params, *args)


# ------------------------------------------------ process-transport faults --


class ProcessKiller:
  """SIGKILL a process-hosted replica's child — the REAL replica death
  :class:`ReplicaKiller` simulates: the child's memory (engine, KV
  cache, scheduler state, everything) is gone the instant the signal
  lands, so there is no corpse to RPC.  The router must detect the
  death at the wire (pipe EOF / waitpid), fence, and recover the
  replica's queued + in-flight requests from its parent-side journal —
  bit-exactly, via prefix replay from the last committed watermark
  (serving/transport.py; `make chaos-proc`)."""

  def __init__(self, transport):
    self.transport = transport
    self.kills = 0
    self.killed_pids: list = []

  def kill(self) -> int:
    """Deliver SIGKILL now; returns the victim pid."""
    pid = self.transport.child_pid
    if pid is None:
      raise RuntimeError("ProcessKiller: transport has no live child")
    self.transport.kill(_signal.SIGKILL)
    self.kills += 1
    self.killed_pids.append(pid)
    return pid


class ProcessStaller:
  """Freeze a process-hosted replica's child with SIGSTOP — a genuinely
  hung worker (no GIL sharing, unlike :class:`ReplicaHang`'s host
  sleep): the child cannot answer the wire at all, so the parent's
  per-call deadline must trip, condemn the replica (a step is not
  idempotent — it can never be retried against a maybe-still-applying
  child) and fence it with SIGKILL before failing its requests over
  from the journal.  :meth:`resume` (SIGCONT) models the stall ending —
  AFTER a fence it arrives at a corpse, which is the point: a fenced
  replica can never double-serve."""

  def __init__(self, transport):
    self.transport = transport
    self.stalls = 0

  def stall(self) -> int:
    pid = self.transport.child_pid
    if pid is None:
      raise RuntimeError("ProcessStaller: transport has no live child")
    self.transport.kill(_signal.SIGSTOP)
    self.stalls += 1
    return pid

  def resume(self) -> None:
    pid = self.transport.child_pid
    if pid is not None:
      try:
        os.kill(pid, _signal.SIGCONT)
      except ProcessLookupError:
        pass  # already fenced — the expected post-failover outcome


class ReplyDropper:
  """Drop chosen reply frames at the parent's wire — the ambiguous
  timeout made deterministic: the child APPLIED the call and answered,
  but the parent never hears it (the frame is read off the socket and
  discarded, then the read raises the same :class:`TransportTimeout`
  a deadline miss would).  The exactly-once machinery under test:
  a retried ``submit`` must hit the child's uid dedup and admit once;
  a lost ``step`` reply must not double-commit tokens — the journal's
  acked-watermark resync (next reply resends the suffix) or the
  failover replay (deterministic regeneration) must both land the
  identical stream.

  ``drop`` are 0-based indices counting every reply frame this parent
  reads from the child."""

  def __init__(self, transport, drop: Sequence[int]):
    self.transport = transport
    self.inner = transport._read_frame
    self.drop = set(drop)
    self.calls = 0
    self.dropped: list = []
    transport._read_frame = self

  def __call__(self, timeout):
    from easyparallellibrary_tpu.serving.transport import TransportTimeout
    frame = self.inner(timeout)
    call, self.calls = self.calls, self.calls + 1
    if call in self.drop:
      self.drop.discard(call)
      self.dropped.append(frame)
      raise TransportTimeout(
          f"chaos: reply frame {call} dropped after the child applied it")
    return frame

  def uninstall(self):
    self.transport._read_frame = self.inner


def poisson_trace(rate_per_s: float, n: int, seed: int = 0,
                  rng: "np.random.RandomState" = None,
                  first_at_zero: bool = True) -> np.ndarray:
  """Arrival-time offsets (seconds, ascending) for `n` requests of a
  Poisson process at `rate_per_s` — THE arrival model for every
  overload episode of the chaos tests (one source, so the traffic
  shape cannot silently diverge).  Pass ``rng`` to draw from an
  existing generator (one seeded stream through arrivals + prompts +
  lengths); ``first_at_zero=False`` keeps the sampled first gap."""
  if rate_per_s <= 0:
    raise ValueError(f"rate_per_s must be > 0: {rate_per_s}")
  if rng is None:
    rng = np.random.RandomState(seed)
  gaps = rng.exponential(1.0 / rate_per_s, n)
  if first_at_zero:
    gaps[0] = 0.0
  return np.cumsum(gaps)


class SlowReader(threading.Thread):
  """A client too slow for its own stream: opens ``/v1/generate`` on a
  live front door (serving/frontdoor/) over a raw socket, then drains
  the SSE response ``read_bytes`` at a time with ``interval_s`` pauses
  — far below token production rate, so the per-connection bounded
  queue (``serving.frontdoor.stream_buffer``) must overflow and the
  front door must shed THIS flow (cancel + ``done`` with reason
  ``"cancelled"``) while neighbouring streams run untouched.

  ``start()`` it, then ``join()``; afterwards ``bytes_read`` counts
  what trickled through and ``eof`` records whether the server closed
  the stream (it should — the shed's done event ends it)."""

  def __init__(self, address: Tuple[str, int], body: Dict[str, Any],
               headers: Optional[Dict[str, str]] = None,
               read_bytes: int = 1, interval_s: float = 0.2,
               duration_s: float = 30.0):
    super().__init__(daemon=True)
    self.address = address
    self.body = body
    self.headers = headers
    self.read_bytes = int(read_bytes)
    self.interval_s = float(interval_s)
    self.duration_s = float(duration_s)
    self.bytes_read = 0
    self.eof = False
    self.error: Optional[BaseException] = None

  def run(self) -> None:
    from easyparallellibrary_tpu.serving.frontdoor.client import (
        open_raw_stream)
    deadline = time.monotonic() + self.duration_s
    try:
      sock = open_raw_stream(self.address, self.body,
                             headers=self.headers,
                             timeout=self.duration_s)
      try:
        while time.monotonic() < deadline:
          chunk = sock.recv(self.read_bytes)
          if not chunk:
            self.eof = True
            return
          self.bytes_read += len(chunk)
          time.sleep(self.interval_s)
      finally:
        sock.close()
    except OSError as e:
      self.error = e


class DisconnectingClient(threading.Thread):
  """A client that vanishes mid-stream: consumes ``after_events`` SSE
  token events from a live front door, then drops the connection —
  with an RST (``rst=True``, SO_LINGER 0: the no-FIN vanish a flaky
  mobile link produces) or a plain close.  The front door must cancel
  the request within one keepalive interval: slot and cache blocks
  freed, retirement reason ``"cancelled"``, trace flow finalized, and
  no stats double-count.

  After ``join()``: ``events_seen`` counts token events consumed before
  the drop; ``dropped`` confirms the disconnect happened (vs the stream
  finishing first)."""

  def __init__(self, address: Tuple[str, int], body: Dict[str, Any],
               headers: Optional[Dict[str, str]] = None,
               after_events: int = 2, rst: bool = False,
               timeout_s: float = 30.0):
    super().__init__(daemon=True)
    self.address = address
    self.body = body
    self.headers = headers
    self.after_events = int(after_events)
    self.rst = rst
    self.timeout_s = float(timeout_s)
    self.events_seen = 0
    self.dropped = False
    self.error: Optional[BaseException] = None

  def run(self) -> None:
    from easyparallellibrary_tpu.serving.frontdoor.client import (
        open_raw_stream)
    try:
      sock = open_raw_stream(self.address, self.body,
                             headers=self.headers,
                             timeout=self.timeout_s)
      buf = b""
      try:
        while self.events_seen < self.after_events:
          chunk = sock.recv(4096)
          if not chunk:
            return                      # finished before we could drop
          buf += chunk
          self.events_seen = buf.count(b"event: token")
        if self.rst:
          # SO_LINGER 0: close() sends RST, not FIN — the server only
          # discovers the corpse when a write (or keepalive probe)
          # faults.
          sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_LINGER,
                          struct.pack("ii", 1, 0))
        self.dropped = True
      finally:
        sock.close()
    except OSError as e:
      self.error = e


def overload_burst(service_rate_per_s: float, n_burst: int,
                   n_recover: int, factor: float = 3.0,
                   recover_frac: float = 0.5,
                   seed: int = 0) -> np.ndarray:
  """Arrival offsets for a self-healing episode (``make chaos-heal``,
  tests/test_serving_autoscale.py): ``n_burst`` Poisson arrivals at
  ``factor`` x the sustainable service rate — the overload that must
  breach the SLO burn rules and fire the actuators — followed by
  ``n_recover`` arrivals back at ``recover_frac`` x the service rate,
  the quiet tail that lets the error budget recover so hysteretic
  de-escalation and scale-down can be observed in the SAME trace.
  One seeded stream end to end, so the episode is reproducible."""
  if factor <= 1.0:
    raise ValueError(f"factor must be > 1 (an overload): {factor}")
  if not 0 < recover_frac <= 1.0:
    raise ValueError(f"recover_frac must be in (0, 1]: {recover_frac}")
  rng = np.random.RandomState(seed)
  burst = poisson_trace(service_rate_per_s * factor, n_burst, rng=rng)
  if n_recover <= 0:
    return burst
  tail = poisson_trace(service_rate_per_s * recover_frac, n_recover,
                       rng=rng, first_at_zero=False)
  return np.concatenate([burst, burst[-1] + tail])
