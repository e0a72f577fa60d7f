"""Host-side request scheduling for the continuous-batching engine.

Iteration-level (continuous) batching as in Orca (OSDI'22): the
scheduler re-forms the working set EVERY engine step, so requests join
the moment a slot frees and leave the moment they finish — no
batch-formation wait, no decode steps wasted running finished requests
to a batch-wide horizon.  The device program never changes shape; all of
the variability lives here, in which tokens each slot is fed.

Responsibilities (and nothing else — device work lives in engine.py):

* FCFS admission, gated by free slots, a configurable concurrent-batch
  cap (``max_batch``) and a per-iteration prefill-token budget that
  bounds how much prompt work any single step may carry
  (Sarathi-style chunked prefill: long prompts stream through the fused
  step ``prefill_chunk`` tokens at a time, so admission never stalls
  decode latency for more than one chunk).  ``latency``-class requests
  jump the FCFS order (:attr:`Request.priority`).
* Per-request decode state: prompt cursor, generated tokens, per-request
  RNG stream (a dedicated PRNGKey folded with the token index — two
  requests with the same seed reproduce the same sample stream no
  matter which slots or iterations they ride).
* Retirement: per-request ``max_new_tokens`` and optional stop-token,
  plus the hard ``max_seq_len`` capacity guard (checked at submit), and
  the lifecycle-control reasons — per-request deadlines / TTFT budgets
  (``deadline``), client cancellation (``cancelled``), overload
  rejection (``shed``, engine-side) and quarantine overflow
  (``failed``).  The full glossary lives in
  ``serving._capabilities.FINISH_REASONS`` / docs/robustness.md.
* Requeue: :meth:`requeue_slot` returns a mid-flight request to the
  FRONT of the queue with its committed prefix intact — on readmission
  the prompt AND the already-generated tokens replay through chunked
  prefill into a fresh slot, which reproduces the exact decode state
  (same KV content, same cursors-as-committed-token-count, same
  ``tok_index`` RNG fold), so a quarantined request's final output is
  bit-identical to an undisturbed run.

With the ambient tracer on, a request's PHASES are spans of category
``serving`` under one ``uid`` (docs/observability.md): ``serving/queued``
on a queue lane from submit to admission, then ``serving/prefill`` and
``serving/decode`` tiling its ``request <uid>`` span on the slot's track.
Each is ONE ``span_at`` written when the phase ends, its start a stamp of
the tracer's clock kept on the queue entry or the slot state; the
scheduler's own ``clock`` is injectable and never mixed with it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import heapq
import itertools
import time
import zlib
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import numpy as np

from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.serving._capabilities import (
    check_request_fields)
from easyparallellibrary_tpu.utils.logging import get_logger


def _slot_track(slot: int, prefix: str = "serving") -> str:
  """Perfetto track name for one KV-cache slot — every request served by
  this slot renders its lifecycle span here (docs/observability.md).
  Replicas pass their own prefix (``serving/replica<i>``) so the fleet's
  tracks stay distinct and a failed-over request's flow arc visibly
  crosses replica tracks."""
  return f"{prefix}/slot {slot}"


# Flow-context ids (Perfetto flow events; docs/observability.md
# "Request-flow correlation"): one id per request lifetime, minted at
# the FIRST submit the request reaches — the router when there is one,
# the scheduler otherwise — and carried through snapshot/restore so a
# failed-over request keeps its flow across replicas.  Process-unique
# is all a trace needs; minting is unconditional (a plain int) so
# enabling the tracer mid-run never sees id-less requests.
_FLOW_IDS = itertools.count(1)


def next_flow_id() -> int:
  return next(_FLOW_IDS)


# Request.snapshot() wire-format version: bump on ANY field change and
# keep a reader for every prior version — snapshots cross process
# boundaries (transport RPC, crash journals) where writer and reader
# can be different builds.  v2 added ``checkpoint_version`` (blue/green
# rollout, serving/rollout.py); v1 snapshots read with it defaulted to
# None ("any version" — pre-rollout fleets have exactly one).
SNAPSHOT_VERSION = 2


@functools.lru_cache(maxsize=None)
def _host_device():
  """The host's own JAX device, or None in a process whose JAX was held
  to the accelerator alone."""
  try:
    return jax.devices("cpu")[0]
  except RuntimeError:
    return None


def _request_key(req: "Request") -> np.ndarray:
  """The request's private PRNG stream key.  Deterministic in
  ``seed``/``uid`` and stable across processes (crc32, not Python's
  per-process-salted hash()), so a request migrated to another replica
  — or a restarted server — reproduces the identical sample stream.

  Made on the host's own device where there is one: on the accelerator
  the little program would queue behind the fused step in flight, and
  reading the key back would hold the plan of the next step until that
  step had finished (serving/engine.py, the overlapped loop)."""
  if req.seed is not None:
    seed = req.seed
  else:
    seed = zlib.crc32(str(req.uid).encode())
  host = _host_device()
  with (jax.default_device(host) if host is not None
        else contextlib.nullcontext()):
    return np.asarray(jax.random.PRNGKey(seed))


@dataclasses.dataclass
class Request:
  """One generation request.

  ``prompt`` is a 1-D int32 token array (non-empty — the model
  conditions the first new token on it, exactly like ``generate()``).
  ``temperature<=0`` is greedy; ``top_k``/``top_p`` mirror
  ``sample_logits`` semantics per slot.  ``stop_token < 0`` disables
  stop-token retirement; when hit, the stop token IS included in the
  output (the caller sees why the request ended).  ``seed`` starts the
  request's private RNG stream (defaults to a hash of ``uid``).
  ``speculative`` toggles speculative decoding per request: None
  follows the engine (a drafter is configured or not), False opts this
  request out (it then keeps the engine's non-speculative sample stream
  bit-exactly), True is a no-op on an engine without a drafter.

  Lifecycle control (docs/robustness.md "Serving resilience"):
  ``deadline_s`` retires the request with reason ``"deadline"`` once
  that many seconds have passed since submit, wherever it is (queued,
  prefilling, decoding; partial output is returned).  ``ttft_budget_s``
  is the stricter first-token bound: expire unless the first token was
  produced within the budget.  Both are 0 = off.  ``priority`` is
  ``"throughput"`` (FCFS) or ``"latency"`` (admitted ahead of queued
  throughput-class requests).

  ``flow_id`` is the request's trace-context id (Perfetto flow events;
  docs/observability.md "Request-flow correlation") — minted
  automatically at the first submit (router or scheduler) and carried
  through snapshot/restore, so callers never set it.

  ``checkpoint_version`` pins the request to the weights it started
  decoding under (docs/robustness.md "Blue/green rollout"): stamped by
  the router at dispatch time from the chosen replica's version and
  carried through snapshot/restore, so the failover journal can refuse
  to replay it onto a replica of a DIFFERENT version — prefix replay
  across checkpoints is not bit-exact.  ``None`` means "any version"
  (single-version fleets, pre-rollout snapshots); callers never set it.
  """
  uid: Any
  prompt: np.ndarray
  max_new_tokens: int
  temperature: float = 0.0
  top_k: int = 0
  top_p: float = 1.0
  stop_token: int = -1
  seed: Optional[int] = None
  speculative: Optional[bool] = None
  deadline_s: float = 0.0
  ttft_budget_s: float = 0.0
  priority: str = "throughput"
  flow_id: Optional[int] = None
  checkpoint_version: Optional[int] = None

  def snapshot(self) -> Dict[str, Any]:
    """JSON-serializable snapshot of the request spec (the immutable
    half of cross-replica migration; the scheduler adds the mutable
    half — committed prefix + lifecycle counters — in
    :meth:`FCFSScheduler.snapshot_requests`).  The PRNG state needs no
    field of its own: the stream key derives deterministically from
    ``seed``/``uid`` (:func:`_request_key`) and is folded by committed
    token index, so prompt + generated prefix IS the full sampler
    state.

    The dict is **versioned** (``"v": 2``): snapshots cross process
    boundaries (serving/transport.py ships them to worker processes and
    journals them for crash recovery), so a future field change must
    bump the version and keep a reader for every prior one —
    :meth:`restore` rejects unknown versions with a clear error instead
    of mis-restoring, and tests/golden/request_snapshot_v{1,2}.json pin
    the exact shapes.  v2 added ``checkpoint_version``; a v1 snapshot
    reads with it defaulted to None."""
    return {
        "v": SNAPSHOT_VERSION,
        "uid": self.uid,
        "prompt": [int(t) for t in np.asarray(self.prompt).reshape(-1)],
        "max_new_tokens": int(self.max_new_tokens),
        "temperature": float(self.temperature),
        "top_k": int(self.top_k),
        "top_p": float(self.top_p),
        "stop_token": int(self.stop_token),
        "seed": None if self.seed is None else int(self.seed),
        "speculative": self.speculative,
        "deadline_s": float(self.deadline_s),
        "ttft_budget_s": float(self.ttft_budget_s),
        "priority": self.priority,
        "flow_id": None if self.flow_id is None else int(self.flow_id),
        "checkpoint_version": (None if self.checkpoint_version is None
                               else int(self.checkpoint_version)),
    }

  @classmethod
  def restore(cls, snap: Dict[str, Any]) -> "Request":
    """Inverse of :meth:`snapshot` (tolerates a JSON round trip).
    Pre-versioning snapshots (no ``"v"`` key) read as v1 — the v1 field
    set with ``checkpoint_version`` absent; a v1 snapshot restores with
    it defaulted to None ("any version").  An UNKNOWN (newer) version
    is rejected loudly, because silently dropping or misreading a field
    would break cross-process failover bit-exactness in the quietest
    possible way."""
    snap = dict(snap)
    version = snap.pop("v", 1)
    if not 1 <= version <= SNAPSHOT_VERSION:
      raise ValueError(
          f"unsupported request snapshot version {version!r}: this build "
          f"reads v1..v{SNAPSHOT_VERSION} (a newer writer must not feed "
          f"an older reader across the failover wire — upgrade the "
          f"reader or re-snapshot with a v{SNAPSHOT_VERSION} writer)")
    snap.setdefault("checkpoint_version", None)
    snap["prompt"] = np.asarray(snap["prompt"], np.int32)
    return cls(**snap)


@dataclasses.dataclass
class FinishedRequest:
  uid: Any
  tokens: np.ndarray          # prompt + generated (stop token included)
  new_tokens: int
  finish_reason: str          # serving._capabilities.FINISH_REASONS


@dataclasses.dataclass(eq=False)
class StepPlan:
  """Device-ready arrays for one fused engine step (all [N] or [N, C])."""
  tokens: np.ndarray          # int32 [N, C] token chunk per slot
  num_valid: np.ndarray       # int32 [N]   live tokens in the chunk
  reset: np.ndarray           # bool  [N]   zero the cursor (fresh slot)
  keys: np.ndarray            # uint32 [N, 2] per-request PRNG keys
  tok_index: np.ndarray       # int32 [N]   tokens generated so far
  temperature: np.ndarray     # f32   [N]
  top_k: np.ndarray           # int32 [N]
  top_p: np.ndarray           # f32   [N]
  draft_cap: np.ndarray       # int32 [N] max speculative drafts this step
  prefilling: np.ndarray      # bool  [N]   this step's grant is prompt work
  prefill_tokens: int         # scheduled prompt tokens this step
  decode_tokens: int          # scheduled decode tokens this step
  active_slots: int
  # Cache rows under the bounds of the slots this step feeds: the sum of
  # cursor + num_valid (the host's mirror of the device cursor).
  live_kv_rows: int = 0
  # Per fed slot, the rows resident before this step (the host's mirror of
  # the device cursor the step starts from): what an engine whose attends
  # read less than every row under the bound counts their rows from.
  resident: Optional[np.ndarray] = None     # int32 [N]
  # Slots whose first chunk position is the PREVIOUS step's sample, which
  # the host has not seen yet (planned past an uncommitted step): the
  # engine's step takes it from that step's output on the device.
  from_prev: Optional[np.ndarray] = None    # bool [N]
  # What commit() needs of the plan it belongs to, whatever was planned
  # or retired since: ``(slot, state, prefix positions fed, sampled)`` of
  # the slots fed, in admission order, each with the state it was fed
  # from; ``sampled``: the slot's sample is a generated token (its prefix
  # ends inside this plan's feed).
  fed: List[Any] = dataclasses.field(default_factory=list)
  # Set by commit(): slots whose request had retired by then (a stop
  # token or a cancellation seen one step late), their position dropped.
  wasted: int = 0
  # Positions (prompt tokens, drafts) this plan held back because the
  # fused step's flat batch had no row for them (``FCFSScheduler.width``).
  flat_trimmed: int = 0


@dataclasses.dataclass(eq=False)
class PagedStepPlan:
  """Device-ready arrays for one token-flat fused step over the paged
  cache (serving/engine.py paged mode).  Flat arrays are [T] —
  ``T = token_budget``, one entry per scheduled position, each tagged
  with its slot and absolute position; per-slot arrays are [N] and share
  :class:`StepPlan`'s semantics so ``commit()`` consumes both plan kinds
  unchanged (``num_valid`` counts a slot's REAL tokens this step — its
  prefill grant, or 1 for decode — never reserved draft positions)."""
  tokens: np.ndarray          # int32 [T]  flat token batch
  slot_ids: np.ndarray        # int32 [T]  owning slot per position
  positions: np.ndarray       # int32 [T]  absolute position per token
  valid: np.ndarray           # bool  [T]  live entry (drafts flip late)
  block_tables: np.ndarray    # int32 [N, MB] per-slot block tables
  base_idx: np.ndarray        # int32 [N]  slot's first flat index
  draft_base: np.ndarray      # int32 [N]  slot's first draft flat index
  num_valid: np.ndarray       # int32 [N]  real tokens scheduled (no drafts)
  draft_cap: np.ndarray       # int32 [N]  reserved draft positions
  prefilling: np.ndarray      # bool  [N]
  keys: np.ndarray            # uint32 [N, 2]
  tok_index: np.ndarray       # int32 [N]
  temperature: np.ndarray     # f32   [N]
  top_k: np.ndarray           # int32 [N]
  top_p: np.ndarray           # f32   [N]
  prefill_tokens: int
  decode_tokens: int
  scheduled_tokens: int       # live flat positions (diagnostics)
  active_slots: int
  live_kv_rows: int = 0       # as StepPlan's: resident + scheduled rows
  fed: List[Any] = dataclasses.field(default_factory=list)  # as StepPlan's
  wasted: int = 0
  flat_trimmed: int = 0       # as StepPlan's; 0: the passes fit the budget


class _Phase:
  """The phase a traced request is in on its slot: when it started on the
  tracer's clock and what the steps committed since have fed it.  Made at
  admission, and only with the tracer on."""

  __slots__ = ("t0_us", "decoding", "steps", "base", "drafted", "accepted")

  def __init__(self, t0_us: float, base: int, decoding: bool = False):
    self.t0_us = t0_us
    self.decoding = decoding  # ``serving/decode``, else ``serving/prefill``
    self.steps = 0            # committed steps that fed the slot
    # Prefix positions fed (prefill) or tokens generated (decode) when the
    # phase started: its ``tokens`` is the count at its end less this.
    self.base = base
    self.drafted = 0          # speculation, decode only
    self.accepted = 0


class _SlotState:
  """Host mirror of one occupied slot.

  ``prefix`` is what chunked prefill feeds: the prompt for a fresh
  request, prompt + already-committed tokens for a requeued one (the
  replay that reconstructs the slot's KV/cursor state exactly).

  ``prompt_pos`` and ``generated`` are COMMITTED state: what snapshots,
  requeues and the paged cache read.  ``fed_ahead`` / ``samples_ahead``
  are what the outstanding (planned, not yet committed) plans add to
  them: prefix positions fed, and generated tokens sampled whose values
  the host has not seen.  Both are zero between a commit and the next
  plan of the serial loop.
  """

  __slots__ = ("req", "slot", "prompt_pos", "generated", "key", "prefix",
               "submitted_at", "admitted_at", "first_token_at",
               "first_token_emitted", "requeues", "bad_streak",
               "admit_seq", "reg_blocks", "fed_ahead", "samples_ahead",
               "phase")

  def __init__(self, req: Request, slot: int, submitted_at: float,
               now: float, carried: Optional["_SlotState"] = None,
               admit_seq: int = 0):
    self.req = req
    self.slot = slot
    self.prompt_pos = 0                    # prefix tokens already fed
    self.fed_ahead = 0
    self.samples_ahead = 0
    self.submitted_at = submitted_at
    self.admitted_at = now
    self.bad_streak = 0                    # consecutive bad-step hits
    # Monotonic admission sequence (preemption eligibility: a slot may
    # only page out strictly-younger same-priority slots, so two
    # starving slots can never preempt each other in a cycle).  A
    # requeued request gets a FRESH seq on readmission — it re-enters as
    # the youngest and cannot immediately steal back its old blocks.
    self.admit_seq = admit_seq
    # Leading blocks already registered in (or mapped from) the prefix
    # cache — the commit-time registration watermark, so the tree walk
    # only runs when a new full block completes.
    self.reg_blocks = 0
    self.phase: Optional[_Phase] = None    # set by a traced admission
    if carried is not None:
      self.generated: List[int] = carried.generated
      self.key = carried.key
      self.first_token_at = carried.first_token_at
      self.first_token_emitted = carried.first_token_emitted
      self.requeues = carried.requeues
      self.prefix = np.concatenate(
          [req.prompt, np.asarray(self.generated, np.int32)])
    else:
      self.generated = []
      self.key = _request_key(req)
      self.first_token_at: Optional[float] = None
      self.first_token_emitted = False
      self.requeues = 0
      self.prefix = req.prompt

  @property
  def prefilling(self) -> bool:
    return self.prompt_pos < len(self.prefix)

  @property
  def planned_pos(self) -> int:
    """Prefix positions fed once every outstanding plan has run."""
    return self.prompt_pos + self.fed_ahead

  @property
  def planned_generated(self) -> int:
    """Tokens generated once every outstanding plan has run."""
    return len(self.generated) + self.samples_ahead


class _Pending:
  """Queue entry: a not-yet-admitted request, optionally carrying the
  slot state of a requeued one (its committed prefix replays through
  prefill on readmission)."""

  __slots__ = ("req", "submitted_at", "carried", "queued_us", "lane")

  def __init__(self, req: Request, submitted_at: float,
               carried: Optional[_SlotState] = None):
    self.req = req
    self.submitted_at = submitted_at
    self.carried = carried
    # Traced entries only (``_trace_enqueue``): when it joined the queue
    # on the tracer's clock, and the queue lane its span will lie on.
    self.queued_us: Optional[float] = None
    self.lane: Optional[int] = None

  @property
  def prefix_len(self) -> int:
    if self.carried is not None:
      return len(self.req.prompt) + len(self.carried.generated)
    return len(self.req.prompt)

  # Read-through to the wrapped request, so queue introspection
  # (`sched.pending[0].uid`) reads the same as before entries carried
  # submit timestamps.
  @property
  def uid(self):
    return self.req.uid

  @property
  def prompt(self):
    return self.req.prompt

  @property
  def priority(self) -> str:
    return self.req.priority


class FCFSScheduler:
  """First-come-first-served continuous-batching scheduler.

  ``plan_step()`` builds the next fused-step inputs (expiring dead
  requests, then admitting new ones as slots and budget allow);
  ``commit(next_tokens)`` folds the step's sampled tokens back into
  per-request state and returns the requests that retired.  The engine
  owns the device half of the loop.

  A plan advances its slots PROVISIONALLY (``_SlotState.fed_ahead`` /
  ``samples_ahead``) and ``commit`` settles the oldest outstanding plan,
  so ``plan_step(ahead=True)`` can plan step k+1 while step k's tokens
  are still on the device: at most two plans are outstanding, committed
  in order.  What the host learns only at commit k (a stop token, a
  cancellation or a deadline seen since) retires the request then; its
  position in the already planned k+1 is dropped at commit k+1
  (``StepPlan.wasted``, ``wasted_positions``).

  The ``on_admit`` / ``on_first_token`` / ``on_finish`` hooks are LISTS
  of subscribers (append, don't assign) so stats, resilience and user
  callbacks compose without clobbering each other.

  ``clock`` is injectable for deterministic deadline tests; production
  callers leave it at ``time.monotonic``.
  """

  def __init__(self, num_slots: int, prefill_chunk: int,
               max_seq_len: int, prefill_token_budget: int = 0,
               max_batch: int = 0, stop_token: int = -1,
               spec_k: int = 0, clock: Callable[[], float] = time.monotonic,
               block_size: int = 0, num_blocks: int = 0,
               token_budget: int = 0, track_prefix: str = "serving",
               prefix_cache: bool = False,
               prefix_session_ttl_s: float = 0.0,
               prefix_max_cached_blocks: int = 0,
               checkpoint_version: int = 0, width: int = 0,
               slot_groups: int = 1):
    from easyparallellibrary_tpu.serving.kv_cache import (
        BlockAllocator, SlotAllocator)
    from easyparallellibrary_tpu.serving.prefix_cache import PrefixCache
    if prefill_chunk < 1:
      raise ValueError(f"prefill_chunk must be >= 1: {prefill_chunk}")
    if prefill_token_budget < 0 or max_batch < 0:
      raise ValueError("prefill_token_budget and max_batch must be >= 0")
    if spec_k < 0:
      raise ValueError(f"spec_k must be >= 0: {spec_k}")
    self.num_slots = num_slots
    self.chunk = prefill_chunk
    self.max_seq_len = max_seq_len
    # The checkpoint version this scheduler's engine serves
    # (docs/robustness.md "Blue/green rollout"): restore_request refuses
    # a snapshot pinned to a DIFFERENT version — prefix replay across
    # weights is not bit-exact — and the prefix cache keys its radix
    # tree on it so a warm block from checkpoint N is never reused to
    # skip prefill under N+1.  0 is the pre-rollout default.
    self.checkpoint_version = int(checkpoint_version)
    # Paged mode (block_size > 0): plan_step builds token-flat
    # PagedStepPlans against a block-table cache; the per-slot K/V
    # region becomes a grown-on-demand block list and pool exhaustion
    # preempts instead of raising (engine: serving.paged.*).
    self.paged = block_size > 0
    if self.paged:
      if max_seq_len % block_size:
        raise ValueError(f"block_size {block_size} must divide "
                         f"max_seq_len {max_seq_len}")
      if token_budget < 1:
        raise ValueError(f"token_budget must be >= 1 in paged mode: "
                         f"{token_budget}")
      eff_batch = min(num_slots, max_batch if max_batch > 0 else num_slots)
      if token_budget < eff_batch:
        raise ValueError(
            f"token_budget {token_budget} below the concurrent-batch cap "
            f"{eff_batch}: a step could not hand every decoding slot its "
            f"one guaranteed token")
      self.block_size = block_size
      self.token_budget = token_budget
      self._mb = max_seq_len // block_size
      self.block_allocator = BlockAllocator(num_blocks, block_size)
      self._slot_blocks: Dict[int, List[int]] = {}
      self._tables = np.zeros((num_slots, self._mb), np.int32)
      self.preemptions = 0
      # Eager evictions at admission so a latency-class arrival never
      # queues behind a throughput slot's blocks (ROADMAP item 5
      # leftover; _preempt_for_latency_admission).
      self.proactive_preemptions = 0
      # Copy-on-write prefix caching (serving/prefix_cache.py): a radix
      # tree over committed prompt blocks.  Admission maps matched
      # blocks by reference and skips their prefill; retirement leaves
      # blocks pinned under the TTL/LRU budget (session persistence).
      self.prefix_cache = (
          PrefixCache(self.block_allocator, block_size,
                      session_ttl_s=prefix_session_ttl_s,
                      max_cached_blocks=prefix_max_cached_blocks,
                      clock=clock, version=self.checkpoint_version)
          if prefix_cache else None)
    else:
      if prefix_cache:
        raise ValueError(
            "prefix caching shares KV at block granularity and therefore "
            "requires the paged cache: enable serving.paged (block_size "
            "> 0) alongside serving.prefix_cache")
      self.block_size = 0
      self.token_budget = 0
      self.block_allocator = None
      self.prefix_cache = None
    self._admit_seq = 0
    # Max speculative drafts per decode slot per step (0 = engine has no
    # drafter); per-request Request.speculative=False opts out, and the
    # engine's degradation ladder flips `spec_enabled` off under load.
    self.spec_k = spec_k
    self.spec_enabled = True
    # 0 = uncapped: every prefilling slot gets a full chunk each step.
    self.prefill_token_budget = prefill_token_budget
    # Rows of the fused step's flat batch (serving/engine.py:flat_width;
    # 0 = no ceiling): the most live positions ONE PLAN may hold.  A
    # decoding slot's one row is never held back; the prefill grants share
    # what the decoding slots leave, in admission order and through the
    # path a prefill budget takes (``plan_step``): the grant that reaches
    # the ceiling is cut short, the ones behind it wait, and all of them
    # go on next step.  ``StepPlan.flat_trimmed`` counts what a plan held
    # back for it.
    # ``slot_groups`` (an engine divided over a mesh axis: its chips):
    # the slots fall into that many equal runs, each with a flat batch of
    # ``width`` rows of its own, and the ceiling holds a run at a time.
    if slot_groups < 1 or num_slots % slot_groups:
      raise ValueError(f"{num_slots} slots do not divide into "
                       f"{slot_groups} groups")
    if 0 < width < num_slots // slot_groups:
      raise ValueError(f"width {width} must hold one row a slot: "
                       f"{num_slots // slot_groups} slots")
    self.width = width
    self.slot_groups = slot_groups
    # Temporary degradation override (engine resilience): when > 0 the
    # effective per-step budget is min(budget or inf, override).
    self.budget_override = 0
    # Autotuner clamps (serving/autotune.py) — all DATA-valued: they
    # steer host-side planning/admission only, so moving them between
    # steps never changes a fused-step shape.  tune_budget (>0) joins
    # the budget min above; tune_slot_cap (>0) caps effective
    # concurrency below max_batch; tune_spec_k (>=0) caps per-slot
    # draft length below spec_k (0 = no drafts planned).
    self.tune_budget = 0
    self.tune_slot_cap = 0
    self.tune_spec_k = -1
    self.max_batch = max_batch if max_batch > 0 else num_slots
    self.default_stop_token = stop_token
    self.clock = clock
    # Slot-track namespace for this scheduler's lifecycle spans
    # (replicas pass serving/replica<i> so fleet tracks stay distinct).
    self.track_prefix = track_prefix
    # Queue lanes, ``<prefix>/queue/<i>``: queued requests overlap each
    # other and the slot's previous occupant, and spans of one name may
    # not overlap on one track, so a traced entry takes the lowest free
    # lane at submit and frees it at admission.  As many lanes as the
    # queue was ever deep while traced.
    self._queue_tracks: List[str] = []
    self._free_lanes: List[int] = []       # a heap
    self.allocator = SlotAllocator(num_slots)
    self.pending: Deque[_Pending] = deque()
    # Count of queued latency-class entries, maintained at every
    # pending mutation: _next_pending_index early-outs to O(1) FCFS
    # when none is queued (the common case — an overload queue of
    # throughput requests must not pay an O(depth) scan per admission).
    self._latency_pending = 0
    # Same O(1) discipline for lifecycle deadlines: counts of queued /
    # active requests carrying a deadline or TTFT budget, so expire()
    # (called every plan_step) skips its queue scan and active-slot
    # sweep outright when no request has one — the default.
    self._deadline_pending = 0
    self._deadline_active = 0
    self.active: Dict[int, _SlotState] = {}   # slot -> state
    self._admit_order: List[int] = []         # slots, admission order
    # Planned, not yet committed, oldest first (class docstring).
    self._plans: Deque[Any] = deque()
    # Positions of slots whose request had retired by the time their
    # plan committed (cumulative).
    self.wasted_positions = 0
    self._finished_buffer: List[FinishedRequest] = []
    self.on_admit: List[Callable[[Any], None]] = []      # fn(uid)
    self.on_first_token: List[Callable[[Any], None]] = []  # fn(uid)
    self.on_finish: List[Callable[[FinishedRequest], None]] = []
    # Per-iteration token delivery: fn(uid, [tok, ...]) with the tokens
    # THIS commit() appended for that request, fired the moment they
    # commit (before any retirement they trigger) — the streaming front
    # door's feed (serving/frontdoor/), so it never polls `finished`.
    self.on_tokens: List[Callable[[Any, List[int]], None]] = []

  def _effective_budget(self) -> int:
    # Branches, not a list build: this runs twice per engine step on
    # the host hot path.
    budget = self.prefill_token_budget
    if self.budget_override > 0 and \
        (budget == 0 or self.budget_override < budget):
      budget = self.budget_override
    if self.tune_budget > 0 and (budget == 0 or self.tune_budget < budget):
      budget = self.tune_budget
    return budget

  @property
  def effective_max_batch(self) -> int:
    """Concurrency cap after the autotuner's slot-cap clamp (admission
    reads this; ``max_batch`` stays the configured baseline)."""
    if self.tune_slot_cap > 0:
      return min(self.max_batch, self.tune_slot_cap)
    return self.max_batch

  @property
  def effective_spec_k(self) -> int:
    """Per-slot draft cap after the autotuner's speculation clamp."""
    if self.tune_spec_k >= 0:
      return min(self.spec_k, self.tune_spec_k)
    return self.spec_k

  # ---------------------------------------------------------------- queue

  def validate(self, req: Request) -> np.ndarray:
    """Raise on a malformed request (mirrors ``generate()``'s argument
    validation so a request the engine accepts can always run); returns
    the normalized prompt.  The engine also calls this BEFORE its shed
    verdict, so a malformed request fails loudly regardless of load
    instead of being silently recorded as ``"shed"``."""
    prompt = np.asarray(req.prompt, np.int32).reshape(-1)
    if prompt.size == 0:
      raise ValueError("request needs a non-empty prompt (at least a BOS "
                       "token) — same contract as generate()")
    if req.max_new_tokens < 1:
      raise ValueError(f"max_new_tokens must be >= 1: {req.max_new_tokens}")
    total = prompt.size + req.max_new_tokens
    if total > self.max_seq_len:
      raise ValueError(f"prompt + new tokens ({total}) exceeds "
                       f"max_seq_len {self.max_seq_len}")
    if not 0.0 < req.top_p <= 1.0:
      raise ValueError(f"top_p must be in (0, 1]: {req.top_p}")
    if req.top_k < 0:
      raise ValueError(f"top_k must be >= 0: {req.top_k}")
    check_request_fields(req)
    return prompt

  def submit(self, req: Request, _prompt: Optional[np.ndarray] = None):
    """Validate and enqueue (FCFS).  ``_prompt`` lets the engine pass
    the normalized prompt from its own pre-shed ``validate`` call so an
    accepted submit validates exactly once."""
    prompt = self.validate(req) if _prompt is None else _prompt
    req = dataclasses.replace(req, prompt=prompt)
    if req.stop_token < 0 and self.default_stop_token >= 0:
      req = dataclasses.replace(req, stop_token=self.default_stop_token)
    # Flow-context id: minted here unless an upstream router already
    # did (its id wins — the flow must span the WHOLE dispatch arc).
    minted = req.flow_id is None
    if minted:
      req = dataclasses.replace(req, flow_id=next_flow_id())
    entry = _Pending(req, self.clock())
    self.pending.append(entry)
    self._latency_pending += req.priority == "latency"
    self._deadline_pending += self._has_deadline(req)
    tracer = trace_lib.get_tracer()
    if tracer.enabled:  # args dicts are not free; skip them when off
      self._trace_enqueue(tracer, entry)
      tracer.instant(
          "serving/submit", cat="serving", track="serving/requests",
          args={"uid": str(req.uid), "prompt_tokens": int(prompt.size),
                "max_new_tokens": int(req.max_new_tokens)})
      # The minter starts the flow; a router-minted flow already has
      # its "s" — this submit is one step of its arc.
      tracer.flow("s" if minted else "t", req.flow_id,
                  track="serving/requests", args={"uid": str(req.uid)})

  @property
  def has_work(self) -> bool:
    return bool(self.pending or self.active)

  @property
  def num_active(self) -> int:
    return len(self.active)

  @property
  def queue_depth(self) -> int:
    return len(self.pending)

  def take_finished(self) -> List[FinishedRequest]:
    """Drain retirements accumulated since the last call (commit-time
    retirements plus plan-time expiries and out-of-band cancellations)."""
    out, self._finished_buffer = self._finished_buffer, []
    return out

  # ------------------------------------------------- request-phase spans

  def _trace_enqueue(self, tracer, entry: _Pending,
                     now_us: Optional[float] = None) -> None:
    """``entry`` joins the queue: its ``serving/queued`` span starts now
    (``now_us``: at the end of the phase it left a slot in) on the lowest
    free queue lane."""
    entry.queued_us = tracer.now_us() if now_us is None else now_us
    if self._free_lanes:
      entry.lane = heapq.heappop(self._free_lanes)
    else:
      entry.lane = len(self._queue_tracks)
      self._queue_tracks.append(f"{self.track_prefix}/queue/{entry.lane}")

  def _trace_dequeue(self, tracer, entry: _Pending,
                     end_us: Optional[float] = None,
                     reason: Optional[str] = None) -> None:
    """``entry`` leaves the queue (admitted at ``end_us``; or now, with
    ``reason``: expired, cancelled or migrated there): its lane is free
    again and, traced from submit to here, its ``serving/queued`` span is
    written."""
    if entry.lane is None:
      return                          # queued while the tracer was off
    lane, entry.lane = entry.lane, None
    heapq.heappush(self._free_lanes, lane)
    if not tracer.enabled:
      return
    if end_us is None:
      end_us = tracer.now_us()
    carried = entry.carried
    args = self._phase_args(entry.req)
    args["requeues"] = int(carried.requeues) if carried is not None else 0
    if reason is not None:
      args["finish_reason"] = reason
    tracer.span_at("serving/queued", entry.queued_us, end_us,
                   cat="serving", track=self._queue_tracks[lane], args=args)

  @staticmethod
  def _phase_args(req: Request) -> Dict[str, Any]:
    """What the phase spans of one request share."""
    args: Dict[str, Any] = {"uid": str(req.uid)}
    if req.flow_id is not None:
      args["flow_id"] = int(req.flow_id)
    return args

  def _trace_phase_end(self, tracer, state: _SlotState,
                       reason: Optional[str] = None) -> Optional[float]:
    """The phase ``state`` is in ends now: its span is written on the
    slot's track, inside ``request <uid>`` (``reason``: why, where that is
    not the next phase starting).  Returns the stamp, for whatever starts
    where it ends; None with the tracer off.  A request admitted with the
    tracer off has no phase and records none.  Call it while the slot
    still holds its blocks."""
    phase, state.phase = state.phase, None
    if not tracer.enabled:
      return None     # switched off since admission: the phase is dropped
    end_us = tracer.now_us()
    if phase is None:
      return end_us
    args = self._phase_args(state.req)
    args["steps"] = phase.steps
    if phase.decoding:
      args["tokens"] = len(state.generated) - phase.base
      if self.spec_k > 0:
        args["drafted"] = phase.drafted
        args["accepted"] = phase.accepted
    else:
      args["tokens"] = state.prompt_pos - phase.base
      if phase.base:                # admitted warm (paged prefix cache)
        args["prefix_blocks_reused"] = phase.base // self.block_size
    if self.paged:
      # The occupancy's block high-water mark: it only ever grows.
      args["kv_blocks"] = len(self._slot_blocks.get(state.slot, ()))
    if reason is not None:
      args["finish_reason"] = reason
    tracer.span_at(
        "serving/decode" if phase.decoding else "serving/prefill",
        phase.t0_us, end_us, cat="serving",
        track=_slot_track(state.slot, self.track_prefix), args=args)
    return end_us

  # ------------------------------------------------------ lifecycle ctl

  def _finish_unadmitted(self, entry: _Pending, reason: str):
    """Retire a request straight out of the queue (expiry/cancel before
    a slot was ever granted — or after a requeue)."""
    generated = (entry.carried.generated if entry.carried is not None
                 else [])
    fin = FinishedRequest(
        uid=entry.req.uid,
        tokens=np.concatenate(
            [entry.req.prompt, np.asarray(generated, np.int32)]),
        new_tokens=len(generated),
        finish_reason=reason)
    tracer = trace_lib.get_tracer()
    self._trace_dequeue(tracer, entry, reason=reason)
    if tracer.enabled:
      tracer.instant(
          f"serving/{reason}", cat="serving", track="serving/requests",
          args={"uid": str(entry.req.uid), "where": "queue"})
      if entry.req.flow_id is not None:
        # Queue-side retirement terminates the flow too — every started
        # flow must reach an "f" (validate_trace).
        tracer.flow("f", entry.req.flow_id, track="serving/requests",
                    args={"uid": str(entry.req.uid), "reason": reason})
    self._finished_buffer.append(fin)
    for fn in self.on_finish:
      fn(fin)
    return fin

  @staticmethod
  def _has_deadline(req: Request) -> bool:
    return req.deadline_s > 0 or req.ttft_budget_s > 0

  def _expired(self, req: Request, submitted_at: float, now: float,
               first_token: bool) -> bool:
    waited = now - submitted_at
    if req.deadline_s > 0 and waited >= req.deadline_s:
      return True
    return (req.ttft_budget_s > 0 and not first_token
            and waited >= req.ttft_budget_s)

  def expire(self, now: Optional[float] = None) -> int:
    """Retire every queued or active request whose deadline / TTFT
    budget has passed (finish reason ``"deadline"``).  Called by
    ``plan_step`` each iteration; callable standalone.  O(1) when no
    queued/active request carries a deadline (the ``_deadline_*``
    counters).  Returns how many requests expired."""
    now = self.clock() if now is None else now
    expired = 0
    if self.pending and self._deadline_pending:
      keep: Deque[_Pending] = deque()
      for entry in self.pending:
        first = (entry.carried.first_token_emitted
                 if entry.carried is not None else False)
        if self._expired(entry.req, entry.submitted_at, now, first):
          # _expired is True only for a deadline-carrying request, so
          # the unconditional decrement is exact.
          self._latency_pending -= entry.req.priority == "latency"
          self._deadline_pending -= 1
          self._finish_unadmitted(entry, "deadline")
          expired += 1
        else:
          keep.append(entry)
      self.pending = keep
    if not self._deadline_active:
      return expired
    for slot in list(self._admit_order):
      state = self.active.get(slot)
      if state is None:
        continue
      if self._expired(state.req, state.submitted_at, now,
                       state.first_token_emitted):
        self._retire(state, "deadline")
        expired += 1
    return expired

  def cancel(self, uid: Any) -> bool:
    """Client cancellation: retire `uid` wherever it is (queued or
    active) with finish reason ``"cancelled"``.  Returns False when the
    request is unknown (already finished, or never submitted)."""
    for i, entry in enumerate(self.pending):
      if entry.req.uid == uid:
        del self.pending[i]
        self._latency_pending -= entry.req.priority == "latency"
        self._deadline_pending -= self._has_deadline(entry.req)
        self._finish_unadmitted(entry, "cancelled")
        return True
    for slot, state in list(self.active.items()):
      if state.req.uid == uid:
        self._retire(state, "cancelled")
        return True
    return False

  def requeue_slot(self, slot: int, reason: str = "bad_step"
                   ) -> Optional[Any]:
    """Quarantine: evict `slot`'s request back to the FRONT of the queue
    with its committed prefix intact (module docstring) — the engine's
    bad-step recovery uses this to stop one poisoned slot from wedging
    the batch.  Returns the requeued uid, or None for an empty slot."""
    state = self.active.get(slot)
    if state is None:
      return None
    del self.active[slot]
    self._admit_order.remove(slot)
    self.allocator.free(slot)
    tracer = trace_lib.get_tracer()
    left_us = self._trace_phase_end(tracer, state, "requeued")
    self._release_blocks(slot)
    self._deadline_active -= self._has_deadline(state.req)
    state.requeues += 1
    state.bad_streak = 0
    if tracer.enabled:
      if state.req.flow_id is not None:
        # Flow step INSIDE the closing span, so the arc anchors on this
        # occupancy before jumping to the request's next slot.
        tracer.flow("t", state.req.flow_id,
                    track=_slot_track(slot, self.track_prefix),
                    args={"uid": str(state.req.uid), "reason": reason})
      tracer.end(
          f"request {state.req.uid}", cat="serving.request",
          track=_slot_track(slot, self.track_prefix),
          args={"finish_reason": "requeued",
                "new_tokens": int(len(state.generated))})
      tracer.instant(
          "serving/requeue", cat="serving", track="serving/requests",
          args={"uid": str(state.req.uid), "slot": int(slot),
                "reason": reason,
                "committed_prefix": int(len(state.req.prompt)
                                        + len(state.generated))})
    entry = _Pending(state.req, state.submitted_at, carried=state)
    if tracer.enabled:
      # Back in the queue from where its phase on the slot ended.
      self._trace_enqueue(tracer, entry, left_us)
    self.pending.appendleft(entry)
    self._latency_pending += state.req.priority == "latency"
    self._deadline_pending += self._has_deadline(state.req)
    return state.req.uid

  def retire_slot(self, slot: int, reason: str) -> Optional[FinishedRequest]:
    """Force-retire an active slot with an explicit finish reason (the
    engine's quarantine-overflow path: reason ``"failed"``)."""
    state = self.active.get(slot)
    if state is None:
      return None
    return self._retire(state, reason)

  # ------------------------------------------------- snapshot / migration

  @staticmethod
  def _snapshot_state(req: Request, generated: List[int], requeues: int,
                      first_token_emitted: bool,
                      submitted_at: float) -> Dict[str, Any]:
    return {
        "request": req.snapshot(),
        "generated": [int(t) for t in generated],
        "requeues": int(requeues),
        "first_token_emitted": bool(first_token_emitted),
        "submitted_at": float(submitted_at),
    }

  def snapshot_requests(self) -> List[Dict[str, Any]]:
    """Serializable snapshots of every IN-FLIGHT and queued request, in
    service order (active slots by admission order, then the queue
    front-to-back).  Each snapshot carries the request spec
    (:meth:`Request.snapshot`) plus the mutable half — committed
    generated prefix, requeue count, first-token flag, submit time —
    which is everything bit-exact resumption needs: restoring on ANY
    scheduler against the same params source replays prompt + prefix
    through chunked prefill, reconstructing KV, cursors and the
    ``tok_index`` PRNG fold exactly (module docstring: the requeue
    contract, here made cross-replica).  Read-only — the scheduler is
    untouched; pair with :meth:`evacuate` to also remove them."""
    snaps = []
    for slot in self._admit_order:
      s = self.active[slot]
      snaps.append(self._snapshot_state(
          s.req, s.generated, s.requeues, s.first_token_emitted,
          s.submitted_at))
    for entry in self.pending:
      c = entry.carried
      snaps.append(self._snapshot_state(
          entry.req, c.generated if c is not None else [],
          c.requeues if c is not None else 0,
          c.first_token_emitted if c is not None else False,
          entry.submitted_at))
    return snaps

  def progress(self) -> List[Any]:
    """``[(uid, generated_token_list)]`` for every live request, in
    service order (active slots by admission order, then the queue) —
    the committed-token watermark stream a transport worker reports so
    the router-side crash journal can replay bit-exactly
    (serving/transport.py).  Lives beside :meth:`snapshot_requests`
    because it walks the identical structure — the wire layer must
    never reach into scheduler internals for it."""
    out: List[Any] = []
    for slot in self._admit_order:
      state = self.active[slot]
      out.append((state.req.uid, state.generated))
    for entry in self.pending:
      carried = entry.carried
      out.append((entry.req.uid,
                  carried.generated if carried is not None else []))
    return out

  def restore_request(self, snap: Dict[str, Any],
                      front: bool = False) -> Any:
    """Resubmit a snapshotted request (queued here, replayed through
    chunked prefill on admission — the committed prefix and sample
    stream resume bit-exactly).  ``front=True`` preserves the migrated
    request's place in line (failover resubmits in REVERSE snapshot
    order so the head of the dead replica's line stays the head here).
    Returns the restored uid.

    A snapshot pinned to a DIFFERENT checkpoint version is REFUSED
    (docs/robustness.md "Blue/green rollout"): replaying its committed
    prefix under other weights would silently fork the sample stream —
    the router places it on a same-version survivor or parks it."""
    pinned = snap["request"].get("checkpoint_version")
    if pinned is not None and int(pinned) != self.checkpoint_version:
      raise ValueError(
          f"cross-version restore refused: request "
          f"{snap['request'].get('uid')!r} is pinned to checkpoint "
          f"version {int(pinned)} but this replica serves version "
          f"{self.checkpoint_version} — prefix replay across versions "
          f"is not bit-exact (migration policy is complete-in-place; "
          f"docs/robustness.md)")
    req = Request.restore(snap["request"])
    req = dataclasses.replace(req, prompt=self.validate(req))
    restored_flow = req.flow_id is not None
    if not restored_flow:  # pre-flow snapshot: start a fresh flow here
      req = dataclasses.replace(req, flow_id=next_flow_id())
    submitted_at = float(snap["submitted_at"])
    generated = [int(t) for t in snap.get("generated", ())]
    carried = None
    if generated or snap.get("requeues") or snap.get("first_token_emitted"):
      # Rebuild the carried per-request state a requeue would have kept:
      # the slot number is a placeholder (never read off a carried
      # state) and the PRNG key re-derives from seed/uid — identical by
      # _request_key's determinism.
      carried = _SlotState(req, -1, submitted_at, self.clock())
      carried.generated = generated
      carried.requeues = int(snap.get("requeues", 0))
      carried.first_token_emitted = bool(snap.get("first_token_emitted"))
      carried.prefix = np.concatenate(
          [req.prompt, np.asarray(generated, np.int32)])
    entry = _Pending(req, submitted_at, carried=carried)
    if front:
      self.pending.appendleft(entry)
    else:
      self.pending.append(entry)
    self._latency_pending += req.priority == "latency"
    self._deadline_pending += self._has_deadline(req)
    tracer = trace_lib.get_tracer()
    if tracer.enabled:
      self._trace_enqueue(tracer, entry)
      tracer.instant(
          "serving/restore", cat="serving", track="serving/requests",
          args={"uid": str(req.uid),
                "committed_prefix": int(len(req.prompt) + len(generated))})
      tracer.flow("t" if restored_flow else "s", req.flow_id,
                  track="serving/requests",
                  args={"uid": str(req.uid), "reason": "restored"})
    return req.uid

  def evacuate(self) -> List[Dict[str, Any]]:
    """Snapshot EVERY queued + in-flight request, then remove them all
    without finish records (they will finish elsewhere — failover and
    drain-timeout migration; router.py).  Slots, blocks and lifecycle
    counters are released exactly as a requeue releases them; each
    active request's trace span ends with reason ``"migrated"`` (like
    ``"requeued"``/``"preempted"``, it names a move, not a final
    resolution).  Call between steps only — never with a plan in
    flight."""
    snaps = self.snapshot_requests()
    tracer = trace_lib.get_tracer()
    for slot in list(self._admit_order):
      state = self.active.pop(slot)
      self._admit_order.remove(slot)
      self.allocator.free(slot)
      self._trace_phase_end(tracer, state, "migrated")
      self._release_blocks(slot)
      self._deadline_active -= self._has_deadline(state.req)
      if tracer.enabled:
        if state.req.flow_id is not None:
          tracer.flow("t", state.req.flow_id,
                      track=_slot_track(slot, self.track_prefix),
                      args={"uid": str(state.req.uid),
                            "reason": "migrated"})
        tracer.end(
            f"request {state.req.uid}", cat="serving.request",
            track=_slot_track(slot, self.track_prefix),
            args={"finish_reason": "migrated",
                  "new_tokens": int(len(state.generated))})
    for entry in self.pending:
      self._trace_dequeue(tracer, entry, reason="migrated")
    self.pending.clear()
    self._latency_pending = 0
    self._deadline_pending = 0
    self._plans.clear()
    return snaps

  # ----------------------------------------------------------------- plan

  def _next_pending_index(self) -> int:
    """Admission order: the oldest ``latency``-class request if any is
    queued (priority admission), else the queue head (FCFS).  O(1)
    unless a latency-class entry is actually queued."""
    if self._latency_pending == 0:
      return 0
    for i, entry in enumerate(self.pending):
      if entry.req.priority == "latency":
        return i
    return 0

  def _admit(self) -> None:
    """Admit pending requests while slots, the batch cap and the prefill
    budget allow — ``latency``-class first, then FCFS.  The budget is
    charged for each admission's first chunk so one step never admits
    more prefill work than it can schedule — an admitted-but-starved
    request would hold a slot while contributing nothing."""
    budget_cap = self._effective_budget()
    batch_cap = self.effective_max_batch   # hoisted: loop-invariant
    budget_left = budget_cap
    if budget_left > 0:
      # Already-active prefill slots have first claim on the budget.
      budget_left -= sum(
          min(self.chunk, len(s.prefix) - s.planned_pos)
          for s in self.active.values()
          if s.planned_pos < len(s.prefix))
    while self.pending:
      idx = self._next_pending_index()
      entry = self.pending[idx]
      first_chunk = min(self.chunk, entry.prefix_len)
      if budget_cap > 0 and budget_left < first_chunk:
        break
      if (self.allocator.num_free == 0
          or len(self.active) >= batch_cap):
        # Capacity-blocked.  Proactive latency-class preemption (paged
        # engine): a latency arrival next in line evicts the youngest
        # throughput slot holding blocks NOW rather than queueing until
        # a retirement or pool exhaustion frees capacity.  The budget
        # check above ran first — evicting for an admission this step
        # cannot afford would burn the victim's progress for nothing.
        if not (self.paged and self._latency_pending
                and entry.req.priority == "latency"):
          break
        if self._preempt_for_latency_admission() is None:
          break
        # The victim re-entered the queue at its front; the latency
        # entry's index may have shifted — re-resolve it.
        idx = self._next_pending_index()
        entry = self.pending[idx]
      budget_left -= first_chunk
      del self.pending[idx]
      self._latency_pending -= entry.req.priority == "latency"
      self._deadline_pending -= self._has_deadline(entry.req)
      req = entry.req
      slot = self.allocator.alloc()
      self._admit_seq += 1
      state = _SlotState(req, slot, entry.submitted_at, self.clock(),
                         carried=entry.carried,
                         admit_seq=self._admit_seq)
      self.active[slot] = state
      self._deadline_active += self._has_deadline(req)
      self._admit_order.append(slot)
      # Warm admission (serving/prefix_cache.py): walk the radix tree
      # with the request's prefix (prompt, plus the committed replay
      # for a requeued one).  Matched blocks map into the table by
      # reference — each already carries one fresh refcount from
      # match() — and the prompt cursor jumps past them, so chunked
      # prefill only ever feeds the unmatched tail.  The match cap
      # (strictly before the last prefix token) guarantees prompt_pos
      # stays short of len(prefix): the slot still runs at least one
      # prefill step, keeping first-token emission on its normal path.
      reused = 0
      if self.paged and self.prefix_cache is not None:
        matched = self.prefix_cache.match(state.prefix)
        if matched:
          blocks = self._slot_blocks.setdefault(slot, [])
          for blk in matched:
            self._tables[slot, len(blocks)] = blk
            blocks.append(blk)
          reused = len(matched)
          state.prompt_pos = reused * self.block_size
          state.reg_blocks = reused
      # The request's lifecycle span opens on its slot's track and stays
      # open until _retire — its ``serving/prefill`` and ``serving/decode``
      # phases tile it, so one Perfetto track row reads as the request's
      # complete timeline.
      tracer = trace_lib.get_tracer()
      admit_us = None
      if tracer.enabled:
        args = {"uid": str(req.uid),
                "prompt_tokens": int(len(req.prompt)),
                "max_new_tokens": int(req.max_new_tokens)}
        if reused:
          args["prefix_blocks_reused"] = int(reused)
        if state.requeues:
          args["requeues"] = int(state.requeues)
        tracer.begin(f"request {req.uid}", cat="serving.request",
                     track=_slot_track(slot, self.track_prefix),
                     args=args)
        if req.flow_id is not None:
          # Flow step just inside the freshly opened span: the arc
          # lands on this slot's track for this occupancy.
          tracer.flow("t", req.flow_id,
                      track=_slot_track(slot, self.track_prefix),
                      args={"uid": str(req.uid)})
        # ONE stamp ends the wait in the queue and starts the prefill.
        admit_us = tracer.now_us()
        state.phase = _Phase(admit_us, state.prompt_pos)
      if entry.lane is not None:
        self._trace_dequeue(tracer, entry, admit_us)
      if state.requeues == 0:
        for fn in self.on_admit:
          fn(req.uid)

  # -------------------------------------------------- paged block planning

  def _resident_tokens(self, state: _SlotState) -> int:
    """Tokens whose K/V are valid-resident in the slot's blocks — the
    host mirror of the contiguous engine's device cursor.  During
    prefill this is the fed prefix; after it, the decode input token's
    position is always ``len(prompt) + len(generated) - 1`` (a requeued
    replay's generated prefix is both inside ``prefix`` AND in
    ``generated``, which this accounting absorbs).  Counted past the
    outstanding plans: what is resident once they have run."""
    if state.planned_pos < len(state.prefix):
      return state.planned_pos
    return len(state.req.prompt) + state.planned_generated - 1

  def slot_blocks(self, slot: int) -> List[int]:
    """The slot's current block list (engine sanitize + tests)."""
    return list(self._slot_blocks.get(slot, ()))

  @property
  def kv_blocks_free(self) -> int:
    return self.block_allocator.num_free if self.paged else 0

  @property
  def kv_blocks_used(self) -> int:
    return self.block_allocator.num_used if self.paged else 0

  @property
  def kv_fragmentation(self) -> float:
    """Fraction of allocated block capacity no resident token occupies
    (last-block slack + preallocated draft headroom)."""
    if not self.paged:
      return 0.0
    used_tokens = sum(self._resident_tokens(s)
                      for s in self.active.values())
    return self.block_allocator.fragmentation(used_tokens)

  def _release_blocks(self, slot: int) -> None:
    if not self.paged:
      return
    for blk in self._slot_blocks.pop(slot, ()):  # noqa: B909
      self.block_allocator.decref(blk)
    self._tables[slot] = 0

  # --------------------------------------------------- prefix-cache interop

  @property
  def prefix_hits(self) -> int:
    return self.prefix_cache.hits if self.prefix_cache is not None else 0

  @property
  def prefix_misses(self) -> int:
    return self.prefix_cache.misses if self.prefix_cache is not None else 0

  @property
  def prefix_blocks_reused(self) -> int:
    return (self.prefix_cache.blocks_reused
            if self.prefix_cache is not None else 0)

  @property
  def prefix_evictions(self) -> int:
    return (self.prefix_cache.evictions
            if self.prefix_cache is not None else 0)

  @property
  def prefix_cached_blocks(self) -> int:
    return (self.prefix_cache.num_cached_blocks
            if self.prefix_cache is not None else 0)

  def invalidate_cached_blocks(self, blocks) -> int:
    """Purge ``blocks`` from the prefix cache (engine sanitize: zeroed
    K/V must never satisfy a future match).  No-op without a cache."""
    if self.prefix_cache is None:
      return 0
    return self.prefix_cache.invalidate_blocks(blocks)

  def _register_cached(self, state: _SlotState) -> None:
    """Register ``state``'s newly COMPLETED full blocks in the prefix
    tree — called at commit watermarks (prefill advance, decode block
    boundaries) and at retirement (session persistence).  Only blocks
    strictly below the committed-K/V watermark register, so a tree
    entry always describes fully-written, commit-gated content; the
    partial tail block (and any position a bad step may have scribbled
    on) stays private to the slot."""
    upto = self._resident_tokens(state)
    n = upto // self.block_size
    blocks = self._slot_blocks.get(state.slot)
    if blocks is not None:
      n = min(n, len(blocks))
    else:
      n = 0
    if n <= state.reg_blocks:
      return
    if state.prefilling:
      tokens = state.prefix  # covers [0, prompt_pos) — exactly what fed
    else:
      tokens = np.concatenate(
          [state.req.prompt, np.asarray(state.generated, np.int32)])
    self.prefix_cache.register(tokens, n, blocks)
    state.reg_blocks = n

  def _preemption_victim(self, req_rank, excluded: set) -> Optional[int]:
    """Shared eligibility rule for BOTH preemption paths (pool
    exhaustion and proactive latency admission).  Victim choice: lowest
    priority class first, then the youngest admission — the
    least-progress slot loses.  A victim must rank strictly below the
    requester (``(is_latency, -admit_seq)`` ordering, so two starving
    peers can never preempt each other in a cycle), must not be in
    ``excluded`` (the requester itself, or slots already holding
    scheduled work in the plan being built — their in-flight writes
    would race the reallocated blocks), and must actually hold blocks
    (a blockless victim frees nothing: evicting it would requeue a
    request — burning its queue position — without refilling the
    pool)."""
    best = None
    best_rank = None
    for slot, state in self.active.items():
      if slot in excluded:
        continue
      if not self._slot_blocks.get(slot):
        continue
      rank = (state.req.priority == "latency", -state.admit_seq)
      if rank >= req_rank:
        continue  # only strictly lower-priority-or-younger slots
      if best is None or rank < best_rank:
        best, best_rank = slot, rank
    return best

  def _preempt_for_blocks(self, requester: int,
                          scheduled: set) -> Optional[int]:
    """Page out one victim to refill the pool (satellite of ROADMAP
    item 1: exhaustion preempts instead of raising).  Eligibility:
    :meth:`_preemption_victim`.  Returns the victim slot or None."""
    req_state = self.active.get(requester)
    if req_state is None:
      return None
    req_rank = (req_state.req.priority == "latency", -req_state.admit_seq)
    best = self._preemption_victim(req_rank, scheduled | {requester})
    if best is None:
      return None
    uid = self.active[best].req.uid
    self.preemptions += 1
    get_logger().warning(
        "KV block pool exhausted: preempting slot %d (uid %r) to refill "
        "it; the request replays its committed prefix on readmission",
        best, uid)
    self.requeue_slot(best, reason="preempted")
    return best

  def _preempt_for_latency_admission(self) -> Optional[int]:
    """Proactive latency-class preemption (ROADMAP item 5 leftover):
    when a ``latency``-priority request is next in line but admission is
    capacity-blocked (no free slot, or the batch cap is full), evict the
    youngest throughput-class slot holding blocks NOW — eagerly, at
    admission — instead of making the latency request wait for a natural
    retirement or the pool to run dry.  Same eligibility rules as
    exhaustion preemption (admission-seq ordering — an older
    latency-class slot is never evicted for a younger latency arrival —
    and draft headroom still never preempts: that rule lives in
    ``_ensure_blocks(preempt=False)``, untouched here).  Returns the
    victim slot or None; counted separately as
    ``proactive_preemptions``."""
    # The would-be admission's rank: strictly younger than every active
    # slot, latency class — so exactly the throughput-class actives are
    # eligible, youngest first.
    req_rank = (True, -(self._admit_seq + 1))
    best = self._preemption_victim(req_rank, set())
    if best is None:
      return None
    uid = self.active[best].req.uid
    self.proactive_preemptions += 1
    get_logger().info(
        "proactive preemption: evicting throughput slot %d (uid %r) to "
        "admit a latency-class request; the victim replays its committed "
        "prefix on readmission", best, uid)
    self.requeue_slot(best, reason="preempted")
    return best

  def _ensure_blocks(self, slot: int, num_tokens: int, scheduled: set,
                     preempt: bool = True) -> int:
    """Grow ``slot``'s block list to cover ``num_tokens`` positions,
    preempting victims when the pool runs dry (``preempt=False`` for
    optional work — speculative draft headroom must never evict a
    request's committed K/V).  Returns the number of positions actually
    covered (callers shrink their grant to it — a short allocation
    starves the slot for a step, never corrupts)."""
    blocks = self._slot_blocks.setdefault(slot, [])
    need = min((num_tokens + self.block_size - 1) // self.block_size,
               self._mb)
    while len(blocks) < need:
      blk = self.block_allocator.alloc()
      if blk is None:
        # Reclamation order on a dry pool: cached-but-unmapped prefix
        # blocks first (pure cache — dropping them costs a future
        # admission some prefill, never a live request its progress),
        # preemption only once the tree has nothing evictable.  A
        # preempted victim's released blocks may themselves become
        # tree-only references, which the NEXT iteration's eviction
        # pass then reclaims.
        if (self.prefix_cache is not None
            and self.prefix_cache.evict_for_space(
                need - len(blocks)) > 0):
          continue
        if not preempt or self._preempt_for_blocks(slot, scheduled) is None:
          break
        continue
      self._tables[slot, len(blocks)] = blk
      blocks.append(blk)
    return min(len(blocks) * self.block_size, self.max_seq_len)

  def _plan_flat(self) -> Optional[PagedStepPlan]:
    """Token-budget planning: the paged twin of the slot-block half of
    :meth:`plan_step`.  Three passes over admission order fill the flat
    batch: (1) every decoding slot gets its one guaranteed token (ITL
    protection — ``token_budget >= max_batch`` is validated so this pass
    never starves), (2) prefill chunks stream in while the flat budget
    and the prefill-token budget allow, (3) leftover budget is reserved
    for speculative drafts (drafts ride spare capacity here, exactly as
    they ride wasted chunk positions in the slot engine).  Block
    coverage is ensured per grant; a dry pool preempts the youngest
    lowest-priority slot, and a still-short allocation shrinks the grant
    (the slot resumes next step)."""
    if not self.active:
      return None
    T, N, MB = self.token_budget, self.num_slots, self._mb
    plan = PagedStepPlan(
        tokens=np.zeros((T,), np.int32),
        slot_ids=np.zeros((T,), np.int32),
        positions=np.zeros((T,), np.int32),
        valid=np.zeros((T,), bool),
        block_tables=np.zeros((N, MB), np.int32),
        base_idx=np.zeros((N,), np.int32),
        draft_base=np.zeros((N,), np.int32),
        num_valid=np.zeros((N,), np.int32),
        draft_cap=np.zeros((N,), np.int32),
        prefilling=np.zeros((N,), bool),
        keys=np.zeros((N, 2), np.uint32),
        tok_index=np.zeros((N,), np.int32),
        temperature=np.zeros((N,), np.float32),
        top_k=np.zeros((N,), np.int32),
        top_p=np.ones((N,), np.float32),
        prefill_tokens=0, decode_tokens=0, scheduled_tokens=0,
        active_slots=len(self.active))
    budget = self._effective_budget()
    pos = 0
    scheduled: set = set()
    # Pass 1: decode slots — one guaranteed token each.
    for slot in list(self._admit_order):
      state = self.active.get(slot)
      if state is None or state.prefilling:
        continue
      dec_pos = self._resident_tokens(state)
      if self._ensure_blocks(slot, dec_pos + 1, scheduled) < dec_pos + 1:
        continue  # pool exhausted with no eligible victim: starve a step
      state = self.active.get(slot)
      if state is None:
        continue  # defensive: a preemption cascade evicted this slot
      plan.base_idx[slot] = pos
      plan.tokens[pos] = state.generated[-1]
      plan.slot_ids[pos] = slot
      plan.positions[pos] = dec_pos
      plan.valid[pos] = True
      plan.num_valid[slot] = 1
      plan.decode_tokens += 1
      pos += 1
      scheduled.add(slot)
    # Pass 2: prefill chunks under both budgets.
    for slot in list(self._admit_order):
      state = self.active.get(slot)
      if state is None or not state.prefilling or pos >= T:
        continue
      remaining = len(state.prefix) - state.prompt_pos
      grant = min(self.chunk, remaining, T - pos)
      if budget > 0:
        grant = min(grant, max(budget - plan.prefill_tokens, 0))
      if grant <= 0:
        continue
      covered = self._ensure_blocks(slot, state.prompt_pos + grant,
                                    scheduled)
      grant = min(grant, covered - state.prompt_pos)
      state = self.active.get(slot)
      if state is None or grant <= 0:
        continue
      chunk = state.prefix[state.prompt_pos:state.prompt_pos + grant]
      plan.base_idx[slot] = pos
      plan.tokens[pos:pos + grant] = chunk
      plan.slot_ids[pos:pos + grant] = slot
      plan.positions[pos:pos + grant] = np.arange(
          state.prompt_pos, state.prompt_pos + grant)
      plan.valid[pos:pos + grant] = True
      plan.num_valid[slot] = grant
      plan.prefilling[slot] = True
      plan.prefill_tokens += grant
      pos += grant
      scheduled.add(slot)
    # Pass 3: speculative draft reservations ride the leftover budget.
    spec_k = self.effective_spec_k
    if spec_k > 0 and self.spec_enabled:
      for slot in list(self._admit_order):
        state = self.active.get(slot)
        if (state is None or state.prefilling
            or plan.num_valid[slot] != 1 or pos >= T
            or state.req.speculative is False):
          continue
        remaining = state.req.max_new_tokens - len(state.generated)
        cap = max(0, min(spec_k, remaining - 1, T - pos))
        if cap <= 0:
          continue
        dec_pos = int(plan.positions[plan.base_idx[slot]])
        # Draft headroom is OPTIONAL work: never preempt for it — a dry
        # pool just shrinks the draft cap (drafts ride spare capacity).
        covered = self._ensure_blocks(slot, dec_pos + 1 + cap, scheduled,
                                      preempt=False)
        cap = max(0, min(cap, covered - 1 - dec_pos))
        if cap <= 0 or self.active.get(slot) is None:
          continue
        plan.draft_base[slot] = pos
        plan.slot_ids[pos:pos + cap] = slot
        plan.positions[pos:pos + cap] = np.arange(dec_pos + 1,
                                                  dec_pos + 1 + cap)
        # valid stays False: the engine flips exactly the positions the
        # drafter fills (serving/engine.py _propose_drafts).
        plan.draft_cap[slot] = cap
        pos += cap
    # Per-slot sampling state for every slot with scheduled work.
    for slot in self._admit_order:
      state = self.active.get(slot)
      if state is None or plan.num_valid[slot] == 0:
        continue
      req = state.req
      plan.keys[slot] = state.key
      plan.tok_index[slot] = len(state.generated)
      plan.temperature[slot] = req.temperature
      plan.top_k[slot] = req.top_k
      plan.top_p[slot] = req.top_p
      plan.live_kv_rows += (self._resident_tokens(state)
                            + int(plan.num_valid[slot]))
      self._note_fed(plan, slot, state)
    plan.scheduled_tokens = pos
    plan.block_tables = self._tables.copy()
    if pos == 0:
      # Every active slot starved (pool exhausted, budget zero): no
      # device work this iteration.
      return None
    self._plans.append(plan)
    return plan

  def _note_fed(self, plan, slot: int, state: _SlotState) -> None:
    """Record that ``plan`` feeds ``slot`` from ``state`` and advance the
    state provisionally by it (class docstring); ``_settle`` takes the
    advance back when the plan commits or is abandoned."""
    fed = int(plan.num_valid[slot]) if plan.prefilling[slot] else 0
    state.fed_ahead += fed
    # The slot's sample is a generated token once its prefix is fed.
    sampled = state.planned_pos >= len(state.prefix)
    state.samples_ahead += sampled
    plan.fed.append((slot, state, fed, sampled))

  @staticmethod
  def _settle(plan) -> None:
    """Take back what ``plan`` advanced provisionally."""
    for _, state, fed, sampled in plan.fed:
      state.fed_ahead -= fed
      state.samples_ahead -= sampled

  def abandon(self, plan=None) -> None:
    """Forget ``plan`` (default: every outstanding plan) without
    committing it: a step that was planned and never ran, or whose
    output was lost.  The committed state is untouched, so the next plan
    feeds the same work again."""
    for old in ([plan] if plan is not None else list(self._plans)):
      self._plans.remove(old)
      self._settle(old)

  def plan_step(self, ahead: bool = False) -> Optional[StepPlan]:
    """Build the next fused step's inputs, or None when idle.

    Order: expire dead requests, admit (priority first, then FCFS),
    then grant tokens.  Budgeting: decode slots always get their one
    token (decode latency is the metric continuous batching protects);
    prefill chunks are granted FCFS in admission order until the
    per-step budget runs out — a starved prefill slot simply carries
    ``num_valid=0`` this step and resumes next step.  The fused step's
    flat batch is a second ceiling on the same path (``width``): the
    prefill grants share the rows the decoding slots leave, the grant
    that reaches the last row is cut short and resumes at its cursor.

    ``ahead=True`` plans PAST the one outstanding plan, whose step is
    still running (class docstring): each slot goes on from where that
    plan leaves it, a slot that plan brings to ``max_new_tokens`` is fed
    nothing more (it retires at that plan's commit), and a decoding
    slot's input token is that plan's sample (``from_prev``).  Without
    it an outstanding plan is a step that never ran: it is abandoned and
    planned again.
    """
    if not ahead:
      self.abandon()
    elif len(self._plans) > 1 or self.paged:
      raise RuntimeError(
          "plan_step(ahead=True) plans past ONE uncommitted step of the "
          "contiguous cache; commit() the oldest plan first")
    self.expire()
    if self.prefix_cache is not None:
      # Session TTL sweep before admission, so an expired session can
      # never satisfy this iteration's matches.  O(expired) — the
      # cache's LRU front is its least-recent entry.
      self.prefix_cache.expire()
    self._admit()
    if self.paged:
      return self._plan_flat()
    if not self.active:
      return None
    N, C = self.num_slots, self.chunk
    plan = StepPlan(
        tokens=np.zeros((N, C), np.int32),
        num_valid=np.zeros((N,), np.int32),
        reset=np.zeros((N,), bool),
        keys=np.zeros((N, 2), np.uint32),
        tok_index=np.zeros((N,), np.int32),
        temperature=np.zeros((N,), np.float32),
        top_k=np.zeros((N,), np.int32),
        top_p=np.ones((N,), np.float32),
        draft_cap=np.zeros((N,), np.int32),
        prefilling=np.zeros((N,), bool),
        prefill_tokens=0, decode_tokens=0,
        active_slots=len(self.active), from_prev=np.zeros((N,), bool),
        resident=np.zeros((N,), np.int32))
    budget = self._effective_budget()
    room = self._prefill_room()
    # Prefill positions granted so far in each run of slots (one run: the
    # plan's own count).
    per_group = N // self.slot_groups
    granted = [0] * self.slot_groups
    spec_k = self.effective_spec_k        # hoisted: loop-invariant
    for slot in self._admit_order:
      state = self.active.get(slot)
      if state is None:
        continue
      req = state.req
      # Where the outstanding plan (if any) leaves the slot.
      pos = state.planned_pos
      generated = state.planned_generated
      prefilling = pos < len(state.prefix)
      if not prefilling and generated >= req.max_new_tokens:
        # Its last token is on the device: nothing more to feed.  The
        # slot is held, and idle, until that step commits and retires it.
        plan.active_slots -= 1
        continue
      plan.keys[slot] = state.key
      plan.tok_index[slot] = generated
      plan.temperature[slot] = req.temperature
      plan.top_k[slot] = req.top_k
      plan.top_p[slot] = req.top_p
      # Nothing fed yet (fresh slot, or a requeued request starting its
      # replay): zero the cursor before this step's writes.
      plan.reset[slot] = pos == 0
      if prefilling:
        grant = min(C, len(state.prefix) - pos)
        if budget > 0:
          grant = min(grant, max(budget - plan.prefill_tokens, 0))
        group = slot // per_group
        if room is not None and grant > room[group] - granted[group]:
          fits = max(room[group] - granted[group], 0)
          plan.flat_trimmed += grant - fits
          grant = fits
        if grant == 0:
          continue  # starved of budget or width this step; resumes next
        plan.tokens[slot, :grant] = state.prefix[pos:pos + grant]
        plan.num_valid[slot] = grant
        plan.prefilling[slot] = True
        plan.prefill_tokens += grant
        granted[group] += grant
      else:
        if state.samples_ahead:
          plan.from_prev[slot] = True   # the sample is still on the device
        else:
          plan.tokens[slot, 0] = state.generated[-1]
        plan.num_valid[slot] = 1
        plan.decode_tokens += 1
        if (spec_k > 0 and self.spec_enabled
            and req.speculative is not False):
          # Drafting past the request's remaining budget is pure waste:
          # at most (remaining - 1) drafts can commit alongside the
          # step's guaranteed token.
          remaining = req.max_new_tokens - generated
          plan.draft_cap[slot] = max(0, min(spec_k, remaining - 1))
      plan.resident[slot] = self._resident_tokens(state)
      plan.live_kv_rows += (int(plan.resident[slot])
                            + int(plan.num_valid[slot]))
      self._note_fed(plan, slot, state)
    if not plan.fed and ahead:
      return None   # every slot's remaining work is already on the device
    if room is not None and spec_k > 0:
      self._fit_drafts(plan)
    self._plans.append(plan)
    return plan

  def _prefill_room(self) -> Optional[List[int]]:
    """Positions the next plan may hand to prefill under the flat batch's
    width, a run of slots (``slot_groups``; one entry where there is one):
    the width less one row a decoding slot of the run, which is never
    held back.  None where no plan of the active slots could reach the
    width (the usual case: nothing is counted)."""
    per_group = self.num_slots // self.slot_groups
    if not self.width or min(len(self.active),
                             per_group) * self.chunk <= self.width:
      return None
    decoding = [0] * self.slot_groups
    for slot, s in self.active.items():
      decoding[slot // per_group] += (
          s.planned_pos >= len(s.prefix)
          and s.planned_generated < s.req.max_new_tokens)
    return [self.width - n for n in decoding]

  def _fit_drafts(self, plan: StepPlan) -> None:
    """Speculative drafts ride the rows the plan's own positions leave of
    the width, in admission order; a draft that does not fit is not
    proposed."""
    # (speculation is refused where the slots fall into several runs)
    spare = self.width - plan.prefill_tokens - plan.decode_tokens
    if int(plan.draft_cap.sum()) <= spare:
      return
    for slot, *_ in plan.fed:
      cap = int(plan.draft_cap[slot])
      fits = min(cap, spare)
      plan.flat_trimmed += cap - fits
      plan.draft_cap[slot] = fits
      spare -= fits

  def slot_histories(self, plan: StepPlan) -> Dict[int, np.ndarray]:
    """Committed tokens (prompt + generated) per draft-eligible slot of
    ``plan`` — the context drafters propose from."""
    out: Dict[int, np.ndarray] = {}
    for slot, state in self.active.items():
      if plan.draft_cap[slot] > 0:
        out[slot] = np.concatenate(
            [state.req.prompt,
             np.asarray(state.generated, np.int32)])
    return out

  # --------------------------------------------------------------- commit

  def _emit_tokens(self, uid: Any, fresh: List[int]) -> None:
    """Fan one request's just-committed tokens out to the ``on_tokens``
    subscribers — always BEFORE any retirement those tokens trigger, so
    a streaming consumer sees every token ahead of the finish event."""
    for fn in self.on_tokens:
      fn(uid, fresh)

  def _retire(self, state: _SlotState, reason: str) -> FinishedRequest:
    slot = state.slot
    del self.active[slot]
    self._admit_order.remove(slot)
    self.allocator.free(slot)
    tracer = trace_lib.get_tracer()
    self._trace_phase_end(tracer, state, reason)
    # Session KV persistence: register the retiring request's completed
    # blocks BEFORE releasing the slot's references, so a multi-turn
    # follow-up (its next prompt = this conversation's full history)
    # admits warm.  The tree's own references keep the blocks resident
    # under its TTL/LRU budget.  A quarantine-overflow retirement
    # ("failed") never registers — its device state is untrusted.
    if self.prefix_cache is not None and reason != "failed":
      self._register_cached(state)
    self._release_blocks(slot)
    self._deadline_active -= self._has_deadline(state.req)
    if tracer.enabled:
      if state.req.flow_id is not None:
        tracer.flow("f", state.req.flow_id,
                    track=_slot_track(slot, self.track_prefix),
                    args={"uid": str(state.req.uid), "reason": reason})
      tracer.end(
          f"request {state.req.uid}", cat="serving.request",
          track=_slot_track(slot, self.track_prefix),
          args={"finish_reason": reason,
                "new_tokens": int(len(state.generated))})
    fin = FinishedRequest(
        uid=state.req.uid,
        tokens=np.concatenate(
            [state.req.prompt,
             np.asarray(state.generated, np.int32)]),
        new_tokens=len(state.generated),
        finish_reason=reason)
    self._finished_buffer.append(fin)
    for fn in self.on_finish:
      fn(fin)
    return fin

  def commit(self, next_tokens: np.ndarray,
             num_committed: Optional[np.ndarray] = None,
             slot_ok: Optional[np.ndarray] = None,
             num_draft: Optional[np.ndarray] = None
             ) -> List[FinishedRequest]:
    """Fold one step's committed tokens back into request state; returns
    this iteration's retirements (commit-time plus any buffered
    plan-time expiries).  ``next_tokens`` is ``[N]`` (one sampled token
    per slot, the non-speculative step) or ``[N, K+1]`` with
    ``num_committed [N]`` (speculative verification: accepted drafts
    plus the correction/bonus token).  ``slot_ok`` (bool [N], engine
    resilience) marks slots whose device step was judged bad — those are
    skipped WHOLESALE (no prefix advance, no token commit), which makes
    the next ``plan_step`` re-feed the identical work: the cursor never
    moved, so the replay is the retry.  A slot's tokens only count when
    its prompt is fully consumed — mid-prefill samples are positions
    whose "next token" is still dictated by the prompt.  Multi-token
    commits apply stop-token and ``max_new_tokens`` checks PER TOKEN in
    commit order, so a stop token appearing mid-draft retires the
    request and discards the rest of its accepted drafts.  ``num_draft``
    (int [N], speculative engines) is what each slot drafted, for the
    request's ``serving/decode`` span alone."""
    if not self._plans:
      raise RuntimeError("commit() without a preceding plan_step()")
    plan = self._plans.popleft()
    self._settle(plan)
    tokens = np.asarray(next_tokens)
    if tokens.ndim == 1:
      tokens = tokens[:, None]
    if num_committed is None:
      num_committed = np.ones((tokens.shape[0],), np.int32)
    now = self.clock()
    tracer = trace_lib.get_tracer()
    for slot, state, _, _ in plan.fed:
      if self.active.get(slot) is not state:
        # Retired (or requeued) since the plan was made: a stop token, a
        # cancellation or a deadline seen one step late.  The position
        # ran for nothing and its sample goes nowhere.
        plan.wasted += 1
        continue
      if slot_ok is not None and not slot_ok[slot]:
        continue  # bad step: state untouched — next plan retries exactly
      req = state.req
      phase = state.phase                 # None unless admitted traced
      if phase is not None:
        phase.steps += 1
        if num_draft is not None and phase.decoding:
          phase.drafted += int(num_draft[slot])
          phase.accepted += int(num_committed[slot]) - 1
      if plan.prefilling[slot]:
        state.prompt_pos += int(plan.num_valid[slot])
        if state.prefilling:
          # More prompt to feed; discard the sample — but the chunk
          # just committed may have COMPLETED full blocks: register
          # them now so a concurrent same-prefix admission already
          # shares them mid-prefill.
          if self.prefix_cache is not None:
            self._register_cached(state)
          continue
        if phase is not None:
          # The prefix is fed and this commit emits the occupancy's first
          # token: ``serving/prefill`` ends and ``serving/decode`` starts
          # on one stamp.  A requeued request's replay ends here too.
          first_us = self._trace_phase_end(tracer, state)
          if first_us is not None:
            state.phase = _Phase(first_us, len(state.generated) + 1,
                                 decoding=True)
        if not state.first_token_emitted:
          state.first_token_emitted = True
          state.first_token_at = now
          if tracer.enabled:
            tracer.instant(
                "serving/first_token", cat="serving",
                track=_slot_track(slot, self.track_prefix),
                args={"uid": str(req.uid)})
          for fn in self.on_first_token:
            fn(req.uid)
        # A requeued replay commits this sample too: the last prefix
        # position's logits ARE the distribution for new token number
        # len(generated) — identical to the undisturbed decode step
        # (tok_index fold included), so the stream continues bit-exactly.
      fresh: List[int] = []
      retired = False
      for j in range(int(num_committed[slot])):
        tok = int(tokens[slot, j])
        state.generated.append(tok)
        fresh.append(tok)
        if req.stop_token >= 0 and tok == req.stop_token:
          if self.on_tokens:
            self._emit_tokens(req.uid, fresh)
          self._retire(state, "stop_token")
          retired = True
          break
        if len(state.generated) >= req.max_new_tokens:
          if self.on_tokens:
            self._emit_tokens(req.uid, fresh)
          self._retire(state, "length")
          retired = True
          break
      if not retired and fresh and self.on_tokens:
        self._emit_tokens(req.uid, fresh)
      # Decode watermark registration: committed tokens may have pushed
      # the written-K/V frontier across a block boundary — register the
      # freshly completed block(s).  A retirement above already
      # registered via _retire; `is state` guards the stale reference.
      if self.prefix_cache is not None and self.active.get(slot) is state:
        self._register_cached(state)
    self.wasted_positions += plan.wasted
    return self.take_finished()
