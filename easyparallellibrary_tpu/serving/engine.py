"""Continuous-batching inference engine over the TP mesh.

The fourth runtime mode (train / eval / generate / **serve**): ONE
jitted step — compiled once, shapes never change — fuses

  * prefill of newly admitted requests (their next prompt chunk), and
  * one-token decode of every other active slot

into a single model call against the slot KV cache (kv_cache.py),
per-slot cursors selecting each slot's absolute positions and causal
window (models/slot_core.py ``slot_cache_attend``).  The plan is a
``[num_slots, chunk]`` block; the call does its position-wise work on
the block's LIVE positions, packed in slot order into a token-flat
batch of ``flat_width(num_slots, chunk)`` rows, moves to the
``[num_slots, chunk, ...]`` layout only around the operations that own
per-slot state (the window write, the attend, a recurrence), and runs
the head on the one row a slot samples from (models/slot_core.py
``SlotRows``; docs/serving.md "The flat batch").
Requests therefore join and leave the batch every iteration with zero
recompilation — iteration-level batching as in Orca (OSDI'22) — and the
cache + cursor buffers are donated, so the engine's steady-state device
allocation is exactly one cache.

Division of labor: :class:`FCFSScheduler` (scheduler.py) owns all
host-side variability (admission, budgets, retirement, RNG streams);
:mod:`serving.resilience` owns fault/overload POLICY (admission
control, the degradation ladder, retry-vs-quarantine); this module owns
the device program, its placement, and the mechanics that policy drives.
Sampling runs per-slot inside the step (:func:`sample_token_slots` —
the traced-parameter twin of ``sample_logits``) with per-request keys
folded by token index, so a request's sample stream is independent of
which slot or iteration serves it.

Speculative decoding (serving/speculative/) rides the same fused step:
a drafter fills each decode slot's unused chunk positions with ``k``
guessed tokens, the one model call scores all of them (verification is
a prefill-shaped call — nearly free in this step), and in-jit per-slot
accept/rollback commits the accepted prefix plus one correction/bonus
token, rolling cursors back to the last accepted position.  Toggled by
``serving.speculative.*`` / per-request ``Request.speculative``.

Resilience (``serving.resilience.*``; docs/robustness.md): with the
group enabled, the fused step additionally returns a per-slot
finiteness verdict on exactly the logit rows the commit consumes — the
PR-2 sentinel pattern, in-trace, zero extra host syncs (the verdict
rides the step's own token fetch) — and gates each slot's cursor
advance on it, so a bad step never moves device state.  The host side
then simply replans: the retry re-feeds identical tokens (exact by
construction), persistent offenders are requeued with their committed
prefix (scheduler.requeue_slot — replay through chunked prefill
rebuilds KV and cursors bit-exactly), and hopeless ones are failed.
Overload is answered at submit (bounded queue + shedding) and by the
degradation ladder (speculation off -> prefill budget tightened ->
shed), never by touching admitted requests' outputs.

Exactness contract: greedy engine output is bit-identical (token ids)
to ``generate(use_cache=True)`` per request — the legacy path stays the
oracle (tests/test_serving.py), including requests admitted mid-flight,
slots reused after retirement, retried/requeued slots, and degradation
transitions (tests/test_serving_resilience.py).  Greedy SPECULATIVE
output keeps the same contract (exact-match acceptance); sampled
speculative output keeps the sampling distribution, not the bitstream
(tests/test_serving_speculative.py).
"""

from __future__ import annotations

import dataclasses
import os
import time
import weakref
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from easyparallellibrary_tpu.env import Env
from easyparallellibrary_tpu.models.slot_core import (
    paged_step_logits, slot_step_logits)
from easyparallellibrary_tpu.observability import device as device_lib
from easyparallellibrary_tpu.observability import slo as slo_lib
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.observability.registry import (
    SERVING_NAMESPACE, MetricRegistry)
from easyparallellibrary_tpu.serving import kv_cache as kv_lib
from easyparallellibrary_tpu.serving._capabilities import (
    check_divided, check_draft_fits_chunk, check_kv_window,
    check_latent_cache,
    check_recurrent_state,
    check_servable, step_overlap)
from easyparallellibrary_tpu.serving.resilience import (
    AdmissionController, BadStepPolicy, DEGRADE_LEVELS)
from easyparallellibrary_tpu.serving.scheduler import (
    FCFSScheduler, FinishedRequest, Request, _slot_track)
from easyparallellibrary_tpu.utils.logging import get_logger

# Periodic ServingStats rollup cadence (engine steps): per-step records
# carry only step-local gauges, so the TTFT/ITL percentile SLO rules
# would otherwise only ever see a rollup at the END of a run() drive —
# and never for router-driven replicas, which step() forever.  The
# rollup is O(sample cap) thanks to the stats reservoirs.
_STATS_PUBLISH_EVERY = 50


def filtered_logits(logits, temperature, top_k, top_p):
  """Per-row temperature/top-k/top-p filtering with TRACED parameters —
  the distribution half of :func:`sample_token_slots` (same filter
  semantics and order as ``models.gpt.sample_logits``: top-k, then top-p
  over the survivors), shared with speculative verification
  (serving/speculative/verify.py), whose acceptance rule must judge
  drafts against EXACTLY the distribution sampling would draw from.

  ``logits`` [M, V]; ``temperature``/``top_p`` f32 [M]; ``top_k`` int32
  [M] (0 disables).  Returns the scaled, filtered logits [M, V]
  (filtered entries at -1e30); their softmax is the sampling
  distribution at ``temperature > 0``.

  The vocabulary is sorted only when the parameters ask for it, decided
  on the device by ``lax.cond`` (the caller stays one compiled program):
  with no filter on in any row (``0 < top_k < V`` or ``top_p < 1``) the
  scaled logits are the answer and nothing is sorted; otherwise ONE
  sort serves both filters for the whole batch.
  """
  V = logits.shape[-1]
  t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
  scaled = logits / t.astype(logits.dtype)
  filter_on = ((top_k > 0) & (top_k < V)) | (top_p < 1.0)
  return jax.lax.cond(jnp.any(filter_on),
                      lambda: _filter_sorted(scaled, top_k, top_p),
                      lambda: scaled)


def _filter_sorted(scaled, top_k, top_p):
  """The filtering branch of :func:`filtered_logits`: top-k, then top-p
  over the survivors, from one descending sort of ``scaled``."""
  V = scaled.shape[-1]
  neg = jnp.asarray(-1e30, scaled.dtype)
  # top-k with a traced k: threshold at the k-th largest value (ties at
  # the threshold survive, exactly like sample_logits' `logits < kth`).
  sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
  kth = jnp.take_along_axis(
      sorted_desc, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
  k_off = (top_k[:, None] <= 0) | (top_k[:, None] >= V)
  scaled = jnp.where((scaled >= kth) | k_off, scaled, neg)
  # The survivors sorted again ARE the first sort with its tail masked:
  # in descending order the entries >= kth are a prefix, and what top-k
  # drops becomes -1e30, which sorts last (logits lie above -1e30).
  sorted_desc = jnp.where((sorted_desc >= kth) | k_off, sorted_desc, neg)
  # top-p over the survivors: keep entries whose PRECEDING mass is < p
  # (the crossing token survives; the top token always survives).
  probs = jax.nn.softmax(sorted_desc.astype(jnp.float32), axis=-1)
  cum = jnp.cumsum(probs, axis=-1)
  keep_sorted = (cum - probs) < top_p[:, None]
  thresh = jnp.min(jnp.where(keep_sorted, sorted_desc,
                             jnp.asarray(jnp.inf, scaled.dtype)),
                   axis=-1, keepdims=True)
  p_on = top_p[:, None] < 1.0
  return jnp.where(p_on & (scaled < thresh), neg, scaled)


def sample_token_slots(logits, keys, temperature, top_k, top_p):
  """Per-slot sampling with TRACED parameters — the vectorized twin of
  ``models.gpt.sample_logits``, for the serving step where every slot
  carries its own sampling knobs and every value must be an array
  (static per-request values would recompile the fused step per
  parameter combination).  ``temperature<=0`` is greedy.

  ``logits`` [N, V]; ``keys`` uint32 [N, 2] per-slot PRNG keys;
  ``temperature``/``top_p`` f32 [N]; ``top_k`` int32 [N] (0 disables).
  Returns int32 [N] token ids.

  The work follows the parameters, chosen on the device by ``lax.cond``
  so the step stays one compiled program: (1) no slot samples (every
  ``temperature <= 0``; idle slots carry 0): the argmax alone, with no
  scaling, sort, softmax or noise; (2) some slot samples and no filter
  is on: scaling and ``categorical``, no sort; (3) a filter is on in
  some row: one sort (:func:`filtered_logits`).  A mixed batch pays for
  the whole batch; the tokens are the same in every case.
  """
  greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

  def sample():
    scaled = filtered_logits(logits, temperature, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temperature <= 0, greedy, sampled).astype(jnp.int32)

  return jax.lax.cond(jnp.any(temperature > 0), sample, lambda: greedy)


def _expert_stats(sown, axis=None):
  """What a step hands back of its expert layers' sown ``stats``
  (models/moe.py ``DroplessMoE``), float32 ``[2]`` (``[3]`` where the
  layers hold a share of their experts): the busiest expert's
  load over the mean, worst layer, and the fewest experts a layer
  touched.  Reduced on the device, fetched with the tokens.  Inside a
  divided step's ``shard_map`` (``axis``) each layer's numbers are first
  brought together over the chips (the load's largest, the touched and
  the held summed), and three more follow, ``[6]``: the assignments that
  left their position's chip, those that arrived (summed over layers and
  chips), and the most rounds an exchange took."""
  named = lambda name: [
      leaf for path, leaf in jax.tree_util.tree_leaves_with_path(sown)
      if any(getattr(k, "key", None) == name for k in path)]
  if axis is None:
    most = total = lambda v: v
  else:
    most = lambda v: jax.lax.pmax(v, axis)
    total = lambda v: jax.lax.psum(v, axis)
  out = [jnp.max(most(jnp.stack(named("expert_load")))),
         jnp.min(total(jnp.stack(named("experts_touched"))))]
  held = named("held_assignments")
  if held:
    # Layers that hold a share of their experts (``cfg.experts_held``):
    # the live assignments that fell on held ones, summed over the
    # layers, third in the same array.
    out.append(total(jnp.sum(jnp.stack(held))))
  if axis is not None:
    out += [total(jnp.sum(jnp.stack(named("exchange_rows_out")))),
            total(jnp.sum(jnp.stack(named("exchange_rows_in")))),
            jnp.max(most(jnp.stack(named("exchange_rounds"))))]
  return jnp.stack(out)


def _rows_up_to(resident, num_valid, most: int) -> int:
  """``sum over live queries of min(t + 1, most)``: slot ``b``'s live
  queries sit at ``t = resident[b] + i``, ``i < num_valid[b]``.  Closed
  form a slot (the queries still under ``most`` count an arithmetic
  series, the others ``most`` each), summed with numpy."""
  r, n = resident.astype(np.int64), num_valid.astype(np.int64)
  under = np.clip(most - r, 0, n)
  return int(np.sum(under * (r + 1) + under * (under - 1) // 2
                    + (n - under) * most))


def _slot_rows(resident, num_valid, window: int):
  """``(context_rows, kv_window_rows)`` of a plan: over its live slots, the
  rows under each slot's bound (``resident + num_valid``: what a full
  layer must read) and the same with each slot's term held to ``window -
  1 + num_valid`` (what a layer behind a window must read: the window's
  reach behind the step's first query, and the step's own rows)."""
  r, n = resident.astype(np.int64), num_valid.astype(np.int64)
  bound = np.where(n > 0, r + n, 0)
  return (int(np.sum(bound)),
          int(np.sum(np.minimum(bound, np.where(n > 0, window - 1 + n, 0)))))


def _walk_rows(resident, num_valid, granule: int, length: int) -> int:
  """Rows one layer's ``slot_attn`` walk covers in a step: each live
  slot's bound ``resident + num_valid`` rounded up to ``granule`` and held
  to ``length``, the sum of ``kernels/slot_attention.py:live_pieces``'
  rows, from the plan."""
  r, n = resident.astype(np.int64), num_valid.astype(np.int64)
  bound = np.where(n > 0, r + n, 0)
  return int(np.sum(np.minimum(-(-bound // granule) * granule, length)))


def _tile_positions(num_valid, tile: int, decode: int) -> int:
  """Chunk positions one layer's tile-grid attend works on in a step: a
  slot that feeds several positions its live tiles of ``tile``, one that
  feeds one ``decode`` (serving/kv_cache.py:attn_tile), from the plan."""
  n = num_valid.astype(np.int64)
  return int(np.sum(np.where(n == 1, decode, -(-n // tile) * tile)))


def flat_width(num_slots: int, chunk: int) -> int:
  """Rows ``T`` of the token-flat batch the contiguous fused step runs its
  position-wise layers on (models/slot_core.py:SlotRows), static for the
  engine's life: one compiled program a twin.  A step computes ``T`` rows
  whatever is live, so ``T`` is sized for what a step holds, not for every
  position of every slot: HALF of ``num_slots x chunk``, up to a multiple
  of 128 (the sweep that chose the half: PERF.md, PR 36), never under
  ``num_slots`` (every slot can always decode) and never above
  ``num_slots x chunk`` (full width, where the map is a reshape and the
  ceiling never binds).  A function of the two shapes an engine is given
  and of nothing else, for every model and every twin.  The scheduler is
  handed ``T`` as the ceiling of a plan's live positions
  (serving/scheduler.py ``width``).  The same program holds a second,
  smaller width, derived from ``T`` by :func:`narrow_width` (half of it
  up to a multiple of 128, never under ``num_slots`` up to one, and only
  where that halves the rows), which a step whose live positions fit it
  runs what is position-wise on."""
  round_up = lambda n: -(-n // 128) * 128
  return min(max(round_up(num_slots * chunk // 2), round_up(num_slots)),
             num_slots * chunk)


def narrow_width(width: int, num_slots: int) -> int:
  """Rows ``T_narrow`` of the flat batch's second width, derived as
  ``flat_width`` is and by every twin alike: HALF of ``width``, up to a
  multiple of 128, never under ``num_slots`` up to a multiple of 128.
  Both widths stand in the ONE compiled step (models/gpt.py
  ``slot_layers``): a step whose live positions fit ``T_narrow``, which
  the step reads off the ``num_valid`` it is handed, runs what is
  position-wise on that many rows; the head, the sampler and the mixers
  that own a leaf over the context are in the program once.  Where the
  rule does not halve the rows there is one width and no conditional: an
  engine of few positions, where it gives ``width`` back, and one whose
  half rounds up to more (384 -> 256: what a third of the position-wise
  rows can save a step, 1.3 of 24.7 ms in the expert cell, is what that
  cell's conditionals cost it on every step, 1.2 ms: PERF.md, PR 41).
  The scheduler's ceiling stays ``width``: no plan is trimmed for the
  narrow one."""
  round_up = lambda n: -(-n // 128) * 128
  narrow = max(round_up(-(-width // 2)), round_up(num_slots))
  return narrow if 2 * narrow <= width else width


def _resolve_mesh(mesh):
  """The engine's placement mesh: the caller's, else the ambient Env
  mesh when one has been BUILT (never force-building one).

  This closes the fit->engine recompile interplay (ROADMAP item 1
  "First"; NOTES.md): once any component builds the cluster mesh (fit's
  setup does), ``utils.sharding.constrain`` binds every activation
  constraint inside the fused step to ``NamedSharding(mesh, ...)`` —
  so the step's OUTPUTS come back committed to that mesh even when the
  engine was constructed meshless, while its first-call inputs (a fresh
  meshless cache) were uncommitted single-device arrays.  Call 2's
  donated inputs then carry a different sharding signature than call
  1's and the step recompiles exactly once.  Adopting the ambient mesh
  makes allocation, in_shardings and out_shardings agree from the first
  call (replicated specs degrade gracefully on a 1-device mesh), so the
  compile-once contract holds in any construction order.
  """
  if mesh is not None:
    return mesh
  cluster = getattr(Env.get(), "cluster", None)
  if cluster is not None:
    # built_mesh observes without forcing a build (Cluster.mesh would
    # force one) — a truly meshless run must stay meshless.
    return getattr(cluster, "built_mesh", None)
  return None


def _weak_method(obj, name: str):
  """``getattr(obj, name)`` as a callable that does not keep ``obj``
  alive; a call after ``obj`` is gone returns ``None``."""
  ref = weakref.ref(obj)

  def call(*args, **kwargs):
    target = ref()
    if target is not None:
      return getattr(target, name)(*args, **kwargs)
    return None
  return call


@dataclasses.dataclass
class _LaunchedStep:
  """A fused step between its launch and its commit: the plan it ran,
  its outputs as they lie on the device (``tokens``: what the host
  commits from; ``ok``: the guarded step's verdict), and the stamps of
  its launch."""
  plan: Any
  tokens: tuple
  ok: Any
  num_draft: Optional[np.ndarray]
  t0: float                    # time.monotonic() before the launch
  t0_us: float                 # tracer clock, before the arguments
  launched_us: float           # tracer clock, at the launch's return
  overlapped: bool             # launched with its predecessor in flight
  xla_ctx: Any = None          # device capture opened for this step


class ContinuousBatchingEngine:
  """Slot-based continuous-batching decode engine for a (non-pipelined)
  GPT.

  ``params`` may be boxed (flax Partitioned) or plain; with ``mesh``
  they should already live in their sharded layout (e.g. from
  ``create_sharded_train_state`` or ``runtime.saver.restore_params``)
  and the cache is allocated heads-over-TP on the same mesh.  All knobs
  default from the active ``Config``'s ``serving.*`` group.

  Typical drive::

      eng = ContinuousBatchingEngine(model, params, mesh=mesh)
      eng.submit(Request(uid="a", prompt=ids, max_new_tokens=32))
      outputs = eng.run()          # {uid: prompt+generated np.int32}
      eng.finished["a"].finish_reason   # why each request ended

  ``submit`` returns False when admission control sheds the request
  (``serving.resilience.queue_limit``); the shed record still lands in
  ``engine.finished`` with reason ``"shed"``.
  """

  def __init__(self, model, params, *, mesh=None,
               num_slots: Optional[int] = None,
               prefill_chunk: Optional[int] = None,
               prefill_token_budget: Optional[int] = None,
               max_batch: Optional[int] = None,
               stop_token: Optional[int] = None,
               donate_cache: Optional[bool] = None,
               drafter=None, speculative: Optional[bool] = None,
               draft_model=None, draft_params=None,
               resilience: Optional[bool] = None,
               paged: Optional[bool] = None,
               block_size: Optional[int] = None,
               num_blocks: Optional[int] = None,
               token_budget: Optional[int] = None,
               prefix_cache: Optional[bool] = None,
               stats=None, metrics_writer=None, registry=None,
               config=None, track_prefix: Optional[str] = None,
               checkpoint_version: int = 0):
    cfg = model.cfg
    root_config = config if config is not None else Env.get().config
    conf = root_config.serving
    # Reconcile the ambient tracer AND the ambient SLO monitor with
    # observability.* so a config-enabled run traces and monitors
    # serving without any wiring at the call site.
    trace_lib.ensure_configured(root_config)
    self._slo = slo_lib.ensure_configured(root_config)
    # Device-truth introspection (observability/device.py): warmup
    # capture of every compiled twin's cost/memory analysis, HBM
    # watermark gauges on the stats cadence, and the per-site measured
    # collective-bytes feed.  None when observability.device is off —
    # every hook below is then a cheap attribute test.
    self._introspector = device_lib.ensure_configured(root_config)
    self._pending_step_specs = None
    self._capture_xla = root_config.observability.slo.capture_xla
    self._pending_xla_dir: Optional[str] = None
    check_servable(cfg)
    # Perfetto track namespace for this engine's per-slot timelines
    # (replicas pass serving/replica<i>; docs/observability.md).
    self._track_prefix = track_prefix or "serving"
    # This engine's twin label in breach payloads — the exact-match key
    # that routes engine-attributed anomalies (recompile, watchdog)
    # back to THIS engine and no other (e.g. the xla-capture listener
    # on a shared ambient monitor must not arm every replica).
    self._twin_label = f"{self._track_prefix}/fused_step"
    self.model = model
    self.params = params
    self.mesh = _resolve_mesh(mesh)
    # ``(axis, chips)`` where the mesh DIVIDES the slots and the held
    # experts over its ``expert`` axis (serving/kv_cache.py ``slot_axis``;
    # docs/serving.md "The divided engine"): slot ``s`` lives on chip ``s
    # // slots_a_chip``, the fused step runs a chip's slots on each chip
    # inside one ``shard_map``.  None: one program over all slots.
    self.slot_axis = kv_lib.slot_axis(self.mesh)
    if self.slot_axis is not None:
      # Placed once, as the divided step takes them (a tree that already
      # lies so is left where it is).
      self.params = jax.device_put(params, self._param_shardings())
    # The checkpoint version these params came from (blue/green rollout,
    # serving/rollout.py): scopes the prefix cache's keys and makes the
    # scheduler refuse cross-version restore replays.  0 = pre-rollout
    # default; the rollout controller stamps green replicas with N+1.
    self.checkpoint_version = int(checkpoint_version)
    self.num_slots = num_slots if num_slots is not None else conf.num_slots
    self.chunk = (prefill_chunk if prefill_chunk is not None
                  else conf.prefill_chunk)
    if self.chunk > cfg.max_seq_len:
      raise ValueError(f"prefill_chunk {self.chunk} exceeds max_seq_len "
                       f"{cfg.max_seq_len}")
    budget = (prefill_token_budget if prefill_token_budget is not None
              else conf.prefill_token_budget)
    if budget > 0 and budget < self.chunk:
      raise ValueError(
          f"prefill_token_budget {budget} below prefill_chunk "
          f"{self.chunk}: no admission could ever afford its first chunk")
    # Paged mode (serving.paged.*; docs/serving.md "Paged KV cache"):
    # token-flat fused step over a block-table cache — decode cost
    # scales with scheduled tokens, concurrency with blocks, not with
    # num_slots * max_seq_len.
    pconf = conf.paged
    self.paged = paged if paged is not None else pconf.enabled
    if self.paged:
      check_recurrent_state(cfg, "the paged cache (serving.paged)")
      check_latent_cache(cfg, "the paged cache (serving.paged)")
      check_kv_window(cfg, "the paged cache (serving.paged)")
    eff_batch = max_batch if max_batch is not None else conf.max_batch
    if self.paged:
      self.block_size = (block_size if block_size is not None
                         else pconf.block_size)
      mb = kv_lib.blocks_per_slot(cfg, self.block_size)
      self.num_blocks = (num_blocks if num_blocks is not None
                         else pconf.num_blocks)
      if self.num_blocks <= 0:
        self.num_blocks = kv_lib.default_num_blocks(cfg, self.num_slots,
                                                    self.block_size)
      self.token_budget = (token_budget if token_budget is not None
                           else pconf.token_budget)
      if self.token_budget <= 0:
        # Auto: every decode slot's guaranteed token plus two prefill
        # chunks of admission headroom per step.
        self.token_budget = self.num_slots + 2 * self.chunk
      # Resolve the attend implementation ONCE (kernels/paged_attention
      # dispatch rule: Pallas on TPU, the bit-exact jnp reference
      # elsewhere) so the jitted step never consults the environment.
      from easyparallellibrary_tpu.kernels.paged_attention import (
          default_paged_impl)
      self._paged_impl = default_paged_impl()
    else:
      self.block_size = self.num_blocks = self.token_budget = 0
      self._paged_impl = None
    # Rows of the token-flat batch the contiguous step's position-wise
    # layers run on (``flat_width``), the plain step's and the speculating
    # one's alike; the scheduler keeps every plan, drafts included, within
    # it.  0 on a paged engine (``token_budget`` is its width).  On a mesh
    # that divides the slots both widths are ONE CHIP's: each runs the flat
    # batch of its own slots, and the scheduler holds each chip's live
    # positions to it.
    chips = 1 if self.slot_axis is None else self.slot_axis[1]
    self.slots_a_chip = self.num_slots // chips
    self.flat_width = 0 if self.paged else flat_width(self.slots_a_chip,
                                                      self.chunk)
    # The second width of the same program (``narrow_width``; equal to
    # ``flat_width`` where there is none).
    self.flat_narrow = narrow_width(self.flat_width, self.slots_a_chip)
    # What the fused step is lowered to (serving/kv_cache.py
    # ``step_lowerings``): each kernel rule's answer under its name,
    # resolved ONCE here from the backend, the leaves' shapes and dtypes
    # and the mesh; ``None`` where the model has no layer a rule is about,
    # and throughout on a paged engine, whose pools take a scatter of flat
    # rows and the paged attend above.  A run says what it timed: a rule
    # that declined shows "reference".  Metadata, so no ring eviction
    # loses it.
    self.lowerings = kv_lib.step_lowerings(
        cfg, self.num_slots, self.chunk, self.mesh, self.flat_width or None)
    if self.paged:
      self.lowerings = dict.fromkeys(self.lowerings)
    for name, impl in kv_lib.recorded(self.lowerings).items():
      trace_lib.get_tracer().metadata(
          f"{self._track_prefix}/{name}", {"impl": impl})
    # The granule and length of the attend kernel's walk, where the step
    # was built on it: what ``serving/attn_rows_read`` counts by.
    self._attn_walk = (
        kv_lib.slot_attn_walk(cfg, self.num_slots, self.chunk,
                              self.lowerings["tile_attn_out"])
        if self.lowerings["slot_attn_impl"] in ("pallas", "interpret")
        else None)
    # The tile and the decoding slot's cost of the one-leaf attends that
    # run on the tile grid instead: what ``serving/attn_tile_positions``
    # counts by.
    self._attn_tile = kv_lib.attn_tile(cfg, self.lowerings, self.chunk)
    # Rows the cache holds for one layer: what ``serving/live_kv_rows``
    # is a share of.
    self._kv_rows = (self.num_blocks * self.block_size if self.paged else
                     self.num_slots * kv_lib.cache_length(cfg, self.chunk))
    # What gates the step's arguments and its counters (their names are
    # PERF.md section 3's): recurrent state beside K/V, which the step
    # must tell which slots start a request (``reset``); routed experts,
    # whose load the step hands back; attends that read less than every
    # row under a slot's bound, and what their counters count rows up to
    # (``_sparse``: a selecting layer's selection and the latent window;
    # ``_kv_window``: the window of K/V rings beside full layers).
    self._recurrent = kv_lib.has_recurrent_state(cfg)
    # A matrix state whose update has two forms (kernels/gdn_scan.py): how
    # many slots advance and how many positions run the chunk's form are
    # counted.
    self._delta = self.lowerings["gdn_scan_impl"] is not None
    self._experts = self.lowerings["moe_gmm_impl"] is not None
    self._sparse = ((cfg.index_topk, cfg.sliding_window)
                    if self.lowerings["dsa_index_impl"] is not None else None)
    self._kv_window = (cfg.sliding_window if
                       self.lowerings["kv_win_write_impl"] is not None
                       else None)
    # A share of the routed experts (``cfg.experts_held``: one chip of
    # several a layer is divided over): which of the router's experts the
    # layers hold.  None: all.
    self.experts_held = getattr(cfg, "experts_held", None)
    if self.experts_held is not None:
      held = {"first": self.experts_held[0], "count": self.experts_held[1],
              "published": cfg.n_routed_experts}
      if self.slot_axis is not None:
        # Chip ``j`` of the axis holds ``a_chip`` experts from ``first + j
        # x a_chip``.
        held.update(chips=self.slot_axis[1],
                    a_chip=self.experts_held[1] // self.slot_axis[1])
      trace_lib.get_tracer().metadata(
          f"{self._track_prefix}/experts_held", held)
    # What the contiguous cache holds of each kind of state (K/V,
    # recurrent state, latent rows) and the ORDER its leaves under a
    # cursor are kept in (``kv_order``: rows or positions,
    # serving/kv_cache.py), which says which form of the write and of the
    # attend the step runs.  None on a paged engine (its pool has its own
    # layout).
    self.cache_layout = None if self.paged else kv_lib.cache_layout(
        cfg, self.num_slots, self.chunk)
    if self.cache_layout is not None:
      trace_lib.get_tracer().metadata(
          f"{self._track_prefix}/cache_layout", dict(self.cache_layout))
    # Copy-on-write prefix caching (serving.prefix_cache.*;
    # docs/serving.md "Prefix caching"): radix-tree block reuse over
    # the paged pool — the scheduler rejects it without paged mode.
    pc_conf = conf.prefix_cache
    self.prefix_caching = (prefix_cache if prefix_cache is not None
                           else pc_conf.enabled)
    if self.prefix_caching:
      check_recurrent_state(cfg, "prefix caching (serving.prefix_cache)")
      check_latent_cache(cfg, "prefix caching (serving.prefix_cache)")
      check_kv_window(cfg, "prefix caching (serving.prefix_cache)")
    self.drafter = self._resolve_drafter(conf, drafter, speculative,
                                         draft_model, draft_params)
    if self.drafter is not None:
      check_recurrent_state(
          cfg, "speculative decoding (serving.speculative: rejected "
          "drafts roll back)")
      check_latent_cache(cfg, "speculative decoding (serving.speculative)")
      check_kv_window(cfg, "speculative decoding (serving.speculative)")
    check_divided(model, self.mesh, self.num_slots, paged=self.paged,
                  prefix_cache=self.prefix_caching,
                  speculative=self.drafter is not None,
                  resilient=(resilience if resilience is not None
                             else conf.resilience.enabled))
    if not self.paged:
      trace_lib.get_tracer().metadata(
          f"{self._track_prefix}/flat_width",
          {"width": self.flat_width, "narrow": self.flat_narrow,
           "positions": self.slots_a_chip * self.chunk})
    if self.slot_axis is not None:
      trace_lib.get_tracer().metadata(
          f"{self._track_prefix}/slot_axis",
          {"axis": self.slot_axis[0], "chips": chips,
           "slots_a_chip": self.slots_a_chip})
    self.scheduler = FCFSScheduler(
        num_slots=self.num_slots, prefill_chunk=self.chunk,
        max_seq_len=cfg.max_seq_len, prefill_token_budget=budget,
        width=self.flat_width, slot_groups=chips,
        max_batch=eff_batch,
        stop_token=stop_token if stop_token is not None
        else conf.stop_token,
        spec_k=self.drafter.k if self.drafter is not None else 0,
        block_size=self.block_size, num_blocks=self.num_blocks,
        token_budget=self.token_budget,
        track_prefix=self._track_prefix,
        prefix_cache=self.prefix_caching,
        prefix_session_ttl_s=pc_conf.session_ttl_s,
        prefix_max_cached_blocks=pc_conf.max_cached_blocks,
        checkpoint_version=self.checkpoint_version)
    res_conf = conf.resilience
    self._resilient = (resilience if resilience is not None
                       else res_conf.enabled)
    if self._resilient:
      check_recurrent_state(
          cfg, "the guarded step (serving.resilience: a retried step "
          "needs the state it started from)")
      check_latent_cache(cfg, "the guarded step (serving.resilience)")
      check_kv_window(cfg, "the guarded step (serving.resilience)")
    # Whether step k+1 is launched while step k still runs (``step``):
    # "on" for the plain step of the contiguous cache, whose one
    # dependency on step k, the sampled token, is handed on inside the
    # device; "off: <reason>" for the engines whose next plan needs this
    # step's commit.  What the engine is decides, once.
    self.step_overlap = step_overlap(
        paged=self.paged, speculative=self.drafter is not None,
        resilient=self._resilient)
    self._overlap = self.step_overlap == "on"
    trace_lib.get_tracer().metadata(
        f"{self._track_prefix}/step_overlap", {"mode": self.step_overlap})
    # The launched step whose tokens are still on the device (overlapped
    # loop only), retirements a drain committed outside ``step()``, and
    # the stamp the next step's time sample starts from.
    self._inflight: Optional[_LaunchedStep] = None
    self._held_finished: List[FinishedRequest] = []
    self._last_step_end = 0.0
    self.stats = stats
    if self._resilient and self.stats is None:
      # The degradation ladder reads measured ITL from ServingStats;
      # auto-build one rather than silently losing that signal.
      from easyparallellibrary_tpu.profiler.serving import ServingStats
      self.stats = ServingStats(finished_limit=conf.finished_limit)
    self.metrics_writer = metrics_writer
    # Optional MetricRegistry (observability/registry.py): per-step
    # records publish under serving/* through the one metric schema.
    self.registry = registry
    # Finish records by uid (reasons incl. shed/deadline/cancelled) —
    # bounded to the most recent serving.finished_limit entries (0 =
    # keep all; a long-running server must bound this or grow host
    # memory linearly with requests served).
    self.finished: Dict[Any, FinishedRequest] = {}
    self._finished_limit = conf.finished_limit
    # Hooks the engine hands its own parts call back WEAKLY: a bound
    # method would close a cycle (engine -> scheduler -> hook -> engine),
    # and an engine in a cycle keeps its cache on the device until the
    # collector happens to run, not until its owner lets go of it — which
    # is too late for an owner about to fill the chip with something else.
    self.scheduler.on_finish.append(_weak_method(self, "_record_finished"))
    if self.stats is not None:
      stats_obj = self.stats
      self.scheduler.on_admit.append(stats_obj.note_admitted)
      self.scheduler.on_first_token.append(stats_obj.note_first_token)
      self.scheduler.on_finish.append(
          lambda fin: stats_obj.note_finished(fin.uid, fin.new_tokens,
                                              fin.finish_reason))
    self._admission: Optional[AdmissionController] = None
    self._bad_policy: Optional[BadStepPolicy] = None
    self._watchdog = None
    if self._resilient:
      self._admission = AdmissionController(
          queue_limit=res_conf.queue_limit,
          itl_slo_s=res_conf.itl_slo_s,
          degrade_queue_frac=res_conf.degrade_queue_frac,
          on_transition=self._on_degrade_transition)
      self._bad_policy = BadStepPolicy(
          max_step_retries=res_conf.max_step_retries,
          max_requeues=res_conf.max_requeues)
      if res_conf.step_timeout_s > 0:
        from easyparallellibrary_tpu.runtime.resilience import StepWatchdog
        # on_timeout binds the STATS and MONITOR objects, not an engine
        # method: the finalizer below pins the watchdog, so a
        # watchdog->engine reference would pin the engine too and the
        # finalizer could never fire.  The monitor raises the hang as a
        # first-class SLO breach (and deep-captures) from the watchdog's
        # monitor thread — both objects are thread-safe.
        stats_obj = self.stats
        slo_obj = self._slo
        twin_label = self._twin_label

        def _on_timeout(step, _stats=stats_obj, _slo=slo_obj,
                        _twin=twin_label):
          if _stats is not None:
            _stats.note_watchdog_timeout()
          if _slo is not None:
            _slo.note_event("watchdog_timeout",
                            {"engine_step": int(step), "twin": _twin},
                            step=int(step))

        self._watchdog = StepWatchdog(
            res_conf.step_timeout_s, on_timeout=_on_timeout,
            knob="serving.resilience.step_timeout_s")
        # The monitor thread's target is a bound watchdog method, so the
        # thread pins the watchdog and never exits without close() — a
        # discarded engine would otherwise leak one live
        # 'epl-step-watchdog' thread per construction (the training
        # loop closes its own watchdog in fit(); the engine must not
        # depend on the caller remembering to).  The finalizer holds
        # the WATCHDOG, not the engine, so the engine stays collectible.
        self._watchdog_finalizer = weakref.finalize(
            self, self._watchdog.close)
    self._drafter_failures = 0
    self._drafter_fail_logged = False
    if self.paged:
      self._kv = kv_lib.allocate_paged_kv_cache(
          cfg, self.num_blocks, self.block_size, self.mesh)
      self._cursors = None
    else:
      self._kv, self._cursors = kv_lib.allocate_kv_cache(
          cfg, self.num_slots, self.chunk, self.mesh)
    # The plain step's own first output, as it lies on the device: what
    # the NEXT step reads a ``from_prev`` slot's token from.  Shaped and
    # placed as the cursors are, so the first call compiles the program
    # every later call reuses.
    self._prev_tokens = (jnp.zeros_like(self._cursors)
                         if not self.paged and self.drafter is None
                         else None)
    # Quarantine hygiene: a poisoned device step leaves non-finite K/V
    # in a bad slot's cache, and slot_cache_attend's V contraction
    # touches every cache row (0 * NaN = NaN), so the poison must be
    # zeroed before the slot is read again.  A freed slot is zeroed
    # whole (its next occupant starts from row 0); a retried slot is
    # zeroed from its committed cursor up — the retry is only
    # guaranteed to rewrite its OWN grant window, which can be smaller
    # than the bad step's (speculation degraded off, drafter fault,
    # prefill budget tightened between steps).  Separate tiny program;
    # dispatched only on bad-step events, compiles once.  Every leaf it
    # sees is K/V with a position axis, in either order (rank 3 kept in
    # rows, rank 4 in positions; the mask follows the leaf's rank): a
    # model with recurrent state (no such axis) is refused the guarded
    # step above.  The SAME
    # program serves both layouts: dim 0 is slots (contiguous) or pool
    # blocks (paged), dim 1 rows within — the paged host side maps slot
    # block lists to (block mask, per-block start row) and always
    # includes the null block, which a NaN-params step poisons through
    # padding writes.
    self._sanitize_fn = jax.jit(
        lambda kv, mask, start: jax.tree_util.tree_map(
            lambda x: jnp.where(
                jnp.expand_dims(
                    mask[:, None] & (jnp.arange(x.shape[1])[None]
                                     >= start[:, None]),
                    tuple(range(2, x.ndim))),
                jnp.zeros((), x.dtype), x), kv),
        donate_argnums=0) if self._resilient else None
    if self._sanitize_fn is not None and self._introspector is not None:
      # The sanitize twin's cost card, captured here (its first real
      # dispatch is a fault — warmup must not wait for one).  Abstract
      # specs only: the live cache is never read.
      rows = self.num_blocks if self.paged else self.num_slots
      self._introspector.capture_twin(
          f"{self._track_prefix}/sanitize", self._sanitize_fn,
          device_lib.specs_of(
              (self._kv, np.zeros((rows,), bool),
               np.zeros((rows,), np.int32))),
          compile_count=1)
    # Perfetto track name per slot (the scheduler's lifecycle and phase
    # spans and the speculating engine's per-step ``speculate`` spans
    # must land on the same track); precomputed so that loop does no
    # string work.
    self._slot_tracks = [_slot_track(i, self._track_prefix)
                         for i in range(self.num_slots)]
    self._steps = 0
    donate = conf.donate_cache if donate_cache is None else donate_cache
    if self.drafter is not None:
      self.drafter.bind(self)
      self._step_fn = (self._build_paged_spec_step(donate, self._resilient)
                       if self.paged
                       else self._build_spec_step(donate, self._resilient))
    elif self.paged:
      self._step_fn = self._build_paged_step(donate, self._resilient)
    else:
      self._step_fn = self._build_step(donate, self._resilient)
    # Always-on compile sentinel (observability/slo.py): the compile-
    # once contract moves from test-only to production — any post-
    # warmup recompile of the fused step is detected the step it
    # happens, attributed to the input signature, and raised as a
    # first-class SLO breach + trace instant.  One host int compare per
    # step; the thunk reads the LIVE attribute so chaos wrappers
    # (testing/chaos._StepFnWrapper) that replace _step_fn stay
    # transparent.
    self._compile_sentinel = slo_lib.CompileSentinel(
        self._twin_label,
        _weak_method(self, "_step_cache_size"),
        on_recompile=[_weak_method(self, "_note_recompile")])
    if self._slo is not None:
      # The monitor consumes this engine's registry records (it IS a
      # registry sink) and merges this engine's scheduler/allocator
      # summary into diagnostic bundles.  Both hooks hold the engine
      # weakly/idempotently — the ambient monitor outlives engines.
      if self.registry is not None:
        self._slo.attach(self.registry)
      self._slo.add_context_provider(self._capture_context)
      if self._capture_xla:
        self._slo.add_listener(self._arm_xla_capture, weak=True)
    # Engine-level SLO actuator (serving/autotune.py; docs/robustness.md
    # "Self-healing fleet"): breaches move data-valued knobs between
    # steps — speculation-k / prefill-budget / slot-cap clamps and the
    # admission-ladder floor — with hysteretic recovery.  Never a shape:
    # the compile-once contract is the actuator's hard constraint.
    self._autotuner = None
    if conf.autotune.enabled:
      from easyparallellibrary_tpu.serving.autotune import EngineAutotuner
      self._autotuner = EngineAutotuner(self, self._slo,
                                        config=root_config)
    if self.paged:
      layout = (f"paged: {self.num_blocks} x {self.block_size}-token "
                f"blocks, token budget {self.token_budget}, "
                f"{self._paged_impl} attend, "
                f"{kv_lib.paged_cache_bytes(cfg, self.num_blocks, self.block_size) / 1e6:.1f} MB")
    else:
      lay = self.cache_layout
      total = kv_lib.cache_bytes(cfg, self.num_slots, self.chunk)
      # The kinds of state the cache holds any of (kv, state, latent, ...).
      held = [key.removesuffix("_leaves") for key in lay
              if key.endswith("_leaves") and lay[key]]
      layout = (f"flat width {self.flat_width}"
                + (f" / {self.flat_narrow}"
                   if self.flat_narrow < self.flat_width else "")
                + f", contiguous slots kept in {lay['kv_order']}, "
                f"{total / 1e6:.1f} MB ("
                + ", ".join(f"{lay[f'{kind}_leaves']} {kind} leaves "
                            f"{lay[f'{kind}_bytes'] / 1e6:.1f} MB"
                            for kind in held)
                + "); "
                + ", ".join(f"{name} {impl}" for name, impl in
                            kv_lib.recorded(self.lowerings).items()))
    get_logger().info(
        "serving engine: %d slots x chunk %d (%s, %s), step overlap %s, "
        "prefill budget %s, max batch %d, speculation %s, resilience %s",
        self.num_slots, self.chunk, layout,
        "single-program" if self.mesh is None else "mesh-sharded"
        if self.slot_axis is None else
        "divided over %s:%d, %d slots and a flat batch a chip" % (
            *self.slot_axis, self.slots_a_chip),
        self.step_overlap,
        budget or "uncapped", self.scheduler.max_batch,
        f"{type(self.drafter).__name__}(k={self.drafter.k})"
        if self.drafter is not None else "off",
        f"on (queue_limit {res_conf.queue_limit or 'unbounded'}, "
        f"itl_slo {res_conf.itl_slo_s or 'off'}, watchdog "
        f"{res_conf.step_timeout_s or 'off'})"
        if self._resilient else "off")

  def _resolve_drafter(self, conf, drafter, speculative, draft_model,
                       draft_params):
    """``speculative=False`` wins over everything (an explicit opt-out
    must be trustworthy even when a drafter object was constructed);
    otherwise an explicit ``drafter`` wins, and ``serving.speculative.*``
    decides the rest (``speculative=True`` overrides its ``enabled``).
    Any resolved drafter must fit the fused step's chunk
    (k + 1 <= prefill_chunk)."""
    from easyparallellibrary_tpu.serving.speculative import (
        DraftModelDrafter, NgramDrafter)
    if speculative is False:
      return None
    spec = conf.speculative
    if drafter is None and (spec.enabled or speculative):
      if spec.kind == "ngram":
        drafter = NgramDrafter(k=spec.k, ngram_max=spec.ngram_max,
                               ngram_min=spec.ngram_min)
      else:  # "draft_model" (config validation rejects anything else)
        if draft_model is None or draft_params is None:
          raise ValueError(
              "serving.speculative.kind='draft_model' needs the drafter's "
              "weights: pass draft_model=/draft_params= (e.g. via "
              "DraftModelDrafter.from_checkpoint) or a drafter= instance")
        drafter = DraftModelDrafter(draft_model, draft_params, k=spec.k)
    if drafter is not None:
      check_draft_fits_chunk(drafter.k, self.chunk)
    return drafter

  # --------------------------------------------------- resilience hooks

  def _on_degrade_transition(self, old: int, new: int, signals):
    if self.stats is not None:
      self.stats.note_degraded(new)

  # -------------------------------------------------- observability hooks

  def _describe_signature(self, plan) -> Dict[str, Any]:
    """Shape/dtype signature of the step's host-side inputs at
    recompile-detection time — built only on the (rare) recompile path
    to attribute the event, never per healthy step."""
    sig: Dict[str, Any] = {"twin": type(plan).__name__,
                           "mesh": self.mesh is not None,
                           "resilient": self._resilient,
                           "paged": self.paged}
    for name, v in vars(plan).items():
      if hasattr(v, "shape"):
        sig[name] = f"{v.dtype}{list(v.shape)}"
    return sig

  def _step_cache_size(self) -> int:
    return self._step_fn._cache_size()

  def _note_recompile(self, label: str, cache_size: int,
                      new_compiles: int, signature) -> None:
    """CompileSentinel subscriber: surface an unexpected fused-step
    recompile as a trace instant, a stats counter, and a first-class
    SLO breach (which also triggers deep capture when configured)."""
    tracer = trace_lib.get_tracer()
    if tracer.enabled:
      tracer.instant(
          "serving/recompile", cat="serving", track="serving",
          args={"twin": label, "cache_size": int(cache_size),
                "new_compiles": int(new_compiles),
                "signature": str(signature)[:512]})
    if self.stats is not None:
      self.stats.note_recompile(new_compiles)
    if self._slo is not None:
      self._slo.note_event(
          "unexpected_recompile",
          {"twin": label, "cache_size": int(cache_size),
           "signature": str(signature)[:512]},
          step=self._steps)

  def _capture_context(self) -> Dict[str, Any]:
    """Scheduler/allocator state summary merged into diagnostic bundles
    (observability/slo.py DiagnosticCapture), keyed by this engine's
    track prefix so replicas' summaries land side by side."""
    sched = self.scheduler
    ctx: Dict[str, Any] = {
        "engine_steps": self._steps,
        "queue_depth": sched.queue_depth,
        "num_active": sched.num_active,
        "num_slots": self.num_slots,
        "paged": self.paged,
        **self.lowerings,
        "kv_order": (self.cache_layout or {}).get("kv_order"),
        "step_overlap": self.step_overlap,
        "wasted_positions": sched.wasted_positions,
        "recompiles": self._compile_sentinel.recompiles,
        "active_uids": [str(s.req.uid)
                        for s in sched.active.values()][:32],
    }
    if self._admission is not None:
      ctx["degraded_level"] = self._admission.level
      ctx["shed_total"] = self._admission.shed_total
    if self._autotuner is not None:
      ctx["autotune_level"] = self._autotuner.level
      ctx["autotune_actuations"] = self._autotuner.actuations
    if self._bad_policy is not None:
      ctx.update(self._bad_policy.counters())
    if self.paged:
      ctx.update(kv_blocks_free=sched.kv_blocks_free,
                 kv_blocks_used=sched.kv_blocks_used,
                 kv_fragmentation=sched.kv_fragmentation,
                 preemptions=sched.preemptions,
                 proactive_preemptions=sched.proactive_preemptions)
      if self.prefix_caching:
        ctx.update(prefix_hits=sched.prefix_hits,
                   prefix_misses=sched.prefix_misses,
                   prefix_blocks_reused=sched.prefix_blocks_reused,
                   prefix_evictions=sched.prefix_evictions,
                   prefix_cached_blocks=sched.prefix_cached_blocks)
    out = {self._track_prefix: ctx}
    if self._introspector is not None:
      # Device truth rides every diagnostic bundle: cost cards, live
      # HBM gauges, the per-site measurement store.  The introspector
      # is ambient (shared across replicas), so one "device" key
      # carries the whole picture.
      out["device"] = self._introspector.context()
    return out

  def _note_step_specs(self, step_args) -> None:
    """Snapshot the warmup call's abstract argument specs (shapes and
    dtypes only — donated buffers are never held) so the device
    introspector can capture this twin's cost card AFTER the step
    completes; no-op past warmup or with device observability off."""
    if (self._introspector is not None and self._steps == 0
        and self._pending_step_specs is None
        and not self._introspector.has_card(self._twin_label)):
      self._pending_step_specs = device_lib.specs_of(step_args)

  def _twin_meta(self) -> Dict[str, Any]:
    """Geometry the perf gate normalizes cost-card numbers by: the
    step's token capacity and the KV footprint per request."""
    cfg = self.model.cfg
    if self.paged:
      kv_bytes = kv_lib.paged_cache_bytes(cfg, self.num_blocks,
                                          self.block_size)
      tokens = self.token_budget
    else:
      kv_bytes = kv_lib.cache_bytes(cfg, self.num_slots, self.chunk)
      tokens = self.num_slots * self.chunk
    return {"tokens_per_step": tokens, "kv_cache_bytes": kv_bytes,
            "kv_bytes_per_request": kv_bytes / max(self.num_slots, 1),
            "num_slots": self.num_slots, "paged": self.paged}

  def _arm_xla_capture(self, rule: str, payload: Dict[str, Any]) -> None:
    """Breach listener (observability.slo.capture_xla): arm a
    jax.profiler device capture around the NEXT fused step, written
    under the breach's diagnostic bundle.  Only for breaches the
    payload attributes to THIS engine's twin — the ambient monitor is
    shared, and a fleet-level breach arming a heavy device capture on
    every healthy replica at once would be the anomaly."""
    bundle = payload.get("bundle")
    if bundle and payload.get("twin") == self._twin_label:
      self._pending_xla_dir = os.path.join(bundle, "xla")

  # ----------------------------------------------------------- device step

  def _jit_step(self, step, donate: bool, n_rep_in: int, n_rep_out: int,
                cursors: bool = True):
    """jit a fused step with the engine's donation/placement discipline:
    cache (+ cursors in the contiguous layout) donated, everything after
    them replicated when a mesh is attached.  The paged step has no
    device cursors — positions are host-planned per step — so only the
    cache pools donate (``cursors=False``)."""
    jit_kwargs: Dict[str, Any] = {}
    if donate:
      jit_kwargs["donate_argnums"] = (1, 2) if cursors else (1,)
    if self.mesh is not None:
      from easyparallellibrary_tpu.parallel.api import state_shardings
      kv_sh, cur_sh = kv_lib.kv_cache_shardings(self.model.cfg, self.mesh)
      param_sh = state_shardings(self.params, self.mesh)
      rep = cur_sh
      state_in = (param_sh, kv_sh) + ((cur_sh,) if cursors else ())
      state_out = (kv_sh,) + ((cur_sh,) if cursors else ())
      jit_kwargs["in_shardings"] = state_in + (rep,) * n_rep_in
      jit_kwargs["out_shardings"] = (rep,) * n_rep_out + state_out
    return jax.jit(step, **jit_kwargs)

  def _param_shardings(self):
    """Divided engine: where every parameter lies, the routed experts'
    stacks split over the axis along their leading, experts dimension
    (chip ``j`` holds the ``j``-th run of them), all else whole on every
    chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    split = NamedSharding(self.mesh, P(self.slot_axis[0]))
    whole = NamedSharding(self.mesh, P())
    stacks = ("experts_gate_up", "experts_down")
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: split if any(
            getattr(k, "key", None) in stacks for k in path) else whole,
        self.params)

  def _chip_live(self, plan):
    """Divided engine: the plan's live positions a chip, int ``[chips]``."""
    return plan.num_valid.reshape(self.slot_axis[1], -1).sum(axis=1)

  def _jit_divided(self, step, donate: bool):
    """The plain step of an engine divided over the slots: ``step`` as one
    chip runs it on its own slots, under a ``shard_map`` over the axis.
    Per-slot arguments and results and the cache's leaves are split along
    their leading dimension, the parameters as :meth:`_param_shardings`
    says; the expert layers' numbers come back whole (the body reduced
    them over the chips)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from easyparallellibrary_tpu.utils.compat import shard_map
    kv_sh, slot_sh = kv_lib.kv_cache_shardings(self.model.cfg, self.mesh)
    whole = NamedSharding(self.mesh, P())
    ins = (self._param_shardings(), kv_sh) + (slot_sh,) * 11
    outs = (slot_sh, whole, kv_sh, slot_sh)
    specs = lambda tree: jax.tree_util.tree_map(lambda sh: sh.spec, tree)
    return jax.jit(
        shard_map(step, self.mesh, in_specs=specs(ins),
                  out_specs=specs(outs), check=False),
        donate_argnums=(1, 2) if donate else (),
        in_shardings=ins, out_shardings=outs)

  def _build_step(self, donate: bool, guard: bool = False):
    model = self.model
    C = self.chunk
    width, narrow = self.flat_width, self.flat_narrow
    lowerings = kv_lib.resolved(self.lowerings)
    recurrent = self._recurrent
    experts = self._experts
    # Divided over the slots the body below is ONE CHIP's: its slots'
    # rows of every argument, its share of the expert stacks, and the
    # model told which axis to exchange expert rows over.
    expert_axis = None if self.slot_axis is None else self.slot_axis[0]

    def step(params, kv, cursors, tokens, num_valid, reset, prev,
             from_prev, keys, tok_index, temperature, top_k, top_p):
      # A slot planned past an uncommitted step (``from_prev``) decodes
      # from that step's sample, which never left the device: ``prev`` is
      # the previous step's first output as it lies there.
      tokens = tokens.at[:, 0].set(jnp.where(from_prev, prev, tokens[:, 0]))
      cursors = jnp.where(reset, 0, cursors)
      # ``num_valid`` bounds what the attend reads of each slot's cache
      # (an idle slot: nothing) and how far a recurrence advances; a
      # recurrence must also be told which slots start a request (stale
      # state is masked by nothing).  A model may have recurrent state,
      # routed experts and positions of its own at once
      # (models/lfm2_moe.py): each argument goes to the models that ask.
      state_args = dict(reset=reset) if recurrent else {}
      if expert_axis is not None:
        state_args["expert_axis"] = expert_axis
      # Each slot's next-token logits sit at its LAST live chunk
      # position, and the head runs on that row alone; idle slots
      # (num_valid=0) read position 0 — garbage the scheduler never
      # consumes.
      last, kv, *sown = slot_step_logits(
          model, params, kv, tokens, cursors, num_valid=num_valid,
          stats=experts, width=width, narrow=narrow,
          head_pos=jnp.clip(num_valid - 1, 0, C - 1), **lowerings,
          **state_args)
      step_keys = jax.vmap(jax.random.fold_in)(keys, tok_index)
      nxt = sample_token_slots(last.astype(jnp.float32), step_keys,
                               temperature, top_k, top_p)
      # An expert model also hands back two floats in ONE array: the
      # busiest expert's load over the mean, worst layer
      # (``serving/expert_load_max``), and the fewest experts a layer
      # touched (``serving/experts_touched_min``).
      nxt = (nxt, _expert_stats(sown, expert_axis)) if experts else (nxt,)
      if not guard:
        return *nxt, kv, cursors + num_valid
      # In-jit finiteness verdict on exactly the rows commit consumes
      # (the PR-2 sentinel pattern): a bad slot's cursor stays put, so
      # its K/V writes beyond the old cursor are unreachable garbage the
      # retry overwrites — device state never advances on a bad step.
      slot_ok = (jnp.all(jnp.isfinite(last), axis=-1)
                 | (num_valid == 0))
      return *nxt, slot_ok, kv, jnp.where(slot_ok, cursors + num_valid,
                                          cursors)

    if self.slot_axis is None:
      return self._jit_step(step, donate, n_rep_in=10,
                            n_rep_out=1 + int(experts) + int(guard))
    return self._jit_divided(step, donate)

  def _build_spec_step(self, donate: bool, guard: bool = False):
    """The speculative twin of :meth:`_build_step`: the SAME single
    model call (drafts ride the chunk positions plain decode wastes, so
    verification adds no model compute), followed by in-jit per-slot
    accept/rollback (serving/speculative/verify.py).  Shapes are static
    in ``k_max = drafter.k``; per-slot draft length is data
    (``num_draft``), so joins/leaves/short proposals never recompile.
    """
    from easyparallellibrary_tpu.serving.speculative.verify import (
        verify_tokens)
    model = self.model
    C = self.chunk
    K = self.drafter.k
    width, narrow = self.flat_width, self.flat_narrow
    lowerings = kv_lib.resolved(self.lowerings)

    def step(params, kv, cursors, tokens, num_valid, num_draft, reset,
             keys, tok_index, temperature, top_k, top_p):
      cursors = jnp.where(reset, 0, cursors)
      # base = non-draft tokens fed (prefill grant, or 1 for decode);
      # position base-1+j's logits are the target distribution for
      # draft j, and base-1+num_draft's feed the bonus token: the head
      # runs on those K+1 rows a slot.  With num_draft=0 row 0 is
      # exactly the plain step's `last` row.
      base = num_valid - num_draft
      pos = jnp.clip(base[:, None] - 1 + jnp.arange(K + 1)[None],
                     0, C - 1)
      tgt, kv = slot_step_logits(model, params, kv, tokens, cursors,
                                 num_valid=num_valid, width=width,
                                 narrow=narrow, head_pos=pos, **lowerings)
      tgt = tgt.astype(jnp.float32)
      dpos = jnp.clip(base[:, None] + jnp.arange(K)[None], 0, C - 1)
      drafts = jnp.take_along_axis(tokens, dpos, axis=1)
      committed, n_committed, accepted = verify_tokens(
          tgt, drafts, num_draft, keys, tok_index, temperature, top_k,
          top_p)
      # Rollback is pure cursor math: the cache keeps K/V for the fed
      # non-draft tokens plus the accepted prefix; rejected-draft K/V
      # beyond the new cursor is masked and later overwritten, exactly
      # like chunked-prefill garbage.
      if not guard:
        return committed, n_committed, kv, cursors + base + accepted
      # All K+1 target rows of a healthy slot are gathers of real
      # (finite) logit positions, so checking the whole [K+1, V] block
      # is safe and covers every row verification consumed.
      slot_ok = (jnp.all(jnp.isfinite(tgt), axis=(1, 2))
                 | (num_valid == 0))
      new_cursors = jnp.where(slot_ok, cursors + base + accepted,
                              cursors)
      return committed, n_committed, slot_ok, kv, new_cursors

    return self._jit_step(step, donate, n_rep_in=9,
                          n_rep_out=3 if guard else 2)

  def _build_paged_step(self, donate: bool, guard: bool = False):
    """Token-flat fused step over the paged cache: ONE model call scores
    the whole ``[token_budget]`` flat batch (prefill chunks, one-token
    decodes — each position tagged with slot and absolute position) so
    device compute scales with scheduled tokens, not
    ``num_slots * chunk``.  Shapes are static in ``token_budget`` /
    ``num_slots`` / the block-table width; block tables, positions and
    validity are data — joins, leaves and pool reshuffles never
    recompile.  No device cursors: positions are host-planned, so the
    only persistent device state is the donated pool pair."""
    model = self.model
    T = self.token_budget
    impl = self._paged_impl

    def step(params, kv, tokens, slot_ids, positions, valid, tables,
             last_idx, active, keys, tok_index, temperature, top_k,
             top_p):
      logits, kv = paged_step_logits(model, params, kv, tokens, slot_ids,
                                     positions, valid, tables, impl=impl)
      # Each slot's next-token logits sit at its LAST scheduled flat
      # position; idle slots read row 0 — garbage the scheduler never
      # consumes (same contract as the slot step's num_valid=0 rows).
      last = jnp.take(logits, jnp.clip(last_idx, 0, T - 1), axis=0)
      step_keys = jax.vmap(jax.random.fold_in)(keys, tok_index)
      nxt = sample_token_slots(last.astype(jnp.float32), step_keys,
                               temperature, top_k, top_p)
      if not guard:
        return nxt, kv
      slot_ok = jnp.all(jnp.isfinite(last), axis=-1) | ~active
      return nxt, slot_ok, kv

    return self._jit_step(step, donate, n_rep_in=12,
                          n_rep_out=2 if guard else 1, cursors=False)

  def _build_paged_spec_step(self, donate: bool, guard: bool = False):
    """The speculative twin of :meth:`_build_paged_step`: drafts ride
    LEFTOVER flat-budget positions (scheduler pass 3) instead of wasted
    chunk columns, the same single model call scores them, and
    verification gathers each slot's K+1 target rows by flat index
    (row 0 at the slot's last real token, rows 1..K at its draft
    positions).  No cursor rollback — the host plans next step's
    positions from the committed count, so rejection is pure
    bookkeeping, and rejected-draft K/V beyond it is masked garbage
    overwritten on the next feed, exactly like chunked-prefill
    garbage."""
    from easyparallellibrary_tpu.serving.speculative.verify import (
        verify_tokens)
    model = self.model
    T = self.token_budget
    K = self.drafter.k
    impl = self._paged_impl

    def step(params, kv, tokens, slot_ids, positions, valid, tables,
             base_last, draft_base, num_draft, active, keys, tok_index,
             temperature, top_k, top_p):
      logits, kv = paged_step_logits(model, params, kv, tokens, slot_ids,
                                     positions, valid, tables, impl=impl)
      j = jnp.arange(K + 1)[None]                       # [1, K+1]
      idx = jnp.concatenate(
          [base_last[:, None],
           draft_base[:, None] + jnp.arange(K)[None]], axis=1)
      # Rows past a slot's actual draft count clamp to its own (real,
      # finite) last row: verification masks them anyway, and the guard
      # verdict must never convict a slot on another slot's rows.
      idx = jnp.where(j <= num_draft[:, None], idx, base_last[:, None])
      idx = jnp.clip(idx, 0, T - 1)
      tgt = jnp.take(logits, idx, axis=0).astype(jnp.float32)  # [N,K+1,V]
      dpos = jnp.clip(draft_base[:, None] + jnp.arange(K)[None], 0, T - 1)
      drafts = jnp.take(tokens, dpos, axis=0)
      committed, n_committed, accepted = verify_tokens(
          tgt, drafts, num_draft, keys, tok_index, temperature, top_k,
          top_p)
      if not guard:
        return committed, n_committed, kv
      slot_ok = jnp.all(jnp.isfinite(tgt), axis=(1, 2)) | ~active
      return committed, n_committed, slot_ok, kv

    return self._jit_step(step, donate, n_rep_in=14,
                          n_rep_out=3 if guard else 2, cursors=False)

  # ------------------------------------------------------------ host loop

  def _record_finished(self, fin: FinishedRequest) -> None:
    """Record a resolution in ``finished``, evicting oldest-first past
    ``serving.finished_limit`` (0 = unbounded)."""
    # pop first: re-assigning an existing key would keep its ORIGINAL
    # dict insertion position, so a reused uid's fresh record would be
    # evicted as if it were the oldest.
    self.finished.pop(fin.uid, None)
    self.finished[fin.uid] = fin
    if self._finished_limit > 0:
      while len(self.finished) > self._finished_limit:
        self.finished.pop(next(iter(self.finished)))

  def submit(self, request: Request) -> bool:
    """Enqueue `request`; returns False when admission control sheds it
    (bounded queue full, or the ladder is at its shed level).  Shed
    records land in ``self.finished`` with reason ``"shed"`` and are
    never admitted — the client learns at submit time, not after a
    hopeless queue wait.  Malformed requests raise regardless of load
    (validation must not depend on instantaneous queue depth)."""
    tracer = trace_lib.get_tracer()
    # Host work of the engine BETWEEN steps: the benchmark reads this
    # span by name (PERF.md section 3).  ``serving/submit`` is the
    # scheduler's instant, one per ACCEPTED request.
    with tracer.span("serving/enqueue", cat="serving", track="serving"):
      prompt = self.scheduler.validate(request)
      if self._admission is not None and not self.scheduler.has_work:
        # The ladder normally de-escalates inside step(), but an idle
        # engine never steps: if the queue drained without stepping
        # (every queued request cancelled or expired after a shed-level
        # observation), a stale shed level would otherwise reject 100%
        # of traffic forever.  Re-observe with the idle signals first.
        self._apply_degradation()
      if (self._admission is not None
          and self._admission.should_shed(self.scheduler.queue_depth)):
        self._admission.note_shed()
        fin = FinishedRequest(uid=request.uid, tokens=prompt,
                              new_tokens=0, finish_reason="shed")
        self._record_finished(fin)
        if self.stats is not None:
          self.stats.note_shed(request.uid)
        if tracer.enabled:
          tracer.instant(
              "serving/shed", cat="serving", track="serving/requests",
              args={"uid": str(request.uid),
                    "queue_depth": int(self.scheduler.queue_depth),
                    "level": DEGRADE_LEVELS[self._admission.level]})
          if request.flow_id is not None:
            # A router-minted flow must terminate even on a shed — the
            # rejection IS this request's resolution.
            tracer.flow("f", request.flow_id, track="serving/requests",
                        args={"uid": str(request.uid), "reason": "shed"})
        get_logger().warning(
            "shedding request %r at submit (queue %d/%d, level %s)",
            request.uid, self.scheduler.queue_depth,
            self._admission.queue_limit,
            DEGRADE_LEVELS[self._admission.level])
        return False
      if self.stats is not None:
        self.stats.note_submitted(request.uid)
      self.scheduler.submit(request, _prompt=prompt)
      return True

  def cancel(self, uid: Any) -> bool:
    """Client cancellation: retire `uid` wherever it is; the record (and
    any partial output) lands in ``self.finished`` immediately (the
    on_finish hook fires inside this call), and the retirement is also
    returned by the next ``step()``.  Returns False for
    unknown/already-finished uids."""
    return self.scheduler.cancel(uid)

  # ------------------------------------------------- snapshot / migration

  def snapshot_requests(self) -> List[Dict[str, Any]]:
    """Serializable snapshots of every queued + in-flight request
    (scheduler.snapshot_requests) — the failover/drain currency of the
    multi-replica router (serving/router.py): restoring them on another
    engine sharing the params source resumes each stream bit-exactly
    via prefix replay.  A step in flight is fetched and committed first;
    what it retired is returned by the next ``step()``."""
    self._drain(tolerant=True)
    return self.scheduler.snapshot_requests()

  def restore_request(self, snap: Dict[str, Any],
                      front: bool = False) -> Any:
    """Resubmit a snapshotted request (bit-exact resumption; see
    :meth:`snapshot_requests`).  Bypasses admission control on purpose:
    a migrated request was already admitted by the fleet once — shedding
    it here would double-charge it for the overload verdict."""
    uid = self.scheduler.restore_request(snap, front=front)
    if self.stats is not None:
      # Keep the ORIGINAL submit time (same monotonic clock domain) so
      # the survivor's TTFT sample includes the pre-migration wait.
      self.stats.note_submitted(uid, at=snap.get("submitted_at"))
    return uid

  def evacuate(self) -> List[Dict[str, Any]]:
    """Snapshot and REMOVE every queued + in-flight request (no finish
    records — they finish elsewhere).  The router's failover and
    drain-timeout migration path; the engine stays warm (cache, compiled
    step and watchdog untouched) and can serve again immediately.  A step
    in flight is DROPPED, never committed: nothing may finish here once
    its requests are to finish elsewhere, and the replay recomputes the
    dropped samples bit for bit (the snapshots hold the committed
    prefix)."""
    step, self._inflight = self._inflight, None
    if step is not None:
      self._close_step(step)
    return self.scheduler.evacuate()     # forgets the dropped step's plan

  @property
  def has_work(self) -> bool:
    """True while anything is queued or active, a step is in flight, or a
    drain's retirements wait for the next ``step()`` to return them."""
    return (self.scheduler.has_work or self._inflight is not None
            or bool(self._held_finished))

  def _take_finished(self) -> List[FinishedRequest]:
    held, self._held_finished = self._held_finished, []
    return held + self.scheduler.take_finished()

  def _drain(self, tolerant: bool = False) -> None:
    """Fetch and commit the step in flight, launching nothing (overlapped
    loop; a no-op otherwise).  What it retires is held for the next
    ``step()``.  ``tolerant``: a device that fails the fetch costs the
    step (the scheduler plans it again), not the caller."""
    step, self._inflight = self._inflight, None
    if step is None:
      return
    try:
      self._held_finished.extend(
          self._finish_step(trace_lib.get_tracer(), step, None))
    except Exception as e:  # noqa: BLE001 — any device fault
      if not tolerant:
        raise
      get_logger().warning(
          "the step in flight was lost draining the engine (%s: %s); its "
          "work is planned again", type(e).__name__, e)

  def close(self):
    """Release background resources (the hung-step watchdog thread).
    Idempotent; the engine remains usable for stepping afterwards —
    the watchdog simply stops firing.  Also runs automatically when the
    engine is garbage-collected (or at interpreter exit) and on
    ``with`` exit, so un-closed engines never leak monitor threads.  A
    step in flight is fetched and committed first."""
    self._drain(tolerant=True)
    if self._watchdog is not None:
      self._watchdog.close()
      self._watchdog = None
      self._watchdog_finalizer.detach()

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.close()
    return False

  def _trace_speculation(self, tracer, plan, t0_us: float, t1_us: float,
                         num_draft, n_committed):
    """One ``speculate`` span a slot that drafted this step, over the
    step's bounds and with its ``drafted`` / ``accepted``, inside the
    request's ``serving/decode`` (scheduler).  Speculating engines only:
    their loop is serial, so the step lies between two commits.  Host
    values only — never called with device arrays."""
    if not tracer.enabled:
      return
    for slot, state, _, _ in plan.fed:
      nd = int(num_draft[slot])
      if nd == 0 or self.scheduler.active.get(slot) is not state:
        continue  # (retired since it was planned: its span has closed)
      args = {"drafted": nd, "accepted": int(n_committed[slot]) - 1}
      if self.paged:
        args["kv_blocks"] = len(self.scheduler.slot_blocks(slot))
      tracer.span_at("speculate", t0_us, t1_us, cat="serving",
                     track=self._slot_tracks[slot], args=args)

  def _apply_degradation(self):
    """Feed the ladder this iteration's post-admission load signals and
    apply its level to the scheduler (speculation gate, budget clamp).
    Occupancy is relative to the EFFECTIVE concurrency cap — with
    max_batch < num_slots the batch saturates below full slot count,
    and budget_tight's occupancy gate must still be reachable."""
    itl = self.stats.itl_ewma_s if self.stats is not None else 0.0
    # The autotuner's slot-cap clamp shrinks effective concurrency;
    # occupancy (and with it budget_tight's gate) is judged against
    # the cap actually in force.
    cap = min(self.num_slots, self.scheduler.effective_max_batch)
    self._admission.observe(
        self.scheduler.queue_depth,
        self.scheduler.num_active / cap, itl)
    self.scheduler.spec_enabled = self._admission.speculation_enabled
    self.scheduler.budget_override = (
        self.chunk if self._admission.budget_tightened else 0)

  def _propose_drafts(self, tracer, plan):
    """Run the drafter for one step, tolerating drafter faults: a
    raising drafter degrades to zero drafts for the step (verification
    would reject garbage anyway — a flaky drafter may cost speed,
    never correctness), and a degraded ladder (spec_off and above)
    skips draft compute outright — the first ballast under overload."""
    # Per-SLOT count — the paged plan's tokens are flat [token_budget],
    # so draft_cap (always [num_slots]) carries N for both plan kinds.
    N = plan.draft_cap.shape[0]
    if not self.scheduler.spec_enabled:
      # getattr: observe_skip postdates the drafter protocol — a
      # duck-typed pre-resilience drafter must not crash the engine the
      # first time the ladder reaches spec_off.
      skip = getattr(self.drafter, "observe_skip", None)
      if skip is not None:
        skip(plan)
      return np.zeros((N,), np.int32)
    with tracer.span("serving/draft", cat="serving", track="serving"):
      try:
        histories = self.scheduler.slot_histories(plan)
        draft_tokens, num_draft = self.drafter.propose(plan, histories)
        # Clip (not minimum): a malformed proposal with a NEGATIVE count
        # must clamp to zero drafts, not ride into the token writes.
        num_draft = np.clip(np.asarray(num_draft, np.int32),
                            0, plan.draft_cap)
        # Inside the try: a propose() that returns malformed shapes
        # without raising fails HERE, and must degrade like any other
        # drafter fault rather than crash the step.
        for slot in np.nonzero(num_draft)[0]:
          nd = int(num_draft[slot])
          if self.paged:
            # Flat layout: drafts land at the slot's reserved draft
            # positions (scheduler pass 3) and flip exactly those
            # entries live; unused reservations stay invalid and write
            # to the null block.
            b = int(plan.draft_base[slot])
            plan.tokens[b:b + nd] = draft_tokens[slot, :nd]
            plan.valid[b:b + nd] = True
          else:
            plan.tokens[slot, 1:1 + nd] = draft_tokens[slot, :nd]
      except Exception as e:  # noqa: BLE001 — any drafter fault degrades
        self._drafter_failures += 1
        if not self._drafter_fail_logged:
          self._drafter_fail_logged = True
          get_logger().warning(
              "drafter %s failed (%s: %s); serving continues without "
              "drafts this step (logged once; see "
              "serving/drafter_failures)", type(self.drafter).__name__,
              type(e).__name__, e)
        # Partial draft writes before the failure are harmless: with
        # zero drafts every decode slot's num_valid stays 1, so the
        # written positions are masked garbage the step never reads.
        return np.zeros((N,), np.int32)
    return num_draft

  def _handle_bad_slots(self, plan, slot_ok: np.ndarray) -> List[int]:
    """Post-commit bad-step policy: update streaks, requeue/fail the
    slots the policy quarantines.  Returns the bad slot list."""
    bad = [int(s) for s in
           np.nonzero(~slot_ok & (plan.num_valid > 0))[0]]
    exercised = {int(s) for s in np.nonzero(plan.num_valid)[0]}
    actions = self._bad_policy.judge(self.scheduler.active, bad,
                                     exercised=exercised)
    if not bad:
      return bad
    get_logger().warning(
        "bad device step (non-finite logits) on slot(s) %s: %s", bad,
        {s: a for s, a in actions.items()})
    # Paged: snapshot block lists BEFORE requeue/retire return them to
    # the pool — the rows must be zeroed either way (the next owner of a
    # reused block needs the finiteness invariant to hold).
    blocks_by_slot = ({s: self.scheduler.slot_blocks(s) for s in bad}
                      if self.paged else None)
    slot_starts: Dict[int, int] = {}
    cursors = None
    for slot, action in actions.items():
      freed = action in (BadStepPolicy.REQUEUE, BadStepPolicy.FAIL)
      if action == BadStepPolicy.REQUEUE:
        self.scheduler.requeue_slot(slot, reason="bad_step")
      elif action == BadStepPolicy.FAIL:
        self.scheduler.retire_slot(slot, "failed")
      if self.paged:
        # Paged: zero from the committed watermark up, freed or not.
        # The plan's first scheduled position for the slot IS the
        # watermark — no device fetch needed (positions are
        # host-planned in the paged layout) — and every one of the bad
        # step's writes landed at a scheduled position at or above it.
        # Rows below hold real committed K/V; with prefix sharing live
        # a released prefix block may still be mapped by the radix
        # tree or a sibling slot's table, so zeroing below the
        # watermark would corrupt a HEALTHY request's cache.
        slot_starts[slot] = int(plan.positions[plan.base_idx[slot]])
      elif freed:
        slot_starts[slot] = 0
      else:  # RETRY: zero the bad step's uncommitted writes only.
        if cursors is None:  # host sync on the rare bad-step path only
          cursors = jax.device_get(self._cursors)
        slot_starts[slot] = int(cursors[slot])
    if slot_starts and self.paged:
      self._sanitize_paged(slot_starts, blocks_by_slot)
    elif slot_starts:
      self._sanitize_slots(slot_starts)
    if self.stats is not None:
      # Single source of truth: the policy already counted this event.
      self.stats.sync_bad_step_counters(self._bad_policy.counters())
    return bad

  def _sanitize_slots(self, slot_starts: Dict[int, int]) -> None:
    """Zero poisoned slots' K/V from each slot's start row up
    (slot_cache_attend's finiteness invariant: masking zeroes a stale
    row's softmax probability, but the V contraction still touches every
    cache row and ``0 * NaN = NaN``).  Freed slots pass start 0 (the
    next occupant must see a clean slot); retried slots pass their
    committed cursor (the prefix is real — only the bad step's writes
    above it are suspect, and the retry's grant may not cover them
    all)."""
    mask = np.zeros((self.num_slots,), bool)
    start = np.zeros((self.num_slots,), np.int32)
    for slot, row in slot_starts.items():
      mask[slot] = True
      start[slot] = row
    self._kv = self._sanitize_fn(self._kv, mask, start)

  def _sanitize_paged(self, slot_starts: Dict[int, int],
                      blocks_by_slot: Dict[int, list]) -> None:
    """Paged twin of :meth:`_sanitize_slots`: map each poisoned slot's
    (pre-release) block list to per-block start rows and zero with the
    same jitted program (dim 0 = pool blocks here).  The null block is
    always included — a NaN-params step poisons it through the padding
    writes, and every slot's gather can touch it."""
    bs = self.block_size
    mask = np.zeros((self.num_blocks,), bool)
    start = np.zeros((self.num_blocks,), np.int32)
    mask[kv_lib.NULL_BLOCK] = True
    for slot, pos in slot_starts.items():
      for j, blk in enumerate(blocks_by_slot.get(slot, ())):
        if (j + 1) * bs <= pos:
          continue  # wholly below the committed watermark: rows are real
        row = max(0, pos - j * bs)
        # A block CAN appear twice now that prefix sharing is real
        # (serving/prefix_cache.py) — but only a shared PREFIX block,
        # which sits wholly below every sharer's watermark and is
        # skipped above.  Two bad slots listing one block therefore
        # agree it needs zeroing; keep the LOWEST start defensively.
        start[blk] = row if not mask[blk] else min(start[blk], row)
        mask[blk] = True
    # Zeroed content must never satisfy a future prefix match.  Purely
    # defensive — registration is commit-gated, so a masked
    # (above-watermark) block is never in the tree — but the purge is
    # cheap and makes the invariant unconditional.
    self.scheduler.invalidate_cached_blocks(
        int(b) for b in np.nonzero(mask)[0] if b != kv_lib.NULL_BLOCK)
    self._kv = self._sanitize_fn(self._kv, mask, start)

  def _step_args(self, plan, num_draft):
    """The fused step's arguments for this plan, in the order its twin
    takes them: speculative (``num_draft`` given) or plain, paged or
    contiguous."""
    sampling = (plan.keys, plan.tok_index, plan.temperature, plan.top_k,
                plan.top_p)
    if self.paged:
      last_idx = (plan.base_idx + plan.num_valid - 1).astype(np.int32)
      drafts = () if num_draft is None else (plan.draft_base, num_draft)
      return (self.params, self._kv, plan.tokens, plan.slot_ids,
              plan.positions, plan.valid, plan.block_tables, last_idx,
              *drafts, plan.num_valid > 0, *sampling)
    if self.slot_axis is None:
      live = plan.prefill_tokens + plan.decode_tokens + (
          0 if num_draft is None else int(num_draft.sum()))
    else:
      # Divided over the slots, the width is a chip's: the fullest one's.
      live = int(self._chip_live(plan).max())
    if live > self.flat_width:
      # The scheduler's ceiling keeps every plan within the width; a
      # plan beyond it would lose its last positions without a sign.
      raise RuntimeError(
          f"the plan holds {live} live positions, the step's flat width "
          f"is {self.flat_width}")
    if num_draft is None:
      return (self.params, self._kv, self._cursors, plan.tokens,
              plan.num_valid, plan.reset, self._prev_tokens,
              plan.from_prev, *sampling)
    return (self.params, self._kv, self._cursors, plan.tokens,
            plan.num_valid + num_draft, num_draft, plan.reset, *sampling)

  def _launch(self, tracer, plan, overlapped: bool) -> _LaunchedStep:
    """[draft ->] gather the fused step's arguments and launch it: the
    host half of the one place where all four twins cross to the device.
    Returns at once, with the step's outputs still on the device; a
    launch that raises leaves the plan abandoned (it never ran)."""
    t0 = time.monotonic()
    if self._watchdog is not None:
      self._watchdog.arm(self._steps)
    xla_ctx = None
    if (self._pending_xla_dir is not None
        and (self._inflight is None or self._inflight.xla_ctx is None)):
      # Deep capture armed a device profile for the step AFTER the
      # breach (observability.slo.capture_xla): the anomaly's immediate
      # aftermath is the timeline worth keeping.  One capture at a time:
      # with one still open around the step in flight, the next launch
      # takes it.
      xla_dir, self._pending_xla_dir = self._pending_xla_dir, None
      xla_ctx = tracer.xla_trace(xla_dir)
      xla_ctx.__enter__()
    try:
      num_draft = None
      if self.drafter is not None:
        # Propose BEFORE the token block gains drafts: the draft
        # model's mirror call needs the same plan the target sees.
        num_draft = self._propose_drafts(tracer, plan)
      t0_us = tracer.now_us()
      step_args = self._step_args(plan, num_draft)
      self._note_step_specs(step_args)
      out = self._step_fn(*step_args)
      launched_us = tracer.now_us()
    except BaseException:
      self.scheduler.abandon(plan)
      if self._watchdog is not None:
        self._watchdog.disarm()
      if xla_ctx is not None:
        xla_ctx.__exit__(None, None, None)
      raise
    # Every twin returns ``(*tokens, [ok,] kv[, cursors])``: (next_tokens,)
    # or, speculative, (committed, n_committed) — commit()'s own
    # positional arguments — and the plain step of an expert model its
    # load beside them (speculation is refused for the only such model).
    n_fetch = ((1 if num_draft is None else 2)
               + int(self._experts and num_draft is None))
    tokens, state = out[:n_fetch], out[n_fetch:]
    ok_dev = None
    if self._resilient:
      ok_dev, state = state[0], state[1:]
    if self.paged:
      (self._kv,) = state
    else:
      self._kv, self._cursors = state
    if self._prev_tokens is not None:
      self._prev_tokens = tokens[0]
    return _LaunchedStep(plan=plan, tokens=tokens, ok=ok_dev,
                         num_draft=num_draft, t0=t0, t0_us=t0_us,
                         launched_us=launched_us, overlapped=overlapped,
                         xla_ctx=xla_ctx)

  def _close_step(self, step: _LaunchedStep) -> None:
    """What ends with a launched step whatever became of it: the
    watchdog's arm and a device capture opened for it."""
    if self._watchdog is not None:
      self._watchdog.disarm()
    if step.xla_ctx is not None:
      step.xla_ctx.__exit__(None, None, None)

  def step(self) -> List[FinishedRequest]:
    """One engine iteration: [degrade ->] plan -> [draft ->] launch the
    fused device step -> fetch -> commit [-> bad-step policy].  Returns
    the requests that retired this iteration (empty when idle), expiries
    and cancellations included.

    Overlapped (``step_overlap == "on"``), the same stages run on TWO
    steps: with step k in flight the call plans k+1, launches k+1, THEN
    fetches k's tokens and commits k, so planning, the argument upload,
    the launch, the commit and whatever the caller does between two calls
    run while the device executes, and it goes from k to k+1 without
    waiting for the host.  A caller sees a step's retirements one call
    later than its launch, and ``has_work`` stays true while a step is in
    flight; an engine with nothing in flight launches at once."""
    tracer = trace_lib.get_tracer()
    if self._autotuner is not None:
      # Knob moves land HERE — strictly between fused-step dispatches,
      # steering the plan built just below (compile-once: data only).
      self._autotuner.on_step(self._steps)
    running = self._inflight
    with tracer.span("serving/plan", cat="serving", track="serving"):
      plan = self.scheduler.plan_step(ahead=running is not None)
    if self._admission is not None:
      # Observe AFTER admission: the ladder's queue signal is the
      # backlog this step could NOT absorb — a one-shot burst that
      # admission fully drains must not read as overload (it would
      # falsely shed follow-up submits for the hysteresis window).
      # The resulting gates steer the NEXT plan; one step of lag is
      # the price of measuring the right signal.
      self._apply_degradation()
    launched = None
    if plan is not None:
      launched = self._launch(tracer, plan, overlapped=running is not None)
    # The serial loop fetches what it just launched; the overlapped one
    # what the call before launched, and leaves this launch in flight.
    if self._overlap:
      self._inflight = launched
    done = running if self._overlap else launched
    if done is None:
      if launched is not None:
        # The first step of a burst: nothing to wait for yet.
        step_arg = {"step": self._steps + 1} if tracer.enabled else None
        tracer.span_at(
            "serving/device_step", launched.t0_us, launched.launched_us,
            cat="serving", track="serving", args=step_arg,
            children=(("serving/dispatch", launched.t0_us,
                       launched.launched_us),))
      # No device work to commit, but plan-time expiries may have
      # retired requests (e.g. every queued request's deadline passed).
      return self._take_finished()
    finished = self._finish_step(tracer, done, launched)
    return self._take_finished() + finished

  def _finish_step(self, tracer, step: _LaunchedStep,
                   launched: Optional[_LaunchedStep]
                   ) -> List[FinishedRequest]:
    """Fetch what ``step`` left on the device, commit it and record it:
    the device -> host half.  ``launched`` is the step this call launched
    before it (``step`` itself in the serial loop, None in a drain).

    Spans, on the ``serving`` track: ``serving/device_step`` from the
    stamp before the launched step's arguments were gathered to the
    return of the last fetch, tiled by ``serving/dispatch`` (host:
    argument transfer and launch) and ``serving/fetch`` (the wait for
    ``step``'s tokens and the way back).  In the serial loop both are of
    one step and the device has nothing to run during the dispatch;
    overlapped, the dispatch is of step k+1 and the fetch of step k,
    which runs meanwhile.  Either way one dispatch starts per step.  The
    benchmark reads all three by name (PERF.md section 3).  ``args.step``
    of ``serving/device_step`` is the step whose fetch the span holds (a
    span that is all dispatch: the step it launched), as the per-step
    record numbers them.  Then ``serving/commit`` and ``serving/publish``
    (``_publish_step``): with ``serving/plan`` the host's turn is named
    from end to end, and a step records the same events whatever is live
    (a speculating engine adds a ``speculate`` span a drafting slot)."""
    plan = step.plan
    drafted = accepted = 0
    num_draft = step.num_draft
    expert_load = None
    t_fetch_us = (launched.launched_us if launched is not None
                  else tracer.now_us())
    try:
      slot_ok = None if step.ok is None else jax.device_get(step.ok)
      # The step's ONE designated token fetch: explicit (device_get),
      # so it stays visible — and legal — under
      # jax.transfer_guard_device_to_host("disallow"); any OTHER
      # device->host crossing in this loop is a bug the guard (and
      # epl-lint's host-sync rule) catches.
      fetched = [jax.device_get(t) for t in step.tokens]
      t1_us = tracer.now_us()
      step_arg = {"step": self._steps + 1} if tracer.enabled else None
      children = (("serving/fetch", t_fetch_us, t1_us),)
      if launched is not None:
        children = (("serving/dispatch", launched.t0_us,
                     t_fetch_us),) + children
      tracer.span_at(
          "serving/device_step",
          launched.t0_us if launched is not None else t_fetch_us, t1_us,
          cat="serving", track="serving", args=step_arg,
          children=children)
      if self._experts and num_draft is None:
        *fetched, expert_load = fetched
      n_committed = None
      if num_draft is not None:
        n_committed = fetched[1]
        self._trace_speculation(tracer, plan, step.t0_us, t1_us, num_draft,
                                n_committed)
      with tracer.span("serving/commit", cat="serving", track="serving"):
        finished = self.scheduler.commit(*fetched, slot_ok=slot_ok,
                                         num_draft=num_draft)
        if self.drafter is not None:
          self.drafter.observe_commit(self._cursors)
      if num_draft is not None:
        # Stats count only slots whose verdict committed: a bad slot's
        # n_committed is NaN-logit garbage and its drafts are re-spent
        # on the retry — counting them would double/poison the
        # acceptance-rate samples under chaos.
        ok = np.ones(num_draft.shape, bool) if slot_ok is None else slot_ok
        speculated = (num_draft > 0) & ok
        drafted = int(num_draft[ok].sum())
        accepted = int((n_committed[speculated] - 1).sum())
    except BaseException:
      # The step's output is lost to the scheduler, and with it the step
      # launched past it: back to the committed state.
      self.scheduler.abandon()
      later, self._inflight = self._inflight, None
      for lost in (step, later):
        if lost is not None:
          self._close_step(lost)
      raise
    with tracer.span("serving/publish", cat="serving", track="serving"):
      self._publish_step(tracer, step, slot_ok, finished, drafted, accepted,
                         expert_load)
    return finished

  def _publish_step(self, tracer, step: _LaunchedStep, slot_ok, finished,
                    drafted: int, accepted: int, expert_load) -> None:
    """What a committed step leaves behind, the tail of the host's turn
    (span ``serving/publish``): the watchdog's arm and a device capture
    closed, the bad-slot policy (its retirements join ``finished``), the
    compile sentinel, the introspector, the step's counters, the stats
    sample and the per-step record to writer, registry and SLO monitor."""
    plan = step.plan
    self._close_step(step)
    if slot_ok is not None:
      self._handle_bad_slots(plan, slot_ok)
      # Quarantine retirements ("failed") belong to this iteration.
      finished.extend(self.scheduler.take_finished())
    self._steps += 1
    # Compile sentinel: one host int compare per step; the signature
    # thunk only runs on the (rare) recompile path.
    self._compile_sentinel.check(
        signature_fn=lambda: self._describe_signature(plan))
    # The step's time runs from its launch or, overlapped, from the end
    # of its predecessor (the device ran them one after the other).
    now = time.monotonic()
    dt = now - max(step.t0, self._last_step_end)
    self._last_step_end = now
    # Device introspection runs BELOW the dt cut, like every other
    # publish path: the warmup capture's AOT compile and the HBM
    # gauges' per-device memory_stats host RPC must never inflate the
    # step_time_s sample that feeds the ITL EWMA the admission ladder
    # and SLO rules act on.
    if self._pending_step_specs is not None:
      # Warmup cost card (observability/device.py): introspect the twin
      # through the AOT surface with the specs snapshotted above.  The
      # jit call cache is untouched (the sentinel above stays silent —
      # pinned) and no live buffer is read.
      specs, self._pending_step_specs = self._pending_step_specs, None
      self._introspector.capture_twin(
          self._twin_label, self._step_fn, specs,
          compile_count=self._compile_sentinel.cache_size() or 0,
          meta=self._twin_meta())
    if (self._introspector is not None
        and (self._steps == 1
             or self._steps % _STATS_PUBLISH_EVERY == 0)):
      # HBM watermark gauges on the existing stats cadence (plus once
      # right after warmup so short episodes still carry a sample):
      # observability/device/* registry records + Perfetto counters;
      # the SLO monitor sees them through the registry sink (or
      # directly on registry-less engines).
      self._introspector.publish_hbm(self._steps, registry=self.registry,
                                     monitor=self._slo)
    # Throughput/ITL samples count COMMITTED tokens only: a bad slot's
    # planned tokens never committed and the identical work is re-fed
    # next step — counting both would double prefill/decode throughput
    # under chaos (same rule as the drafted/accepted exclusion above).
    if slot_ok is None or bool(slot_ok.all()):
      pf_tokens, dc_tokens = plan.prefill_tokens, plan.decode_tokens
    else:
      ok = (plan.num_valid > 0) & slot_ok
      pf_tokens = int(plan.num_valid[ok & plan.prefilling].sum())
      dc_tokens = int((ok & ~plan.prefilling).sum())
    # Slots the step was handed a temperature > 0 for: exactly what the
    # plain step's own predicate reads (sample_token_slots), so 0 means
    # that step took the argmax alone and sorted nothing.
    sampled_slots = int(np.count_nonzero(plan.temperature > 0))
    # Cache rows under the bounds of the slots the step fed (the plan's
    # own sum of cursor + num_valid): what an attend bounded per slot
    # reads of ``_kv_rows``, and all a roofline of it may count.
    live_kv_rows = plan.live_kv_rows
    # Live rows of the step's flat batch (the plan's sum of ``num_valid``
    # and the drafts that rode it), of ``flat_width`` on the contiguous
    # cache; and the positions the plan held back for want of a row (0:
    # the width cut nothing this step).  An expert model's layers route
    # the plan's own positions.
    # Rows one layer's ``slot_attn`` walk covers this step: each live
    # bound up to the walk's granule.  Over ``live_kv_rows`` it is how
    # much of what the kernel fetches no query can see.
    attn_rows_read = (
        _walk_rows(plan.resident, plan.num_valid, *self._attn_walk)
        if self._attn_walk is not None else None)
    # Chunk positions one layer's tile-grid attend works on this step:
    # over ``flat_positions`` it is how much of that work no live query
    # asked for (a partial last tile's dead positions).
    attn_tile_positions = (
        _tile_positions(plan.num_valid, *self._attn_tile)
        if self._attn_tile is not None else None)
    fed_positions = plan.prefill_tokens + plan.decode_tokens
    flat_positions = fed_positions + (
        0 if step.num_draft is None else int(step.num_draft.sum()))
    flat_trimmed = plan.flat_trimmed
    # 1 where the step ran its layers on the narrow width
    # (``narrow_width``): the step's own predicate, on the host's copy of
    # the sum it takes it from.
    flat_narrow = int(flat_positions <= self.flat_narrow < self.flat_width)
    routed_positions = fed_positions if self._experts else 0
    expert_load_max, experts_touched_min, *held = (
        map(float, expert_load) if expert_load is not None else (0.0, 0.0))
    held_assignments = held[0] if held else 0.0
    if self.slot_axis is not None:
      # The fullest and the emptiest chip's live positions (the step is
      # as slow as the fullest), and what the expert layers exchanged.
      chip_live = self._chip_live(plan)
      chip_live_max, chip_live_min = int(chip_live.max()), int(chip_live.min())
      exchange_rows_out, exchange_rows_in, exchange_rounds = held[1:4]
      # Narrow only where every chip's own live positions fit.
      flat_narrow = int(chip_live_max <= self.flat_narrow < self.flat_width)
    if self._sparse is not None:
      # Three sums over the plan: the index rows one selecting layer's
      # queries score (every row under the slot's bound), the rows its
      # selection keeps (``min(t + 1, top_k)`` a live query at ``t``) and
      # the rows a window layer keeps (``min(t + 1, window)``).
      index_rows = int(np.sum(plan.num_valid.astype(np.int64)
                              * (plan.resident + plan.num_valid)))
      selected_rows, window_rows = (
          _rows_up_to(plan.resident, plan.num_valid, k)
          for k in self._sparse)
    if self._kv_window is not None:
      # Two sums over the plan's live slots: the rows a full layer must
      # read and the rows a layer behind the window must.
      context_rows, kv_window_rows = _slot_rows(
          plan.resident, plan.num_valid, self._kv_window)
    # Whether the step was launched with its predecessor in flight (0:
    # the pipeline was empty, the first step after idle or a drain), and
    # the positions it ran for requests that had retired by its commit.
    overlapped = int(step.overlapped)
    if tracer.enabled:
      tracer.counter("serving/active_slots", plan.active_slots)
      tracer.counter("serving/overlapped_steps", overlapped)
      tracer.counter("serving/wasted_positions", plan.wasted)
      tracer.counter("serving/sampled_slots", sampled_slots)
      tracer.counter("serving/live_kv_rows", live_kv_rows)
      if attn_rows_read is not None:
        tracer.counter("serving/attn_rows_read", attn_rows_read)
      tracer.counter("serving/flat_positions", flat_positions)
      if attn_tile_positions is not None:
        tracer.counter("serving/attn_tile_positions", attn_tile_positions)
      tracer.counter("serving/flat_trimmed", flat_trimmed)
      tracer.counter("serving/flat_narrow", flat_narrow)
      if self._recurrent:
        # Slots whose recurrent state this step zeroed: requests that
        # started (or restarted, after a requeue) here.
        tracer.counter("serving/state_resets", int(plan.reset.sum()))
      if self._delta:
        # Slots whose matrix state advanced this step, and the live
        # positions of those that fed more than one: the chunk's form of
        # the delta rule (a slot that fed one ran one position's).
        tracer.counter("serving/state_slots",
                       int((plan.num_valid > 0).sum()))
        tracer.counter("serving/state_chunk_positions", int(
            plan.num_valid[plan.num_valid > 1].sum()))
      if self._experts:
        # Live positions the step handed its expert layers (each goes to
        # ``num_experts_per_tok`` experts), and how unevenly they fell.
        tracer.counter("serving/routed_positions", routed_positions)
        tracer.counter("serving/expert_load_max", expert_load_max)
        # The fewest experts with a live assignment over the step's
        # expert layers: under the model's expert count, the step did
        # not stream every expert's weights.
        tracer.counter("serving/experts_touched_min", experts_touched_min)
      if self.experts_held is not None:
        # Live assignments that fell on the experts this chip holds, all
        # expert layers: of ``routed_positions x num_experts_per_tok`` a
        # layer.
        tracer.counter("serving/held_assignments", held_assignments)
      if self.slot_axis is not None:
        tracer.counter("serving/exchange_rows_out", exchange_rows_out)
        tracer.counter("serving/exchange_rows_in", exchange_rows_in)
        tracer.counter("serving/chip_live_max", chip_live_max)
        tracer.counter("serving/chip_live_min", chip_live_min)
      if self._sparse is not None:
        tracer.counter("serving/index_rows", index_rows)
        tracer.counter("serving/selected_rows", selected_rows)
        tracer.counter("serving/window_rows", window_rows)
      if self._kv_window is not None:
        tracer.counter("serving/context_rows", context_rows)
        tracer.counter("serving/kv_window_rows", kv_window_rows)
    if self.stats is not None:
      self.stats.note_step(
          active_slots=plan.active_slots, num_slots=self.num_slots,
          prefill_tokens=pf_tokens,
          decode_tokens=dc_tokens, step_time_s=dt,
          drafted_tokens=drafted, accepted_tokens=accepted,
          sampled_slots=sampled_slots, live_kv_rows=live_kv_rows,
          kv_rows=self._kv_rows, routed_positions=routed_positions,
          expert_load_max=expert_load_max,
          experts_touched_min=experts_touched_min,
          overlapped=overlapped, wasted_positions=plan.wasted,
          flat_positions=flat_positions, flat_trimmed=flat_trimmed,
          flat_narrow=flat_narrow)
      if self._sparse is not None:
        self.stats.note_sparse_step(index_rows, selected_rows, window_rows,
                                    held_assignments)
      if self._kv_window is not None:
        self.stats.note_kv_window_step(context_rows, kv_window_rows)
      if self.slot_axis is not None:
        self.stats.note_divided_step(
            chip_live_max, chip_live_min, exchange_rows_out,
            exchange_rows_in, exchange_rounds)
      if self.paged:
        self.stats.note_blocks(self.scheduler.kv_blocks_free,
                               self.scheduler.kv_blocks_used,
                               self.scheduler.kv_fragmentation,
                               self.scheduler.preemptions,
                               self.scheduler.proactive_preemptions)
        if self.prefix_caching:
          self.stats.note_prefix(self.scheduler.prefix_hits,
                                 self.scheduler.prefix_misses,
                                 self.scheduler.prefix_blocks_reused,
                                 self.scheduler.prefix_evictions,
                                 self.scheduler.prefix_cached_blocks)
    if (self.metrics_writer is not None or self.registry is not None
        or self._slo is not None):
      record = {
          "active_slots": plan.active_slots,
          "slot_occupancy": plan.active_slots / self.num_slots,
          "sampled_slots": sampled_slots,
          "live_kv_rows": live_kv_rows,
          "flat_positions": flat_positions,
          "flat_trimmed": flat_trimmed,
          "flat_narrow": flat_narrow,
          "prefill_tokens": pf_tokens,
          "decode_tokens": dc_tokens,
          "step_time_s": dt,
          "overlapped_steps": overlapped,
          "wasted_positions": plan.wasted,
      }
      if self._experts:
        record["routed_positions"] = routed_positions
        record["expert_load_max"] = expert_load_max
        record["experts_touched_min"] = experts_touched_min
      if self.experts_held is not None:
        record["held_assignments"] = held_assignments
      if self.slot_axis is not None:
        record["exchange_rows_out"] = exchange_rows_out
        record["exchange_rows_in"] = exchange_rows_in
        record["chip_live_max"] = chip_live_max
        record["chip_live_min"] = chip_live_min
      if self._sparse is not None:
        record["index_rows"] = index_rows
        record["selected_rows"] = selected_rows
        record["window_rows"] = window_rows
      if self._kv_window is not None:
        record["context_rows"] = context_rows
        record["kv_window_rows"] = kv_window_rows
      if self.paged:
        # The block-pool gauges (ROADMAP item 1 satellite): pool
        # occupancy, internal fragmentation, and preemption count under
        # the serving/* schema.
        record["kv_blocks_free"] = self.scheduler.kv_blocks_free
        record["kv_blocks_used"] = self.scheduler.kv_blocks_used
        record["kv_fragmentation"] = self.scheduler.kv_fragmentation
        record["preemptions"] = self.scheduler.preemptions
        record["proactive_preemptions"] = (
            self.scheduler.proactive_preemptions)
        if self.prefix_caching:
          # Prefix-cache counters under the same serving/* schema
          # (cumulative, like preemptions).
          record["prefix_hits"] = self.scheduler.prefix_hits
          record["prefix_misses"] = self.scheduler.prefix_misses
          record["prefix_blocks_reused"] = (
              self.scheduler.prefix_blocks_reused)
          record["prefix_evictions"] = self.scheduler.prefix_evictions
          record["prefix_cached_blocks"] = (
              self.scheduler.prefix_cached_blocks)
      if self.drafter is not None:
        record["drafted_tokens"] = drafted
        record["accepted_tokens"] = accepted
        record["drafter_failures"] = self._drafter_failures
      if self._resilient:
        record["queue_depth"] = self.scheduler.queue_depth
        record["degraded_level"] = self._admission.level
        record["shed"] = self._admission.shed_total
        record.update(self._bad_policy.counters())
        if self.stats is not None:
          # The cumulative good-counter partner of "shed", so burn-rate
          # rules (bad="shed", good="finished_requests") evaluate on
          # every per-step record — not only on the sparse percentile
          # rollups — and an overloaded engine's own monitor breaches
          # while the overload is still happening.
          record["finished_requests"] = float(
              self.stats.finished_requests)
      if self._autotuner is not None:
        # Actuator evidence rides the existing serving/* schema: the
        # current tune level and cumulative actuation count per step.
        record["autotune_level"] = self._autotuner.level
        record["autotune_actuations"] = self._autotuner.actuations
      if self.metrics_writer is not None:
        # Legacy flat keys (pre-registry callers depend on them).
        self.metrics_writer.write(self._steps, record)
      if self.registry is not None:
        # The SLO monitor rides the registry as a sink (attach above) —
        # publishing once feeds the sinks AND the rules.
        self.registry.publish(self._steps, record, "serving")
      elif self._slo is not None:
        # Registry-less engine: feed the monitor the same namespaced
        # record directly (host scalars only — no added syncs), through
        # the validated schema helper rather than an ad-hoc key literal.
        self._slo.observe(
            self._steps,
            MetricRegistry.namespaced(SERVING_NAMESPACE, record))
    if (self.stats is not None
        and self._steps % _STATS_PUBLISH_EVERY == 0
        and (self.registry is not None or self._slo is not None)):
      # Periodic percentile rollup so latency SLO rules stay LIVE on a
      # long-serving engine (_STATS_PUBLISH_EVERY above).
      if self.registry is not None:
        self.stats.publish(self.registry, self._steps)
      else:
        self._slo.observe(
            self._steps,
            MetricRegistry.namespaced(SERVING_NAMESPACE,
                                      self.stats.summary()))

  def run(self, max_steps: Optional[int] = None
          ) -> Dict[Any, np.ndarray]:
    """Drive until the queue drains (or ``max_steps``); returns
    ``{uid: prompt+generated}`` for every request finished during the
    call (finish reasons: ``self.finished[uid].finish_reason``).  A step
    still in flight when ``max_steps`` cuts the drive is fetched and
    committed before the call returns: every step launched has committed."""
    out: Dict[Any, np.ndarray] = {}
    steps = 0
    while self.has_work and (max_steps is None or steps < max_steps):
      for fin in self.step():
        out[fin.uid] = fin.tokens
      steps += 1
    self._drain()
    for fin in self._take_finished():
      out[fin.uid] = fin.tokens
    if self.registry is not None and self.stats is not None:
      # End-of-drive rollup (tokens/s, TTFT/ITL percentiles, occupancy,
      # speculation + resilience counters) under the serving/* namespace.
      self.stats.publish(self.registry, self._steps)
    return out
