"""Auto-parallel planner — stage search and collective-matmul crossover.

Analog of the reference's ``AutoStageGenerator``
(epl/parallel/planner.py:37-112), which searches stage boundaries with
three policies: balance-op-num, repeated-layers, and a heuristic mix.
Here the unit is a block (module) list with optional weights:

  * ``balance_param`` — contiguous min-max partition by parameter count
    (the balance-op-num analog; uses partitioner.partition_balance),
  * ``balance_flops`` — same, weighted by per-block FLOPs from the XLA
    cost model when provided,
  * ``repeated_layers`` — split at repeated-block family boundaries
    (partitioner.find_repeated_blocks), then balance within the dominant
    family.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

from easyparallellibrary_tpu.env import Env
from easyparallellibrary_tpu.parallel.partitioner import (
    find_repeated_blocks, partition_balance, partition_stages)
from easyparallellibrary_tpu.utils.logging import get_logger
from easyparallellibrary_tpu.utils.pytree import tree_param_count


class AutoStageGenerator:
  """Search stage assignment for an ordered block list."""

  def __init__(self, policy: Optional[str] = None,
               num_stages: Optional[int] = None):
    cfg = Env.get().config
    self.policy = policy or cfg.auto.stage_policy
    self.num_stages = num_stages or cfg.pipeline.num_stages

  def search(self, block_names: Sequence[str],
             block_params: Optional[Dict[str, int]] = None,
             block_flops: Optional[Dict[str, float]] = None
             ) -> List[List[str]]:
    """Returns num_stages lists of block names."""
    names = list(block_names)
    if self.num_stages <= 1:
      return [names]
    if self.policy == "balance_flops" and block_flops:
      return partition_stages(names, self.num_stages, block_flops)
    if self.policy == "repeated_layers":
      groups = find_repeated_blocks(names)
      # Dominant repeated family sets the cut points, but stages must
      # cover EVERY block: cut the full ordered list at the positions of
      # the chosen family members, so interleaved non-family blocks stay
      # attached to their neighbourhood.
      family = max(groups.values(), key=len)
      if len(family) >= self.num_stages:
        fam_stages = partition_stages(family, self.num_stages, block_params)
        # Index in `names` where each stage's first family member sits.
        cut_points = [names.index(s[0]) for s in fam_stages]
        cut_points[0] = 0
        cut_points.append(len(names))
        return [names[cut_points[s]:cut_points[s + 1]]
                for s in range(self.num_stages)]
      get_logger().warning(
          "repeated_layers policy found only %d repeated blocks for %d "
          "stages; falling back to balance_param", len(family),
          self.num_stages)
    weights = block_params or {}
    return partition_stages(names, self.num_stages, weights)

  def search_from_params(self, params_by_block: Dict[str, dict],
                         ) -> List[List[str]]:
    """Stage search weighted by actual per-block parameter counts."""
    weights = {name: float(tree_param_count(tree))
               for name, tree in params_by_block.items()}
    return self.search(list(params_by_block), block_params=weights)

  def search_from_cost_model(self, apply_fns: Dict[str, Callable],
                             *sample_args) -> List[List[str]]:
    """Stage search weighted by XLA-measured per-block FLOPs.

    `apply_fns` maps block name → a jittable fn of `sample_args` (e.g.
    `lambda x: block.apply(params_i, x)`).  This is the profiled-cost path
    the reference feeds from its static profiler into the planner
    (epl/profiler/profiler.py:36-60 → parallel/planner.py).
    """
    from easyparallellibrary_tpu.profiler.flops import compiled_cost
    flops = {}
    for name, fn in apply_fns.items():
      cost = compiled_cost(fn, *sample_args)
      flops[name] = float(cost.get("flops", 1.0)) or 1.0
    # This method IS the balance-by-measured-flops path, regardless of the
    # instance policy (which governs name/param-based searches).
    if self.num_stages <= 1:
      return [list(apply_fns)]
    return partition_stages(list(apply_fns), self.num_stages, flops)


# ---------------------------------------------------------------------------
# Collective-matmul overlap crossover (communicators/overlap.py's policy).
# ---------------------------------------------------------------------------

# Canonical overlap-site names — the planner OWNS the site naming so
# the measurement half of the loop (observability/device.py: per-site
# measured collective bytes registered/consumed through
# ``resolve_num_chunks(site=...)``) and the call sites themselves
# (ops/layers.py, ops/distributed_ops.py, parallel/pipeline_smap.py)
# never drift on the string.  A site is one decomposition adjacency in
# the program, not one tensor: every row-parallel Dense shares
# SITE_ROW_DENSE, so a measurement there describes the per-layer wire
# traffic of that adjacency, which is exactly the quantity
# ``plan_collective_matmul``'s crossover trades against MXU time.
SITE_ROW_DENSE = "layers/row_dense"
SITE_GATHER_MATMUL = "distributed_ops/gather_matmul"
SITE_MATMUL_SCATTER = "distributed_ops/matmul_scatter"
SITE_ZERO1_REDUCE_SCATTER = "pipeline_smap/zero1_reduce_scatter"
OVERLAP_SITES = (SITE_ROW_DENSE, SITE_GATHER_MATMUL,
                 SITE_MATMUL_SCATTER, SITE_ZERO1_REDUCE_SCATTER)

# Defaults for the analytic model.  ICI link bandwidth is the per-chip
# bidirectional ring figure public TPU specs quote (~100 GB/s is the v4
# per-link order of magnitude); the per-ring-step latency covers permute
# launch + hop.  Both are overridable per call — the CROSSOVER SHAPE
# (overlap wins once the hidden bytes outweigh per-step latency and
# small-matmul inefficiency) is what the policy needs, not chip-exact
# constants.
DEFAULT_ICI_BYTES_PER_S = 100e9
DEFAULT_STEP_LATENCY_US = 2.0
# A chunked matmul loses MXU efficiency once chunks get skinny; modeled
# as a fixed per-chunk re-issue cost.
DEFAULT_CHUNK_OVERHEAD_US = 1.0
# Off-TPU (the CPU test mesh) the crossover is still computed, as a
# model of the chip the program is written for.
MODEL_DEVICE_KIND = "TPU v5e"


def _model_peak_flops() -> float:
  """Peak FLOP/s the analytic crossover divides by: the running chip's
  own table entry on TPU (an unknown kind raises there), the named
  model device's everywhere else."""
  import jax
  from easyparallellibrary_tpu.profiler.flops import (
      PEAK_FLOPS, peak_flops_per_chip)
  if jax.default_backend() == "tpu":
    return peak_flops_per_chip()
  return PEAK_FLOPS[MODEL_DEVICE_KIND]


@dataclasses.dataclass(frozen=True)
class OverlapDecision:
  """Outcome of the analytic collective-matmul crossover model."""
  enabled: bool
  num_chunks: int          # ring chunk count when enabled (1 otherwise)
  fused_us: float          # modeled serialized (fused) time
  overlapped_us: float     # modeled time at `num_chunks`
  comm_us: float           # wire time of the collective alone
  matmul_us: float         # MXU time of the matmul alone


def _divisors_desc(n: int) -> List[int]:
  return [d for d in range(n, 1, -1) if n % d == 0]


def plan_collective_matmul(kind: str, *, m: int, k: int, n_out: int,
                           axis_size: int, dtype_bytes: int = 2,
                           num_chunks: int = 0,
                           peak_flops: Optional[float] = None,
                           link_bytes_per_s: float = DEFAULT_ICI_BYTES_PER_S,
                           step_latency_us: float = DEFAULT_STEP_LATENCY_US,
                           chunk_overhead_us: float =
                           DEFAULT_CHUNK_OVERHEAD_US,
                           measured_collective_bytes: Optional[float] =
                           None) -> OverlapDecision:
  """Analytic crossover for one decomposed-collective-matmul site.

  ``kind``: "all_gather_matmul" (x local [m, k] gathered then @ [k,
  n_out]), "matmul_reduce_scatter" ([m, k] @ [k, n_out] then scattered),
  or "reduce_scatter" (an [m, k] buffer reduced, no adjacent matmul —
  the hidden compute is the neighbouring buckets', modeled as the wire
  time itself).  Dims are LOCAL (per device).

  The quantities are the ones the XLA cost-model path reports
  (``profiler.flops.compiled_cost``: flops and bytes): matmul time =
  flops / peak, wire time = ring bytes / link bandwidth.  Fused time
  serializes them; overlapped time hides the smaller under the larger
  but pays per-ring-step latency and per-chunk re-issue overhead:

      T_fused       = T_comm + T_mm
      T_overlap(K)  = max(T_comm, T_mm) + min(T_comm, T_mm) / K
                      + (n - 1) * step_latency + K * chunk_overhead

  Overlap is enabled iff the best divisor K of ``axis_size`` (or the
  caller-pinned ``num_chunks``) beats the fused time.  Below the
  crossover — small matmuls, where per-step latency dominates the bytes
  it could hide — the model picks the fused program, which is why the
  ``auto`` policy is safe to leave on everywhere.

  ``measured_collective_bytes`` replaces the analytically-derived wire
  bytes with a PROFILER MEASUREMENT of THIS SITE's collective traffic
  per step, so the crossover flips on from evidence instead of modeled
  dims (ROADMAP item 5c: TPU crossovers need measured constants).  The
  measurement must be site-scoped — e.g. ``profiler.flops.
  collective_bytes`` over a lowering of just this decomposition site —
  NOT a whole-program aggregate like ``FlopsProfiler``'s
  ``comm_bytes_per_step``, which sums every collective in the step and
  would inflate each site's comm time N-fold in an N-site program.
  The analytic derivation stays the fallback when None/0 — same
  decision shape, better inputs.
  """
  if kind not in ("all_gather_matmul", "matmul_reduce_scatter",
                  "reduce_scatter"):
    raise ValueError(f"unknown collective-matmul kind {kind!r}")
  n = axis_size
  if n <= 1:
    return OverlapDecision(False, 1, 0.0, 0.0, 0.0, 0.0)
  if peak_flops is None:
    peak_flops = _model_peak_flops()

  if kind == "all_gather_matmul":
    # Ring moves (n-1) local shards past each device; the matmul is the
    # full gathered product.
    wire_bytes = (n - 1) * m * k * dtype_bytes
    flops = 2.0 * (n * m) * k * n_out
  elif kind == "matmul_reduce_scatter":
    # Ring moves (n-1) accumulator blocks of [m/n, n_out].
    wire_bytes = (n - 1) * (m / n) * n_out * dtype_bytes
    flops = 2.0 * m * k * n_out
  else:  # reduce_scatter
    wire_bytes = (n - 1) * (m / n) * k * dtype_bytes
    # No adjacent matmul: what the ring hides is its neighbours' adds —
    # model the hideable compute as the local add stream.
    flops = float(m * k)

  if measured_collective_bytes is not None and measured_collective_bytes > 0:
    # Evidence wins over the analytic derivation (docstring).
    wire_bytes = float(measured_collective_bytes)

  comm_us = wire_bytes / link_bytes_per_s * 1e6
  matmul_us = flops / peak_flops * 1e6
  fused_us = comm_us + matmul_us

  if num_chunks > 1:
    ks = [k_ for k_ in _divisors_desc(n) if k_ <= num_chunks] or [n]
    ks = ks[:1]
  else:
    ks = _divisors_desc(n)
  best_k, best_t = 1, float("inf")
  for K in ks:
    t = (max(comm_us, matmul_us) + min(comm_us, matmul_us) / K
         + (n - 1) * step_latency_us + K * chunk_overhead_us)
    if t < best_t:
      best_k, best_t = K, t
  enabled = best_t < fused_us
  return OverlapDecision(enabled, best_k if enabled else 1,
                         fused_us, best_t, comm_us, matmul_us)


def plan_collective_matmul_from_cost(fn: Callable, *sample_args,
                                     kind: str, axis_size: int,
                                     **model_kwargs) -> OverlapDecision:
  """Crossover decision fed by the XLA cost model instead of analytic
  dims: lowers ``fn(*sample_args)`` (the LOCAL per-device matmul), reads
  its flops from ``Compiled.cost_analysis()``, and scores the same
  T_fused / T_overlap(K) model.  This is the profiled-cost twin of
  :func:`plan_collective_matmul`, the same relationship
  ``search_from_cost_model`` has to ``search``."""
  from easyparallellibrary_tpu.profiler.flops import compiled_cost
  cost = compiled_cost(fn, *sample_args)
  flops = float(cost.get("flops", 0.0)) or 1.0
  bytes_out = float(cost.get("bytes accessed", 0.0))
  peak = model_kwargs.pop("peak_flops", None) or _model_peak_flops()
  # Back out effective dims for the analytic model: treat the measured
  # flops as one [m, k] @ [k, n_out] with the caller's k/n_out hints, or
  # fall back to a square split.
  k_hint = model_kwargs.pop("k", None)
  n_hint = model_kwargs.pop("n_out", None)
  if k_hint and n_hint:
    m = max(int(flops / (2.0 * k_hint * n_hint)), 1)
    k_dim, n_dim = k_hint, n_hint
  else:
    side = max(int(round((flops / 2.0) ** (1.0 / 3.0))), 1)
    m = k_dim = n_dim = side
  del bytes_out  # bytes-accessed includes HBM traffic; wire bytes are
  # derived from the dims like the analytic path, so both paths rank
  # sites identically.
  if kind == "all_gather_matmul":
    m = max(m // max(axis_size, 1), 1)  # cost fn saw the gathered rows
  return plan_collective_matmul(kind, m=m, k=k_dim, n_out=n_dim,
                                axis_size=axis_size, peak_flops=peak,
                                **model_kwargs)
