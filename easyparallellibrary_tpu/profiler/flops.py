"""FLOPs profiling and MFU accounting.

Analog of the reference's FLOPs profiler (epl/profiler/flops.py): the
reference registers custom FLOPs formulas for TF ops missing statistics
(:34-117) and reads RunMetadata traces (:120-158).  On TPU, XLA itself is
the cost model: `Compiled.cost_analysis()` reports the flops of the
*optimized* program, so no per-op registry is needed; the hook reports
GFLOPs/step and model FLOPs utilization against the chip's peak.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax

from easyparallellibrary_tpu.utils.logging import get_logger

# Peak bf16 FLOP/s per chip by ``device_kind`` prefix (public TPU specs;
# a v5e chip reports "TPU v5 lite").
PEAK_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}


def peak_flops_info(device: Optional[jax.Device] = None
                    ) -> "tuple[float, str]":
  """(peak bf16 FLOP/s, table key) for the device kind.  The single
  source of truth for every MFU denominator in the package (the tables
  must not fork and drift).  A device kind the table
  does not know raises: an MFU against a guessed peak is not an MFU."""
  device = device or jax.devices()[0]
  kind = device.device_kind
  for name, flops in sorted(PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])):
    if kind.startswith(name):
      return flops, name
  raise ValueError(
      f"no peak FLOP/s on record for device kind {kind!r} (platform "
      f"{device.platform!r}); add it to profiler.flops.PEAK_FLOPS with "
      f"its source before reporting utilization on it")


def peak_flops_per_chip(device: Optional[jax.Device] = None) -> float:
  return peak_flops_info(device)[0]


def compiled_cost(fn: Callable, *args, **kwargs) -> Dict[str, float]:
  """XLA cost analysis of `fn(*args)`: flops, bytes accessed, etc."""
  lowered = jax.jit(fn).lower(*args, **kwargs)
  compiled = lowered.compile()
  cost = compiled.cost_analysis()
  if isinstance(cost, list):  # some backends return a per-computation list
    cost = cost[0] if cost else {}
  return dict(cost or {})


# StableHLO collective ops whose result bytes count as wire traffic.
_COLLECTIVE_OPS = ("all_gather", "all_reduce", "reduce_scatter",
                   "collective_permute", "all_to_all",
                   "collective_broadcast")
_TENSOR_RE = None  # compiled lazily (keeps `re` out of the hot import)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
                "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2,
                "ui16": 2, "i8": 1, "ui8": 1, "i1": 1}


def collective_op_sizes(text: str) -> "list[tuple[str, float]]":
  """``(op_kind, result_bytes)`` for every collective op in a StableHLO
  program text, in program order — the per-op split behind
  :func:`collective_bytes`, and the raw material the device
  introspector's per-SITE attribution works from
  (observability/device.py): one entry per all_gather / all_reduce /
  reduce_scatter / collective_permute / all_to_all, sized by its result
  tensor type."""
  import re
  global _TENSOR_RE
  if _TENSOR_RE is None:
    _TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z]+[0-9]+)>")

  def result_bytes(tail: str) -> float:
    sub = 0.0
    for dims, dtype in _TENSOR_RE.findall(tail):
      elems = 1
      for d in dims.split("x"):
        if d:
          elems *= int(d)
      sub += elems * _DTYPE_BYTES.get(dtype, 4)
    return sub

  out: "list[tuple[str, float]]" = []
  awaiting: Optional[str] = None
  for line in text.splitlines():
    if awaiting is not None:
      # Region-bearing collectives (all_reduce/reduce_scatter carry a
      # reduction body) print their type signature on the CLOSING
      # `}) : (...) -> ...` line, not the op line — count it there and
      # ignore the body lines in between.
      if "})" in line and "->" in line:
        out.append((awaiting, result_bytes(line.rsplit("->", 1)[-1])))
        awaiting = None
      continue
    hit = next((op for op in _COLLECTIVE_OPS
                if f"stablehlo.{op}" in line or f'"{op}"' in line), None)
    if hit is None:
      continue
    if "->" in line:
      # Inline form: result type follows the last `->`.  (Attribute
      # tensors like replica_groups sit BEFORE the arrow and are not
      # counted.)
      out.append((hit, result_bytes(line.rsplit("->", 1)[-1])))
    else:
      awaiting = hit
  return out


def collective_bytes(fn: Callable, *args, **kwargs) -> float:
  """Bytes produced by collective ops in the lowered program of
  ``fn(*args)`` — the comm-traffic counter feeding the profiler's
  comm-share line.  Counted from the StableHLO text (result tensor types
  of all_gather / all_reduce / reduce_scatter / collective_permute /
  all_to_all), the same program the XLA cost model scores, so the flops
  and comm numbers describe one artifact."""
  text = jax.jit(fn).lower(*args, **kwargs).as_text()
  return float(sum(b for _op, b in collective_op_sizes(text)))


def estimate_mfu(flops_per_step: float, step_time_s: float,
                 n_chips: Optional[int] = None) -> float:
  """Achieved over peak FLOP/s; raises on a device without a peak on
  record (:func:`peak_flops_info`) — the profilers report ``mfu`` only
  on TPU, a CPU run has none."""
  n_chips = n_chips or len(jax.devices())
  achieved = flops_per_step / max(step_time_s, 1e-12)
  return achieved / (peak_flops_per_chip() * n_chips)


class FlopsProfiler:
  """Per-step GFLOPs/MFU reporter (reference FlopsProfilerHook,
  epl/profiler/flops.py:120-158: capture once, then log per scope)."""

  def __init__(self, flops_per_step: Optional[float] = None,
               every_n_steps: int = 100,
               comm_bytes_per_step: Optional[float] = None,
               link_bytes_per_s: Optional[float] = None,
               registry=None):
    # Optional MetricRegistry (observability/registry.py): each periodic
    # stats line also publishes under the namespaced schema — timing/MFU
    # as train/*, the collective-traffic counters as comm/*, and the
    # health counters as resilience/*.
    self.registry = registry
    self.flops_per_step = flops_per_step
    self.every_n_steps = every_n_steps
    # Collective-traffic counters for the comm-share line: what fraction
    # of the step the wire would need at `link_bytes_per_s` — the
    # quantity the overlap crossover (parallel/planner.py:
    # plan_collective_matmul) trades against MXU time.  > ~1/2 means the
    # step is communication-bound and latency-hiding collectives
    # (communication.overlap) have headroom to claim.
    self.comm_bytes_per_step = comm_bytes_per_step
    if link_bytes_per_s is None:
      from easyparallellibrary_tpu.parallel.planner import (
          DEFAULT_ICI_BYTES_PER_S)
      link_bytes_per_s = DEFAULT_ICI_BYTES_PER_S
    self.link_bytes_per_s = link_bytes_per_s
    # Resilience counters (runtime/resilience.py): callers feed skipped
    # non-finite steps and transient-IO retries here so the periodic
    # stats line carries the health of the run, not just its speed.
    self.bad_steps = 0
    self.io_retries = 0
    self._t0 = None
    self._step0 = 0
    self._step = 0

  def note_bad_step(self, n: int = 1):
    """Count `n` anomaly-skipped steps into the next stats line."""
    self.bad_steps += n

  def note_retry(self, n: int = 1):
    """Count `n` transient-IO retries into the next stats line."""
    self.io_retries += n

  def measure_from(self, fn: Callable, *args, **kwargs):
    """Fill flops_per_step (and the comm counter) from XLA's cost model
    and the lowered program."""
    cost = compiled_cost(fn, *args, **kwargs)
    self.flops_per_step = float(cost.get("flops", 0.0))
    try:
      self.comm_bytes_per_step = collective_bytes(fn, *args, **kwargs)
    except Exception:  # comm counter is best-effort; flops must survive
      self.comm_bytes_per_step = None
    return self.flops_per_step

  def step(self) -> Optional[Dict[str, float]]:
    """Call once per training step; returns stats every n steps."""
    now = time.perf_counter()
    self._step += 1
    if self._t0 is None:
      self._t0 = now
      self._step0 = self._step
      return None
    if (self._step - self._step0) % self.every_n_steps != 0:
      return None
    dt = (now - self._t0) / (self._step - self._step0)
    self._t0, self._step0 = now, self._step
    stats = {"step_time_s": dt, "steps_per_sec": 1.0 / dt}
    if self.flops_per_step:
      stats["gflops_per_step"] = self.flops_per_step / 1e9
      if jax.default_backend() == "tpu":
        stats["mfu"] = estimate_mfu(self.flops_per_step, dt)
    if self.comm_bytes_per_step:
      stats["comm_gb_per_step"] = self.comm_bytes_per_step / 1e9
      # Wire-time share of the step at the modeled link bandwidth; the
      # overlap policy's headroom indicator.
      stats["comm_share"] = min(
          self.comm_bytes_per_step / self.link_bytes_per_s / dt, 1.0)
    if self.bad_steps:
      stats["bad_steps"] = float(self.bad_steps)
    if self.io_retries:
      stats["io_retries"] = float(self.io_retries)
    get_logger().info("flops profiler: %s", stats)
    if self.registry is not None:
      from easyparallellibrary_tpu.observability.registry import (
          split_namespaces)
      self.registry.publish_many(self._step, split_namespaces(stats))
    return stats
