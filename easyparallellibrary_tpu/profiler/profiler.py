"""Step profiler — wall-clock, throughput, MFU, pipeline bubble, and
XLA trace capture.

Combines the roles of the reference's cost-model entry points
(epl/profiler/profiler.py:36-60 profile_flops/profile_memory over the
unbuilt graph) with a convenient training-loop hook.  Trace capture
wraps `jax.profiler` (TensorBoard-compatible) — the reference's
RunMetadata FULL_TRACE analog.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax

from easyparallellibrary_tpu.parallel.pipeline import bubble_fraction
from easyparallellibrary_tpu.profiler.flops import (
    compiled_cost, estimate_mfu)
from easyparallellibrary_tpu.profiler.memory import compiled_memory
from easyparallellibrary_tpu.utils.logging import get_logger


def profile_step(fn: Callable, *args, tokens_per_step: Optional[int] = None,
                 num_stages: int = 1, num_micro_batch: int = 1,
                 **kwargs) -> Dict[str, float]:
  """Static profile of a train step: flops, memory plan, expected bubble.

  This is the planner-facing cost model (the reference feeds its static
  profile into auto-GC, epl/runtime/gc/auto_gradient_checkpoint.py:146).
  """
  report = {}
  try:
    report.update({f"cost_{k}": v for k, v in
                   compiled_cost(fn, *args, **kwargs).items()
                   if isinstance(v, (int, float))})
  except Exception as e:  # pragma: no cover
    get_logger().warning("cost analysis unavailable: %s", e)
  try:
    report.update(compiled_memory(fn, *args, **kwargs))
  except Exception as e:  # pragma: no cover
    get_logger().warning("memory analysis unavailable: %s", e)
  if num_stages > 1:
    report["pipeline_bubble"] = bubble_fraction(num_stages, num_micro_batch)
  if tokens_per_step:
    report["tokens_per_step"] = float(tokens_per_step)
  return report


class StepProfiler:
  """Training-loop timing hook with optional XLA trace capture."""

  def __init__(self, flops_per_step: float = 0.0,
               tokens_per_step: int = 0, warmup: int = 2):
    self.flops_per_step = flops_per_step
    self.tokens_per_step = tokens_per_step
    self.warmup = warmup
    self.times = []
    # Resilience counters: fed by fit() (runtime/loop.py) from the
    # sentinel's on-device totals and the transient-IO retry count, so
    # the end-of-run summary reports the health of the run too.
    self.bad_steps = 0
    self.io_retries = 0
    self._last = None
    self._count = 0

  def note_bad_step(self, n: int = 1):
    self.bad_steps += n

  def note_retry(self, n: int = 1):
    self.io_retries += n

  def tick(self):
    now = time.perf_counter()
    self._count += 1
    if self._count > self.warmup and self._last is not None:
      self.times.append(now - self._last)
    self._last = now

  def summary(self) -> Dict[str, float]:
    if not self.times:
      return {}
    dt = sum(self.times) / len(self.times)
    out = {"step_time_s": dt, "steps_per_sec": 1.0 / dt}
    if self.tokens_per_step:
      out["tokens_per_sec"] = self.tokens_per_step / dt
    if self.flops_per_step and jax.default_backend() == "tpu":
      out["mfu"] = estimate_mfu(self.flops_per_step, dt)
    if self.bad_steps:
      out["bad_steps"] = float(self.bad_steps)
    if self.io_retries:
      out["io_retries"] = float(self.io_retries)
    return out

  def publish(self, registry, step: int):
    """Publish :meth:`summary` through a MetricRegistry
    (observability/registry.py): timing under ``train/*``, the health
    counters under ``resilience/*``.  ``fit()`` calls this for the
    auto-built registry at the end of a run."""
    out = self.summary()
    if not out:
      return
    from easyparallellibrary_tpu.observability.registry import (
        split_namespaces)
    registry.publish_many(step, split_namespaces(out))

  def trace(self, log_dir: str):
    """Capture an XLA trace viewable in TensorBoard/Perfetto.

    Delegates to the ambient tracer's :meth:`Tracer.xla_trace`, which
    brackets the capture with a host span when span tracing is enabled
    — the device timeline in ``log_dir`` and the host timeline in the
    exported trace JSON then correlate by wall clock."""
    from easyparallellibrary_tpu.observability import trace as trace_lib
    return trace_lib.get_tracer().xla_trace(log_dir)
