"""Unified tracing & telemetry (docs/observability.md).

One event substrate for the whole runtime:

* :mod:`trace` — ring-buffered host-side span tracer with Chrome /
  Perfetto JSON export, ``jax.profiler`` capture attachment, and a
  cheap ambient ``get_tracer()`` the training loop, checkpoint path,
  resilience layer, and serving stack all record into;
* :mod:`registry` — the namespaced metric schema (``train/*``,
  ``serving/*``, ``comm/*``, ``resilience/*``) feeding the existing
  ``MetricsWriter`` / ``TensorBoardWriter`` sinks;
* :mod:`report` — ``python -m easyparallellibrary_tpu.observability
  .report <trace>`` latency-breakdown summaries, including per-request
  serving timelines (``--follow`` tails a live metrics JSONL);
* :mod:`slo` — declarative SLO rules over the registry records, the
  always-on compile sentinel, and anomaly-triggered diagnostic-bundle
  capture (``observability.slo.*``);
* :mod:`device` — device-truth introspection: compiled-twin cost cards
  (``Compiled.cost_analysis()``/``memory_analysis()`` at warmup),
  per-site measured collective bytes feeding the overlap planner, and
  HBM watermark gauges (``observability.device.*``);
* :mod:`perfgate` — ``make perf-gate``: cost-card invariants pinned
  in ``perf_budget.json``, failing CI-style on regression.

Knobs: the ``observability.*`` config group (enabled / trace_path /
ring_capacity / sample_rate / metrics_jsonl / slo.* / device.*).
"""

from easyparallellibrary_tpu.observability.device import (
    CostCard, DeviceIntrospector, get_introspector,
)
from easyparallellibrary_tpu.observability.registry import (
    NAMESPACES, MetricRegistry, split_namespaces,
)
from easyparallellibrary_tpu.observability.slo import (
    BurnRateRule, CompileSentinel, DiagnosticCapture, SLOMonitor,
    SLORule, get_monitor,
)
from easyparallellibrary_tpu.observability.trace import (
    Tracer, ensure_configured, get_tracer, install, validate_trace,
)

__all__ = [
    "MetricRegistry", "NAMESPACES", "split_namespaces",
    "BurnRateRule", "CompileSentinel", "CostCard", "DeviceIntrospector",
    "DiagnosticCapture", "SLOMonitor", "SLORule", "get_introspector",
    "get_monitor", "Tracer", "ensure_configured", "get_tracer",
    "install", "validate_trace",
]
