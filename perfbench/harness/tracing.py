"""A traced window: the profiler's device trace for a few seconds, with
the host's spans on the same clock.

``DeviceTrace`` starts ``jax.profiler`` (Python tracing off: it slows the
host and floods the file) and writes one anchor annotation whose start
is also read from ``time.perf_counter_ns``; the difference puts any
``perf_counter`` span onto the trace's clock.  The raw trace goes to
``<checkout>/.perfbench_tmp`` and is removed once reduced.
"""

from __future__ import annotations

import os
import shutil
import time

from perfbench.harness import xplane
from perfbench.harness.manifest import ROOT

ANCHOR = "perfbench/anchor"
SETTLE_NS = 0.25e9


class DeviceTrace:

  def __init__(self, tag: str):
    self.dir = os.path.join(ROOT, ".perfbench_tmp", f"trace-{tag}")
    self.anchor_ns = None
    self.t0_ns = self.t1_ns = None

  def start(self) -> None:
    import jax
    shutil.rmtree(self.dir, ignore_errors=True)
    os.makedirs(self.dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(self.dir, profiler_options=opts)
    self.anchor_ns = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(ANCHOR):
      time.sleep(0.001)
    self.t0_ns = time.perf_counter_ns()

  def stop(self) -> None:
    import jax
    self.t1_ns = time.perf_counter_ns()
    jax.profiler.stop_trace()

  def reduce(self, host_spans_ns, n_chips: int) -> dict:
    """The device block and breakdown; ``host_spans_ns`` is ``[(name,
    start, end)]`` in ``perf_counter_ns`` time."""
    planes = xplane.load(xplane.find_xplane(self.dir))
    shutil.rmtree(self.dir, ignore_errors=True)
    dump = os.environ.get("PERFBENCH_DUMP_PLANES")
    if dump:                       # records a fixture for perfbench/tests
      xplane.dump_slice(planes, dump)
    anchors = xplane.host_annotations(planes, ANCHOR)
    if not anchors:
      raise RuntimeError("the trace holds no anchor annotation")
    shift = anchors[0][1] - self.anchor_ns
    spans = [(n, s + shift, e + shift) for n, s, e in host_spans_ns]
    # The profiler's own start-up leaves the device waiting for a moment
    # right after ``start``; the reduced window begins once that is over.
    window = (self.t0_ns + shift + SETTLE_NS, self.t1_ns + shift)
    return xplane.reduce(planes, window=window, host_spans=spans,
                         n_chips=n_chips)
