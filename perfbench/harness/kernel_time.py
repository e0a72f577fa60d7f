"""Device time of one named kernel per step of the serving loop.

A Mosaic kernel's name (``pallas_call(name=)``) is the name of its custom
call on the device trace's ``XLA Ops`` line, and ``xplane.reduce`` sums the
calls of each name inside the traced window (``custom_calls``).  Steps in
that window: the time the loop spent stepping over the median step period
(``loop_spans.step_periods_ms``).  The stepping time is the window less the
device idle that lies under no host span: the benchmark closes a traced
serving window with its own ``tracer.events()`` (PERF.md section 7), a
sixth of a second in which no step runs.  ``None`` where the program has no
kernel of that name (a parent commit, or a step that fell back to another
lowering) or records no ``serving/dispatch`` span.
"""

from __future__ import annotations

from perfbench.harness import loop_spans, stats
from perfbench.harness.result import say

NO_SPAN = "(no host span)"
KV_WRITE = "kv_write"


def ms_per_step(ctx, kernel: str):
  block = ctx.get("trace")
  periods = loop_spans.step_periods_ms(ctx.get("spans", ()))
  if not block or not periods:
    return None
  calls, seconds = block.get("custom_calls", {}).get(kernel, (0.0, 0.0))
  if calls <= 0 or seconds <= 0:
    return None
  closing = sum(s for name, s in block.get("idle_gaps", ()) if name == NO_SPAN)
  steps = (block["window_s"] - closing) * 1e3 / stats.median(periods)
  if steps <= 0:
    return None
  say(f"{kernel}: {calls:.0f} calls of {1e6 * seconds / calls:.1f} us in "
      f"{steps:.2f} steps ({calls / steps:.1f} a step; the window's "
      f"{block['window_s']:.3f} s less {closing:.3f} s in no host span)")
  return 1e3 * seconds / steps


def kv_write_ms(ctx):
  """Reader of ``engine.kv_write_ms.*``: the in-place append of each
  slot's K/V window, one ``kv_write`` call a layer
  (``kernels/kv_write.py``).  Absent where the step was built with the
  reference write: the engine's trace metadata ``serving/kv_write_impl``
  says which."""
  return ms_per_step(ctx, KV_WRITE)
