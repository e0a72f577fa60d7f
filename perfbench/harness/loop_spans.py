"""Arithmetic on the spans of the serving loop: ``[(name, start_ns,
end_ns)]``, every ``serving/*`` span the engine recorded in the window.

The engine tiles its ``serving/device_step`` into ``serving/dispatch``
(the host hands over the step's arguments and launches the program; the
device has nothing to run meanwhile) and ``serving/fetch`` (the device's
run and the way back).  One ``serving/dispatch`` starts per step, so the
gaps between successive starts are the loop's step periods.  A program
that records no such span (a parent commit) gives ``None`` everywhere.
"""

from __future__ import annotations

from perfbench.harness import spans as spans_lib
from perfbench.harness import stats, xplane
from perfbench.harness.result import say

DISPATCH = "serving/dispatch"
STALL_FACTOR = 1.5


def step_starts(spans):
  return sorted(s for n, s, _ in spans if n == DISPATCH)


def step_periods_ms(spans):
  """Gaps between successive ``serving/dispatch`` starts."""
  return [g / 1e6 for g in stats.gaps(step_starts(spans))]


def stall_of(periods_ms) -> float:
  """What the periods above ``STALL_FACTOR`` x the median lost: the sum
  of (period - median) over them.  0 in a quiet series."""
  if not periods_ms:
    return 0.0
  med = stats.median(periods_ms)
  return sum(p - med for p in periods_ms if p > STALL_FACTOR * med)


def unspanned_ms(spans):
  """Per step period, the time that lies in no span at all."""
  starts = step_starts(spans)
  out = [0.0] * max(len(starts) - 1, 0)
  if not out:
    return out
  covered = xplane.union((s, e) for _, s, e in spans)
  # A bare stretch ends where a span starts, so none crosses a period's
  # start (a ``serving/dispatch`` starts there): one pass over them.
  i = 0
  for s, e in xplane.subtract([(starts[0], starts[-1])], covered):
    while starts[i + 1] <= s:
      i += 1
    out[i] += (e - s) / 1e6
  return out


def dispatch_ms(ctx):
  """Reader of ``engine.dispatch_ms.*``: median ``serving/dispatch``, the
  host's share of the fused step (host clock)."""
  if "spans" not in ctx:
    return None
  return spans_lib.median_ms(ctx["spans"], DISPATCH)


def stall_ms(ctx):
  """Reader of ``engine.stall_ms.*``: time the window lost to step
  periods above ``STALL_FACTOR`` x the median one.  Tells a run that fell
  into a stall of the loop from one whose every step got slower."""
  periods = step_periods_ms(ctx.get("spans", ()))
  if not periods:
    return None
  bare = unspanned_ms(ctx["spans"])
  say(f"step periods: {len(periods)}, median {stats.median(periods):.3f} "
      f"ms, worst {max(periods):.3f} ms; in no serving span: median "
      f"{stats.median(bare):.3f} ms a period, {sum(bare):.1f} ms in all")
  return stall_of(periods)
