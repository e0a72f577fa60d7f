"""Arithmetic on the program's spans: ``[(name, start_ns, end_ns)]``."""

from __future__ import annotations

from perfbench.harness import stats


def durations_ms(spans, name: str):
  return [(e - s) / 1e6 for n, s, e in spans if n == name]


def median_ms(spans, name: str):
  d = durations_ms(spans, name)
  return stats.median(d) if d else None


def median_sum_ms(spans, names):
  """Median over steps of the summed durations of ``names``: the i-th
  span of each name belongs to the i-th step."""
  cols = [durations_ms(spans, n) for n in names]
  n = min(len(c) for c in cols)
  if n == 0:
    return None
  return stats.median([sum(c[i] for c in cols) for i in range(n)])


def sched_host_ms(ctx):
  """Reader of ``sched.host_ms.*``: host work of the scheduler per engine
  step, the median over the window's steps of the program's
  ``serving/plan`` + ``serving/commit`` spans."""
  if "spans" not in ctx:
    return None
  return median_sum_ms(ctx["spans"], ("serving/plan", "serving/commit"))


def engine_step_ms(ctx):
  """Reader of ``engine.step_ms.*``: median of the program's
  ``serving/device_step`` span, the dispatch of the fused step plus the
  blocking token fetch (host clock, not device time)."""
  if "spans" not in ctx:
    return None
  return median_ms(ctx["spans"], "serving/device_step")
