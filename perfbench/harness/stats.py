"""Percentiles, spreads and the measured set: plain arithmetic, no jax."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
  """The ``q``-th percentile (0..100) by linear interpolation between
  closest ranks (numpy's default rule), on a copy."""
  xs = sorted(float(v) for v in values)
  if not xs:
    raise ValueError("percentile of no values")
  if len(xs) == 1:
    return xs[0]
  k = (len(xs) - 1) * q / 100.0
  lo = math.floor(k)
  hi = min(lo + 1, len(xs) - 1)
  return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
  return percentile(values, 50.0)


def quartile_spread(values) -> float:
  """Distance between the first and third quartile as a share of the
  median, with ``statistics.quantiles(values, n=4)`` as the driver
  takes them."""
  q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
  return (q3 - q1) / abs(statistics.median(values))


def measured_set(due_s, window_s: float, drain_margin_s: float):
  """Indices of the requests whose due time (seconds from the window's
  start) lies in ``[0, window_s - drain_margin_s)``: they are the ones
  timed, and each has to finish before the window closes."""
  end = window_s - drain_margin_s
  if end <= 0:
    raise ValueError(f"window {window_s} s does not cover the drain "
                     f"margin {drain_margin_s} s")
  return [i for i, t in enumerate(due_s) if 0.0 <= t < end]


def gaps(stamps):
  """Differences between successive time stamps of one request."""
  return [b - a for a, b in zip(stamps, stamps[1:])]
