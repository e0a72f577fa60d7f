"""The comparisons that decide ``correct``; plain arithmetic."""

from __future__ import annotations

import math
import statistics

from perfbench.harness.result import compared


def worst_leaf_gap(program: dict, reference: dict):
  """The widest gap between the program's norm of a leaf and the
  reference's, measured against the reference's norm of that leaf or of
  the median leaf, whichever is larger (some gradients are all but
  zero).  Returns ``(gap, leaf)``."""
  if set(program) != set(reference):
    raise KeyError("leaves differ: "
                   f"{sorted(set(program) ^ set(reference))[:8]}")
  floor = statistics.median(reference.values())
  worst, at = -1.0, None
  for name, ref in reference.items():
    got = program[name]
    gap = (abs(got - ref) / max(ref, floor)
           if math.isfinite(got) else math.inf)
    if gap > worst:
      worst, at = gap, name
  return worst, at


class Verdict:
  """Collects every number compared; ``correct`` is their conjunction."""

  def __init__(self):
    self.correct = True
    self.numbers = {}

  def at_most(self, name: str, value: float, limit: float) -> None:
    ok = math.isfinite(value) and value <= limit
    self.numbers[name] = value
    compared(name, value, limit, ok)
    self.correct = self.correct and ok

  def require(self, name: str, ok: bool, detail: str = "") -> None:
    compared(name + (f" [{detail}]" if detail else ""), 0.0 if ok else 1.0,
             0.0, ok)
    self.numbers[name] = 0.0 if ok else 1.0
    self.correct = self.correct and bool(ok)
