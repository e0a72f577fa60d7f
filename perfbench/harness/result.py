"""The last line a run prints, and the earlier lines beside it."""

from __future__ import annotations

import json


def say(msg: str) -> None:
  print(msg, flush=True)


def compared(name: str, value: float, limit: float, ok: bool) -> None:
  """One number of the correctness comparison beside its limit."""
  say(f"correct? {name}: {value:.6g} (limit {limit:.6g}) "
      f"{'ok' if ok else 'NOT CORRECT'}")


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, units: dict, device: dict,
                breakdown: dict | None = None) -> str:
  """One JSON object: ``metrics`` is ``{name: value}``; every value goes
  out as measured, with all its digits."""
  doc = {
      "correct": bool(correct),
      "attempted": int(attempted),
      "failed": int(failed),
      "metrics": {k: {"value": float(v), "unit": units[k]}
                  for k, v in metrics.items()},
      "device": device,
  }
  if breakdown is not None:
    doc["breakdown"] = breakdown
  return json.dumps(doc)
