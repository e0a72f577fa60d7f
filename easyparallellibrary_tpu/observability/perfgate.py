"""Perf regression gate: cost-card invariants of the compiled twins,
pinned in ``perf_budget.json`` (``make perf-gate``).

A checked-in budget file pins, per compiled twin (the deterministic
tiny reference geometry :func:`collect_cards` builds), bounds on the
numbers XLA itself reports at warmup via the device introspector
(observability/device.py): ``compile_count`` (the compile-once contract
as a number), ``flops_per_token``, ``kv_bytes_per_request``, the static
``peak_hbm_bytes`` plan, and ``donation_verified``.  These are COMPILER
facts, not wall clocks — they are bit-stable on a noisy 1-core box,
which is exactly why they gate where timing cannot.  The gate collects
them afresh from the tree as it is; speed is the benchmark's business
(``perfbench/``, ``PERF.md``).

Budget entry forms (``perf_budget.json``)::

    {"version": 1,
     "cost_cards": {
       "<twin label>": {"<metric>": {"max": 1.0}            # <= bound
                        | {"min": 1.0}                      # >= bound
                        | {"max": ..., "min": ...}}}}

Bounds are written pre-inflated (``--write-budget`` applies the
per-metric tolerances below to the measured values), so the check
itself is a plain comparison.  Exit status is CI-shaped: 0 clean, 1 on
any violation, with one ``path: got vs bound`` line each.

Run: ``python -m easyparallellibrary_tpu.observability.perfgate``
(``make perf-gate``; ``make gate`` chains epl-lint first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from easyparallellibrary_tpu.utils.logging import get_logger

# Tolerance applied per cost-card metric when GENERATING a budget from
# measured cards (--write-budget): the bound ships pre-inflated so the
# gate is a plain compare.  compile_count and donation_verified are
# exact — a second compile or a lost alias IS the regression.
_CARD_TOLERANCE = {
    "compile_count": 0.0,
    "donation_verified": 0.0,
    "flops_per_token": 0.10,
    "flops": 0.10,
    "kv_bytes_per_request": 0.10,
    "peak_hbm_bytes": 0.25,
}
# Metrics the generated budget pins per twin (when the card carries
# them); max-bounded except donation_verified, which is min-bounded.
_CARD_PINNED = ("compile_count", "flops_per_token", "flops",
                "kv_bytes_per_request", "peak_hbm_bytes")

_DEFAULT_BUDGET = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "perf_budget.json")


def default_budget_path() -> str:
  return os.environ.get("EPL_PERF_BUDGET", _DEFAULT_BUDGET)


def load_budget(path: Optional[str] = None) -> Dict[str, Any]:
  path = path or default_budget_path()
  with open(path, encoding="utf-8") as f:
    doc = json.load(f)
  if not isinstance(doc, dict):
    raise ValueError(f"perf budget {path!r} is not a JSON object")
  return doc


# ------------------------------------------------------ card collection


def collect_cards(twins: Tuple[str, ...] = ("plain", "guarded", "paged")
                  ) -> Dict[str, Dict[str, float]]:
  """Capture cost cards for the canonical reference twins on THIS
  backend: deterministic ``testing.factories.tiny_gpt`` engines, each
  serving one seeded request so warmup capture fires.  Returns
  ``{twin label: flat metrics dict}`` — the measured side the budget's
  ``cost_cards`` section compares against.

  The geometry is pinned (it IS the budget's reference program): any
  change here invalidates the checked-in budget and must regenerate it
  (``--write-budget``)."""
  import numpy as np

  from easyparallellibrary_tpu.observability import device as device_lib
  from easyparallellibrary_tpu.serving import (
      ContinuousBatchingEngine, Request)
  from easyparallellibrary_tpu.testing.factories import tiny_gpt

  previous = device_lib.get_introspector()
  intro = device_lib.install(device_lib.DeviceIntrospector())
  try:
    model, params = tiny_gpt()
    variants = {
        "plain": dict(resilience=False, track_prefix="serving"),
        "guarded": dict(resilience=True,
                        track_prefix="serving/guarded"),
        "paged": dict(resilience=False, paged=True, block_size=8,
                      track_prefix="serving/paged"),
    }
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 64, (5,)).astype(np.int32)
    for name in twins:
      kw = variants[name]
      eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                     prefill_chunk=4, speculative=False,
                                     **kw)
      try:
        eng.submit(Request(uid=f"gate-{name}", prompt=prompt,
                           max_new_tokens=3))
        eng.run()
      finally:
        eng.close()
    return {label: card.metrics()
            for label, card in sorted(intro.cards.items())}
  finally:
    device_lib.install(previous)


# ------------------------------------------------------------ checking


def _check_bound(path: str, value: Any, bound: Dict[str, Any]
                 ) -> List[str]:
  if isinstance(value, bool):
    value = float(value)
  if not isinstance(value, (int, float)):
    return [f"{path}: measured value {value!r} is not numeric"]
  errs = []
  if "max" in bound and value > bound["max"]:
    errs.append(f"{path}: {value:g} exceeds budget max {bound['max']:g}")
  if "min" in bound and value < bound["min"]:
    errs.append(f"{path}: {value:g} below budget min {bound['min']:g}")
  return errs


def check_cost_cards(budget: Dict[str, Any],
                     cards: Dict[str, Dict[str, float]]) -> List[str]:
  """Violations of the budget's ``cost_cards`` section against measured
  cards.  A budgeted twin or metric that was NOT measured is a
  violation — a gate that cannot see a pinned number has not passed
  it."""
  errs: List[str] = []
  for label, pins in (budget.get("cost_cards") or {}).items():
    card = cards.get(label)
    if card is None:
      errs.append(f"cost_cards[{label}]: twin not captured "
                  f"(collection geometry changed?)")
      continue
    for metric, bound in pins.items():
      if metric not in card:
        errs.append(f"cost_cards[{label}].{metric}: metric missing "
                    f"from the captured card")
        continue
      errs.extend(_check_bound(f"cost_cards[{label}].{metric}",
                               card[metric], bound))
  return errs


def run_gate(budget_path: Optional[str] = None,
             cards: Optional[Dict[str, Dict[str, float]]] = None
             ) -> List[str]:
  """The whole gate: load the budget, collect (or accept) measured
  cards, check them.  Returns every violation."""
  budget = load_budget(budget_path)
  if not budget.get("cost_cards"):
    return []
  if cards is None:
    cards = collect_cards()
  return check_cost_cards(budget, cards)


# ----------------------------------------------------------- generation


def generate_budget(cards: Dict[str, Dict[str, float]]
                    ) -> Dict[str, Any]:
  """A budget document pinning ``cards`` with the standard tolerances
  (the ``--write-budget`` path; the checked-in starter budget was
  produced exactly this way)."""
  cost_cards: Dict[str, Any] = {}
  for label, metrics in sorted(cards.items()):
    pins: Dict[str, Any] = {}
    for metric in _CARD_PINNED:
      if metric not in metrics:
        continue
      tol = _CARD_TOLERANCE.get(metric, 0.25)
      bound = metrics[metric] * (1.0 + tol)
      pins[metric] = {"max": round(bound, 4)}
    if metrics.get("donation_verified") is not None:
      pins["donation_verified"] = {"min": metrics["donation_verified"]}
    cost_cards[label] = pins
  return {
      "version": 1,
      "comment": "Perf budget: cost-card invariants enforced by "
                 "`make perf-gate` (observability/perfgate.py).  "
                 "Regenerate with --write-budget ONLY when a perf "
                 "change is intentional, and say why in the PR.",
      "cost_cards": cost_cards,
  }


def main(argv: Optional[List[str]] = None) -> int:
  parser = argparse.ArgumentParser(
      prog="python -m easyparallellibrary_tpu.observability.perfgate",
      description="Perf regression gate over device cost cards "
                  "(perf_budget.json)")
  parser.add_argument("--budget", default=None,
                      help="budget file (default: repo perf_budget.json)")
  parser.add_argument("--write-budget", action="store_true",
                      help="regenerate the budget from freshly "
                           "collected cards (tolerances applied) "
                           "instead of checking")
  args = parser.parse_args(argv)
  budget_path = args.budget or default_budget_path()
  if args.write_budget:
    cards = collect_cards()
    doc = generate_budget(cards)
    with open(budget_path, "w", encoding="utf-8") as f:
      json.dump(doc, f, indent=1, sort_keys=False)
      f.write("\n")
    print(f"perf budget written: {budget_path} "
          f"({len(doc['cost_cards'])} twin(s))")
    return 0
  violations = run_gate(budget_path)
  if violations:
    print(f"perf-gate: {len(violations)} violation(s):")
    for v in violations:
      print(f"  FAIL {v}")
    return 1
  budget = load_budget(budget_path)
  print(f"perf-gate: OK ({len(budget.get('cost_cards') or {})} twin(s))")
  return 0


if __name__ == "__main__":
  get_logger().setLevel("WARNING")
  sys.exit(main())
