"""Find the knee of an open-loop cell once, on the chip: the same mix at
several arrival rates, one process, the compiled programs reused.

    python3 perfbench/sweep.py --workload <cell> --rates 6 8 10 12 \
        --seconds 30 --seed 7 [--out sweep.json]

Prints one row a rate: tails, the queue's wait in the last third of the
window and its depth when the window closed.  The knee is the highest rate
at which the queue does not grow over the window; the cell's mix then
states 0.8 of it.  The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from perfbench import run as run_lib  # noqa: E402


def main(argv=None, allow_cpu: bool = False) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--workload", required=True)
  parser.add_argument("--rates", type=float, nargs="+", required=True)
  parser.add_argument("--seconds", type=float, default=30.0)
  parser.add_argument("--seed", type=int, default=7)
  parser.add_argument("--out", default=None)
  args = parser.parse_args(argv)

  man, cell, run_cell = run_lib.open_cell(args.workload, allow_cpu)
  rows = []
  for rate in args.rates:
    mix = man.traffic_file(cell["traffic"])      # read anew: ours to change
    mix["arrivals"]["rate_per_s"] = rate
    out = run_cell(traffic=mix, seed=args.seed, seconds=args.seconds,
                   trace=False, t_process_start=time.perf_counter())
    row = {"rate_per_s": rate, "failed": out["failed"],
           "correct": out["correct"], **out["observed"],
           **{k: v for k, v in out["end_to_end"].items() if k != "setup_s"}}
    rows.append(row)
    print("SWEEP " + json.dumps(row), flush=True)
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
      json.dump(rows, f, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())
