"""Read the numbers a cell's ``correct`` compares, over many seeds in one
process, for the sound program and for the control.

    python3 perfbench/control.py --workload <cell> --seeds 11 12 13 \
        --seconds 5 --control int8 [--out readings.json]

Each seed is one whole run of the cell's runner (new weights, new inputs,
the compiled programs reused), with a short window.  ``--control`` names
the lower precision in which the reference is also computed, in the
program's place; its numbers are printed beside the program's.  The limits
in a cell's file are set from these two readings (PERF.md says how).  The
benchmark's own runs never call this.
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
  parser.add_argument("--seeds", type=int, nargs="+", required=True)
  parser.add_argument("--seconds", type=float, default=5.0)
  parser.add_argument("--control", default=None,
                      help="fp8, int8, bfloat16 or a comma list: also "
                           "compute the control(s)")
  parser.add_argument("--control-seeds", type=int, default=3,
                      help="how many of the seeds also run the control")
  parser.add_argument("--out", default=None)
  args = parser.parse_args(argv)

  _, _, run_cell = run_lib.open_cell(args.workload, allow_cpu)
  rows = []
  for i, seed in enumerate(args.seeds):
    out = run_cell(seed=seed, seconds=args.seconds, trace=False,
                   t_process_start=time.perf_counter(),
                   control=args.control if i < args.control_seeds else None)
    rows.append({"seed": seed, "correct": out["correct"],
                 "numbers": out["numbers"],
                 "control_numbers": out.get("control_numbers"),
                 "end_to_end": out["end_to_end"]})
    print("READING " + json.dumps(rows[-1]), flush=True)
  summary = {}
  for n in sorted(rows[0]["numbers"]):
    sound = [r["numbers"][n] for r in rows]
    summary[n] = {"sound_max": max(sound), "sound_all": sound,
                  "control_min": {}, "control_all": {}}
    for precision in (args.control.split(",") if args.control else ()):
      ctrl = [r["control_numbers"][f"{precision}:{n}"] for r in rows
              if r["control_numbers"]
              and f"{precision}:{n}" in r["control_numbers"]]
      if ctrl:
        summary[n]["control_min"][precision] = min(ctrl)
        summary[n]["control_all"][precision] = ctrl
  print("SUMMARY " + json.dumps(summary), flush=True)
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
      json.dump({"rows": rows, "summary": summary}, f, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())
