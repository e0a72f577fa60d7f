"""perfbench entry point.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration, its sizes
(``perfbench/workloads/<cell>.json``) and its traffic mix
(``perfbench/traffic/<mix>.json``) by name, hands them to the runner the
cell names (``perfbench/runners/<runner>.py``) and prints the runner's
measurements as one JSON line, the last of standard output.  With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, each read by
``perfbench/layer_metrics/<metric>.py``.

This file and ``perfbench/harness/`` hold no cell's, configuration's, mix's
or metric's name.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys
import time

T_PROCESS_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from perfbench.harness import device as device_lib  # noqa: E402
from perfbench.harness import hostwatch  # noqa: E402
from perfbench.harness import manifest as manifest_lib  # noqa: E402
from perfbench.harness import result as result_lib  # noqa: E402


def load_module(kind: str, name: str):
  """``perfbench/<kind>/<name>.py`` as a module, found by name."""
  manifest_lib.check_name(name, kind)
  path = os.path.join(ROOT, "perfbench", kind, name + ".py")
  if not os.path.exists(path):
    raise manifest_lib.ManifestError(f"no {path}")
  spec = importlib.util.spec_from_file_location(
      f"perfbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def read_layer_metrics(man, cell: str, ctx: dict) -> dict:
  """Every per-layer metric of the cell through its own reader; a reader
  that finds nothing to read returns None and the metric is left out."""
  out = {}
  for m in man.metrics_for(cell, "per_layer"):
    value = load_module("layer_metrics", m["name"]).read(ctx)
    if value is not None:
      out[m["name"]] = value
  return out


def open_cell(workload: str, allow_cpu: bool = False):
  """``(manifest, cell, run_cell)``: the cell's files loaded, the compile
  cache set, the chips required; ``run_cell(seed=..., seconds=..., trace=...,
  t_process_start=..., traffic=None, control=None)`` is one run of the
  cell's runner (``traffic`` overrides the mix: ``sweep.py``)."""
  man = manifest_lib.Manifest(ROOT)
  cell = man.workload(workload)
  cell_file = man.cell_file(cell["name"])
  runner = load_module("runners", cell_file["runner"])
  # Only now touch jax: the manifest errors above cost no chip.
  if not allow_cpu:
    result_lib.say(f"compile cache {device_lib.configure_compile_cache()}")
  devices, peaks = device_lib.require_chips(cell["chips"], allow_cpu)

  def run_cell(*, traffic=None, **kw):
    return runner.run(
        cell=cell, cell_file=cell_file,
        config_file=man.config_file(cell["config"]),
        traffic=traffic or man.traffic_file(cell["traffic"]),
        devices=devices, peaks=peaks, **kw)

  return man, cell, run_cell


def main(argv=None, allow_cpu: bool = False) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seed", type=int, required=True)
  parser.add_argument("--seconds", type=float, required=True)
  parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
  parser.add_argument("--diagnose", type=int, choices=(0, 1), default=0,
                      help="builder's tool: a heartbeat thread and the "
                      "per-step series under chiprun_out/ (harness/"
                      "hostwatch.py); the driver never sets it")
  args = parser.parse_args(argv)
  hostwatch.DIAGNOSE = bool(args.diagnose)

  man, cell, run_cell = open_cell(args.workload, allow_cpu)
  run = run_cell(seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), t_process_start=T_PROCESS_START)
  cell_file = man.cell_file(cell["name"])

  units = {m["name"]: m["unit"] for m in man.doc["end_to_end"]}
  units.update({m["name"]: m["unit"] for m in man.doc["per_layer"]})
  if args.trace:
    metrics = read_layer_metrics(man, cell["name"], run["layer_ctx"])
  else:
    wanted = [m["name"] for m in man.metrics_for(cell["name"], "end_to_end")]
    missing = [n for n in wanted if n not in run["end_to_end"]]
    if missing:
      raise SystemExit(f"runner {cell_file['runner']} did not measure "
                       f"{missing}")
    metrics = {n: run["end_to_end"][n] for n in wanted}
  print(result_lib.result_line(
      correct=run["correct"], attempted=run["attempted"],
      failed=run["failed"], metrics=metrics, units=units,
      device=run["device"], breakdown=run.get("breakdown")), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
