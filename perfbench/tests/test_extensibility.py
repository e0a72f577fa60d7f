"""A later PR adds a configuration, a cell, a traffic mix, a runner and a
layer metric as NEW files and NEW manifest entries, editing no file that
is there: shown here in a temporary copy."""

import hashlib
import json
import os

from perfbench.tests import toy_checkout

NEW_RUNNER = '''
"""A runner of a later PR: it reuses nothing but the harness."""
import time
from perfbench.harness import device as device_lib


def run(*, cell, cell_file, config_file, traffic, devices, peaks, seed,
        seconds, trace, t_process_start, control=None):
  import jax.numpy as jnp
  n = cell_file["rows"] * traffic["scale"] * config_file["width"]
  t0 = time.perf_counter()
  total = float(jnp.arange(n).sum())
  out = {"correct": total == n * (n - 1) / 2, "attempted": 1, "failed": 0,
         "end_to_end": {"rows_per_s": n / (time.perf_counter() - t0),
                        "setup_s": t0 - t_process_start},
         "device": device_lib.device_block(devices, 0)}
  if trace:
    out["device"].update(busy_s=1e-3, window_s=1e-2)
    out["layer_ctx"] = {"rows": n}
  return out
'''

NEW_METRIC = '''
def read(ctx):
  return float(ctx["rows"]) if "rows" in ctx else None
'''


def _digest(root):
  out = {}
  for d, _, files in os.walk(root):
    for f in files:
      p = os.path.join(d, f)
      with open(p, "rb") as fh:
        out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
  return out


def test_one_of_each_is_added_without_editing_a_file(tmp_path):
  co = toy_checkout.make(str(tmp_path))
  bench = os.path.join(co, "perfbench")
  before = _digest(bench)

  def add(rel, text):
    path = os.path.join(bench, rel)
    assert not os.path.exists(path)
    with open(path, "w") as f:
      f.write(text)

  add("configs/new-config.json", json.dumps({"width": 3}))
  add("workloads/new-cell.json", json.dumps({"runner": "new-runner",
                                            "rows": 5}))
  add("traffic/new-mix.json", json.dumps({"kind": "rows", "scale": 7}))
  add("runners/new-runner.py", NEW_RUNNER)
  add("layer_metrics/new.rows.py", NEW_METRIC)
  with open(os.path.join(co, "BENCHMARK.json")) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "new-config", "source": "none (test)",
                         "file": "perfbench/configs/new-config.json",
                         "reduced": [], "why": "test"})
  doc["workloads"].append({"name": "new-cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1, "why": "test"})
  doc["end_to_end"].append({"name": "rows_per_s", "unit": "rows/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["new-cell"]})
  doc["per_layer"].append({"name": "new.rows", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "new layer", "moves": "rows_per_s"})
  with open(os.path.join(co, "BENCHMARK.json"), "w") as f:
    json.dump(doc, f)

  for trace, want in (("0", {"rows_per_s", "setup_s"}), ("1", {"new.rows"})):
    r = toy_checkout.run_cell(co, "--workload", "new-cell", "--seed", "1",
                              "--seconds", "1", "--trace", trace)
    assert r.returncode == 0, r.stderr[-2000:]
    line = toy_checkout.last_line(r)
    assert line["correct"] is True and set(line["metrics"]) == want
  if "new.rows" in want:
    assert line["metrics"]["new.rows"]["value"] == 105.0

  after = _digest(bench)
  after = {k: v for k, v in after.items() if "__pycache__" not in k}
  assert {k: after[k] for k in before} == before      # nothing edited
  assert len(after) == len(before) + 5
