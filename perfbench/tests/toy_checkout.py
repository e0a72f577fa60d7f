"""A throw-away checkout for perfbench's tests: ``perfbench/`` as it is
plus the toy manifest, configuration, cells and mixes of ``tests/toy``."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def make(dst: str) -> str:
  """Copy ``perfbench/`` to ``dst/perfbench`` and lay the toy files over
  it; returns ``dst``."""
  target = os.path.join(dst, "perfbench")
  shutil.copytree(BENCH, target, ignore=shutil.ignore_patterns(
      "__pycache__", "_scratch", "*.pyc"))
  toy = os.path.join(HERE, "toy")
  shutil.copy(os.path.join(toy, "BENCHMARK.json"), dst)
  for sub in ("configs", "workloads", "traffic", "layer_metrics"):
    src = os.path.join(toy, sub)
    if os.path.isdir(src):
      for name in os.listdir(src):
        shutil.copy(os.path.join(src, name), os.path.join(target, sub))
  return dst


def run_cell(checkout: str, *args: str, allow_cpu: bool = True,
             env: dict | None = None, timeout: int = 600,
             prelude: str = "", entry: str = "run"):
  """``perfbench/<entry>.py`` of ``checkout`` in a process of its own;
  with ``allow_cpu`` the look for a chip is skipped (tests only).
  ``prelude`` is Python run first: a test breaks the timed path there."""
  code = ("import sys; sys.path.insert(0, %r)\n%s\n"
          "from perfbench import %s as entry\n"
          "sys.exit(entry.main(sys.argv[1:], allow_cpu=%r))"
          % (checkout, prelude, entry, allow_cpu))
  full = dict(os.environ, JAX_PLATFORMS="cpu",
              PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
  full.update(env or {})
  return subprocess.run([sys.executable, "-c", code, *args], env=full,
                        capture_output=True, text=True, timeout=timeout,
                        cwd=checkout)


def last_line(proc) -> dict:
  """The result line: the last line of standard output, as JSON."""
  import json
  lines = [l for l in proc.stdout.splitlines() if l.strip()]
  return json.loads(lines[-1])


FAKE_TRACE = """
import json
from perfbench.harness import tracing
class _Fake(tracing.DeviceTrace):
  def start(self):
    import time; self.t0_ns = time.perf_counter_ns()
  def stop(self):
    import time; self.t1_ns = time.perf_counter_ns()
  def reduce(self, host_spans_ns, n_chips):
    from perfbench.harness import xplane
    planes = json.load(open(%r))
    return xplane.reduce(planes, host_spans=[], n_chips=n_chips)
tracing.DeviceTrace = _Fake
"""
