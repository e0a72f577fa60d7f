"""What the host was doing during a window: is a slow run the host's?

A one-chip machine shares its host's cores.  ``HostWatch`` reads, around a
window, the machine's CPU ticks (``/proc/stat``: ``steal`` is time the
hypervisor gave to someone else) and this process's own CPU time.  With
``heartbeat_s`` it also runs one thread that sleeps that long over and
over and notes every time it overslept by more than ``late_s``, and the
ticks once a second: a stall of the whole process or machine shows there
even while the main thread waits on the device.  The heartbeat is a
builder's tool (``run.py --diagnose 1``); the benchmark's runs never start
it.  Plain Python, no jax.
"""

from __future__ import annotations

import json
import os
import threading
import time

_FIELDS = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
           "steal")

# Set by ``run.py --diagnose 1``: runners then keep the heartbeat and write
# their per-step series under ``chiprun_out/``.
DIAGNOSE = False


def cpu_ticks() -> dict:
  """The machine's CPU ticks since boot by kind; empty off Linux."""
  try:
    with open("/proc/stat") as f:
      parts = f.readline().split()
  except OSError:
    return {}
  if not parts or parts[0] != "cpu":
    return {}
  return {k: int(v) for k, v in zip(_FIELDS, parts[1:])}


def ticks_delta(a: dict, b: dict) -> dict:
  """Seconds of each kind between two ``cpu_ticks`` readings."""
  hz = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
  return {k: (b[k] - a[k]) / hz for k in a if k in b}


class HostWatch:

  def __init__(self, heartbeat_s: float | None = None,
               late_s: float = 0.02):
    self.heartbeat_s = heartbeat_s
    self.late_s = late_s
    self.late = []          # (seconds since start, overslept seconds)
    self.series = []        # (seconds since start, cpu_ticks) each second
    self._stop = threading.Event()
    self._thread = None

  def start(self) -> "HostWatch":
    self.t0 = time.perf_counter()
    self.cpu0 = time.process_time()
    self.ticks0 = cpu_ticks()
    if self.heartbeat_s:
      self._thread = threading.Thread(target=self._beat, daemon=True)
      self._thread.start()
    return self

  def _beat(self):
    last = time.perf_counter()
    next_series = 0.0
    while not self._stop.is_set():
      time.sleep(self.heartbeat_s)
      now = time.perf_counter()
      over = now - last - self.heartbeat_s
      if over > self.late_s:
        self.late.append((now - self.t0, over))
      if now - self.t0 >= next_series:
        self.series.append((now - self.t0, cpu_ticks()))
        next_series += 1.0
      last = now

  def stop(self) -> dict:
    wall = time.perf_counter() - self.t0
    if self._thread is not None:
      self._stop.set()
      self._thread.join()
    out = {"wall_s": wall,
           "process_cpu_s": time.process_time() - self.cpu0,
           "cpus": os.cpu_count(),
           "machine_s": ticks_delta(self.ticks0, cpu_ticks())}
    if self.heartbeat_s:
      out["heartbeat_late"] = self.late
    return out


class TpuMonitor:
  """Samples every metric ``libtpu.sdk.tpumonitoring`` offers, each
  ``every_s`` seconds, in a thread of its own (diagnosis only; whatever it
  cannot read it leaves out)."""

  def __init__(self, every_s: float = 10.0):
    self.every_s = every_s
    self.samples = []       # (seconds since start, {metric: data})
    self.error = None
    self._stop = threading.Event()
    self._thread = threading.Thread(target=self._run, daemon=True)

  def start(self) -> "TpuMonitor":
    self.t0 = time.perf_counter()
    self._thread.start()
    return self

  def _run(self):
    try:
      from libtpu.sdk import tpumonitoring
      names = list(tpumonitoring.list_supported_metrics())
    except Exception as e:            # no libtpu, or no sdk in it
      self.error = repr(e)
      return
    while not self._stop.wait(self.every_s):
      row = {}
      for n in names:
        try:
          row[n] = list(tpumonitoring.get_metric(n).data())
        except Exception as e:
          row[n] = repr(e)
      self.samples.append((time.perf_counter() - self.t0, row))

  def stop(self) -> dict:
    self._stop.set()
    self._thread.join(timeout=10.0)
    return {"error": self.error, "samples": self.samples}


def summary(report: dict) -> str:
  """One line of a ``stop()`` report."""
  m = report["machine_s"]
  busy = sum(v for k, v in m.items() if k not in ("idle", "iowait"))
  line = (f"host over {report['wall_s']:.1f} s: this process "
          f"{report['process_cpu_s']:.2f} cpu-s; machine "
          f"({report['cpus']} cpus) busy {busy:.2f} s, steal "
          f"{m.get('steal', 0.0):.2f} s, iowait {m.get('iowait', 0.0):.2f} s")
  late = report.get("heartbeat_late")
  if late is not None:
    worst = max((o for _, o in late), default=0.0)
    line += (f"; heartbeat late {len(late)} times, "
             f"{sum(o for _, o in late):.3f} s in all, worst {worst:.3f} s")
  return line


def gap_summary(gaps_ms, slow_share: float = 0.01) -> str:
  """Step-completion gaps: the median, the worst, and how much time the
  gaps over ``1 + slow_share`` of the median lost against it."""
  xs = sorted(gaps_ms)
  if not xs:
    return "no step gaps"
  med = xs[len(xs) // 2]
  slow = [g for g in gaps_ms if g > med * (1.0 + slow_share)]
  lost = sum(g - med for g in slow)
  return (f"step gaps: {len(xs)}, median {med:.3f} ms, min {xs[0]:.3f}, "
          f"max {xs[-1]:.3f}; {len(slow)} over {1 + slow_share:.2f} x median "
          f"lost {lost:.1f} ms together")


def dump(name: str, doc: dict) -> str:
  """``doc`` as ``<checkout>/chiprun_out/<name>.json`` (a directory git
  ignores): the series a line of output cannot hold."""
  from perfbench.harness.manifest import ROOT
  path = os.path.join(ROOT, "chiprun_out", name + ".json")
  os.makedirs(os.path.dirname(path), exist_ok=True)
  with open(path, "w") as f:
    json.dump(doc, f)
  return path
