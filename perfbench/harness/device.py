"""The machine a run is on: chips, peaks, memory, the compile cache.

A run that finds no TPU, fewer chips than its cell asks for, or a
``device_kind`` with no peaks on record stops here with a non-zero exit
code and no result line.
"""

from __future__ import annotations

import json
import os
import sys

from perfbench.harness.manifest import ROOT

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class DeviceError(SystemExit):
  """No accelerator, too few chips, or an unknown kind (exit code 3)."""

  def __init__(self, msg: str):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    super().__init__(3)


def configure_compile_cache() -> str:
  """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when the
  machine sets it, else at the fixed ``<checkout>/.jax_cache`` (the path
  is part of the key, so it never moves).  The variable is exported so
  the program's own ``utils.compile_cache.configure`` takes the same
  place.  Every program goes in, however quick it was to compile."""
  import jax
  path = os.environ.get(CACHE_ENV)
  if not path:
    path = os.path.join(ROOT, ".jax_cache")
    os.environ[CACHE_ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  return path


def load_peaks(device_kind: str, path: str | None = None) -> dict:
  """The peaks of ``device_kind``; an unknown kind is an error."""
  path = path or os.path.join(os.path.dirname(__file__), "peaks.json")
  with open(path) as f:
    table = json.load(f)
  if device_kind not in table or device_kind.startswith("_"):
    raise KeyError(
        f"no peaks on record for device_kind {device_kind!r}; "
        f"perfbench/harness/peaks.json has "
        f"{sorted(k for k in table if not k.startswith('_'))}")
  return table[device_kind]


def require_chips(n_chips: int, allow_cpu: bool = False):
  """The first ``n_chips`` devices, which must be TPUs, with their
  peaks.  ``allow_cpu`` is for perfbench's own tests only: ``run.py``
  never sets it."""
  import jax
  devices = jax.devices()
  dev = devices[0]
  print(f"platform {dev.platform}\ndevice_kind {dev.device_kind}\n"
        f"device_count {len(devices)}", flush=True)
  if allow_cpu:
    if len(devices) < n_chips:
      raise DeviceError(f"{len(devices)} devices, the cell needs {n_chips}")
    return devices[:n_chips], None
  if dev.platform != "tpu":
    raise DeviceError(
        f"no accelerator: jax {jax.__version__} found platform "
        f"{dev.platform!r} ({dev.device_kind!r}); perfbench measures on "
        "a TPU only")
  if len(devices) < n_chips:
    raise DeviceError(f"{len(devices)} chip(s) here, the cell needs "
                      f"{n_chips}")
  try:
    peaks = load_peaks(dev.device_kind)
  except KeyError as e:
    raise DeviceError(str(e.args[0]))
  return devices[:n_chips], peaks


def device_block(devices, memory_peak_bytes: int) -> dict:
  d = devices[0]
  return {"platform": d.platform, "kind": d.device_kind,
          "count": len(devices),
          "memory_peak_bytes": int(memory_peak_bytes)}


class CompileCounter:
  """Counts XLA compile requests (``backend_compile_duration`` fires once
  per request, whether the persistent cache answers it or not).  A run
  reads it around its measured window: a compilation inside the window
  fails the run."""

  _EVENT = "/jax/core/compile/backend_compile_duration"

  def __init__(self):
    from jax import monitoring
    self.count = 0
    monitoring.register_event_duration_secs_listener(self._on_event)

  def _on_event(self, name, *_args, **_kw):
    if name == self._EVENT:
      self.count += 1

  def require_none_since(self, mark: int, what: str) -> None:
    if self.count != mark:
      print(f"perfbench: {self.count - mark} compilation(s) inside {what}; "
            "every shape has to be warmed up during set-up",
            file=sys.stderr, flush=True)
      raise SystemExit(4)


def live_peak_bytes(devices) -> int:
  """What the fullest chip holds now plus what the backend has reserved
  for running programs: read right after the window, this is the
  program's own peak (on this backend a step's temporaries are
  ``bytes_reserved``, not ``bytes_in_use``)."""
  peak = 0
  for d in devices:
    s = d.memory_stats() or {}
    peak = max(peak, int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)))
  return peak
