"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Works on plain tuples so that the arithmetic is tested without a trace:
``load`` turns the file into ``{plane: {line: [(name, start_ns, dur_ns),
...]}}`` and everything else is interval arithmetic on that.

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event per executed HLO operation (fusions, custom calls,
collectives), ``XLA Modules`` one per program run, ``Steps`` one per
step.  Busy time is the union of the ``XLA Ops`` intervals.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)", re.I)
CONTAINER_RE = re.compile(r"^(while|conditional|call)(\.\d+)*$")


def find_xplane(trace_dir: str) -> str:
  paths = sorted(glob.glob(os.path.join(
      trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
  if not paths:
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
  return paths[-1]


def load(path: str) -> dict:
  """``{plane name: {line name: [(event name, start_ns, dur_ns)]}}``."""
  import jax
  data = jax.profiler.ProfileData.from_file(path)
  out = {}
  for plane in data.planes:
    lines = {}
    for line in plane.lines:
      lines.setdefault(line.name, []).extend(
          (ev.name, float(ev.start_ns), float(ev.duration_ns))
          for ev in line.events)
    out[plane.name] = lines
  return out


def device_planes(planes: dict) -> dict:
  """``{chip index: lines}`` of the device planes, sorted by index."""
  found = {}
  for name, lines in planes.items():
    m = DEVICE_PLANE_RE.match(name)
    if m and lines.get(OPS_LINE):
      found[int(m.group(1))] = lines
  return dict(sorted(found.items()))


def union(intervals):
  """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
  merged = []
  for s, e in sorted(intervals):
    if merged and s <= merged[-1][1]:
      if e > merged[-1][1]:
        merged[-1] = (merged[-1][0], e)
    else:
      merged.append((s, e))
  return merged


def total(intervals) -> float:
  return sum(e - s for s, e in intervals)


def subtract(a, b):
  """The parts of merged intervals ``a`` not covered by merged ``b``."""
  out = []
  j = 0
  for s, e in a:
    cur = s
    while j < len(b) and b[j][1] <= cur:
      j += 1
    k = j
    while k < len(b) and b[k][0] < e:
      if b[k][0] > cur:
        out.append((cur, b[k][0]))
      cur = max(cur, b[k][1])
      k += 1
    if cur < e:
      out.append((cur, e))
  return out


def clip(events, t0: float, t1: float):
  """Events cut to the window ``[t0, t1]`` as ``(name, start, end)``."""
  out = []
  for name, s, d in events:
    e = s + d
    if e <= t0 or s >= t1:
      continue
    out.append((name, max(s, t0), min(e, t1)))
  return out


def op_name(text: str) -> str:
  """The instruction's name from an event's text.  On a TPU an ``XLA
  Ops`` event carries the whole HLO line (``%fusion.12 = f32[..]
  fusion(...)``); elsewhere just the name."""
  return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
  """``fusion.123`` -> ``fusion``: instances of one kind of operation
  share a row in the breakdown."""
  return re.sub(r"(\.(\d+|remat\d*|clone))+$", "", name) or name


def is_collective(name: str) -> bool:
  return bool(COLLECTIVE_RE.match(name))


def is_container(name: str) -> bool:
  """Operations that only hold others (their bodies' operations are
  events of their own): counting both would count the time twice."""
  return bool(CONTAINER_RE.match(name))


def reduce_chip(ops, async_ops, t0: float, t1: float) -> dict:
  """One chip over the window ``[t0, t1]`` (ns): ``ops`` are its ``XLA
  Ops`` events, ``async_ops`` its ``Async XLA Ops`` (start to done)."""
  cut = [(op_name(n), s, e) for n, s, e in clip(ops, t0, t1)]
  leaf = [(n, s, e) for n, s, e in cut if not is_container(n)]
  busy = union((s, e) for _, s, e in leaf)
  coll = union(
      [(s, e) for n, s, e in leaf if is_collective(n)]
      + [(s, e) for n, s, e in clip(async_ops, t0, t1)
         if is_collective(op_name(n))])
  comp = union((s, e) for n, s, e in leaf if not is_collective(n))
  by_name = {}
  for n, s, e in leaf:
    by_name[n] = by_name.get(n, 0.0) + (e - s)
  examples = {}
  for text, _, _ in ops:
    examples.setdefault(base_name(op_name(text)), text)
  return {
      "examples": examples,
      "busy_ns": total(busy),
      "collective_ns": total(coll),
      "exposed_collective_ns": total(subtract(coll, comp)),
      "idle": subtract([(t0, t1)], busy),
      "by_name": by_name,
  }


def attribute_gaps(idle, host_spans, top: int = 10):
  """The longest idle gaps by what the host was doing: each gap's time
  goes to the host spans that overlap it (innermost last in
  ``host_spans`` wins nothing special; a gap split across spans is
  split), the rest to ``(no host span)``.  ``host_spans`` is
  ``[(name, start_ns, end_ns)]`` on the trace's clock."""
  by = {}
  spans = sorted(host_spans, key=lambda x: x[1])
  for gs, ge in idle:
    covered = []
    for name, s, e in spans:
      if e <= gs:
        continue
      if s >= ge:
        break
      lo, hi = max(s, gs), min(e, ge)
      if hi > lo:
        by[name] = by.get(name, 0.0) + (hi - lo)
        covered.append((lo, hi))
    rest = total(subtract([(gs, ge)], union(covered)))
    if rest > 0:
      by["(no host span)"] = by.get("(no host span)", 0.0) + rest
  rows = sorted(by.items(), key=lambda kv: -kv[1])[:top]
  return [[n, t / 1e9] for n, t in rows]


def reduce(planes: dict, window=None, host_spans=(), n_chips=None,
           top: int = 10) -> dict:
  """The device block of a traced run.

  ``window`` is ``(t0_ns, t1_ns)`` on the trace's clock; without it the
  window runs from the first to the last device operation.  Busy time
  is averaged over the chips; collective times likewise.
  """
  chips = device_planes(planes)
  if n_chips is not None:
    chips = dict(list(chips.items())[:n_chips])
  if not chips:
    raise ValueError("the trace holds no device plane with an "
                     f"{OPS_LINE!r} line: {sorted(planes)}")
  if window is None:
    starts = [ev[1] for lines in chips.values() for ev in lines[OPS_LINE]]
    ends = [ev[1] + ev[2] for lines in chips.values()
            for ev in lines[OPS_LINE]]
    window = (min(starts), max(ends))
  t0, t1 = window
  per_chip = [reduce_chip(lines[OPS_LINE], lines.get(ASYNC_LINE, ()),
                          t0, t1) for lines in chips.values()]
  n = len(per_chip)
  by_name = {}
  for r in per_chip:
    for name, t in r["by_name"].items():
      key = base_name(name)
      by_name[key] = by_name.get(key, 0.0) + t / n
  ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
  first = per_chip[0]
  return {
      "busy_s": sum(r["busy_ns"] for r in per_chip) / n / 1e9,
      "window_s": (t1 - t0) / 1e9,
      "collective_s": sum(r["collective_ns"] for r in per_chip) / n / 1e9,
      "exposed_collective_s":
          sum(r["exposed_collective_ns"] for r in per_chip) / n / 1e9,
      "chips": n,
      "device_ops": [[k, t / 1e9] for k, t in ops],
      "idle_gaps": attribute_gaps(first["idle"], host_spans, top),
      "op_seconds": {k: t / 1e9 for k, t in by_name.items()},
      "op_examples": first["examples"],
      "custom_calls": custom_calls(chips, t0, t1, n),
  }


def custom_calls(chips: dict, t0: float, t1: float, n: int) -> dict:
  """``{base name: (calls, seconds)}`` of the custom calls, averaged over
  the chips.  A Mosaic kernel carries the name of the scope that called
  it; the kernel readers look theirs up by that name."""
  acc = {}
  for lines in chips.values():
    for text, s, e in clip(lines[OPS_LINE], t0, t1):
      if " custom-call(" in text:
        key = base_name(op_name(text))
        calls, secs = acc.get(key, (0.0, 0.0))
        acc[key] = (calls + 1.0 / n, secs + (e - s) / n / 1e9)
  return acc


def host_annotations(planes: dict, prefix: str):
  """``[(name, start_ns, end_ns)]`` of the host-plane events whose name
  starts with ``prefix`` (``jax.profiler.TraceAnnotation`` spans)."""
  out = []
  for pname, lines in planes.items():
    if DEVICE_PLANE_RE.match(pname):
      continue
    for events in lines.values():
      for name, s, d in events:
        if name.startswith(prefix):
          out.append((name, s, s + d))
  return sorted(out, key=lambda x: x[1])


def dump_slice(planes: dict, path: str, slice_ns: float = 60e6,
               name_chars: int = 160) -> None:
  """Write the device planes' first ``slice_ns`` after a third of the
  trace (and the host annotations) as JSON, names cut short: a small
  recorded trace for the tests of this module."""
  import json
  chips = device_planes(planes)
  starts = [ev[1] for lines in chips.values() for ev in lines[OPS_LINE]]
  ends = [ev[1] + ev[2] for lines in chips.values() for ev in lines[OPS_LINE]]
  t0 = min(starts) + (max(ends) - min(starts)) / 3.0
  out = {}
  for pname, lines in planes.items():
    if not DEVICE_PLANE_RE.match(pname):
      continue
    out[pname] = {
        lname: [(n[:name_chars], s, d) for n, s, d in events
                if s + d > t0 and s < t0 + slice_ns]
        for lname, events in lines.items()
        if lname in (OPS_LINE, ASYNC_LINE, "XLA Modules", "Steps")}
  os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
  with open(path, "w") as f:
    json.dump(out, f)
