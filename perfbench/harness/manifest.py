"""Load ``BENCHMARK.json`` and the files it names; refuse what the
contract refuses (names, units, paths), so a bad entry fails here on the
CPU and not in the driver's check."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "perfbench")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
  """``BENCHMARK.json`` or a file it names breaks the contract."""


def check_name(name, what: str) -> str:
  if not isinstance(name, str) or not NAME_RE.match(name):
    raise ManifestError(
        f"{what} {name!r}: a name is at most 64 of a-z A-Z 0-9 _ . - and "
        "does not start with . or -")
  return name


def check_unit(unit, what: str) -> str:
  if not isinstance(unit, str) or not UNIT_RE.match(unit):
    raise ManifestError(
        f"{what}: unit {unit!r} is not 1 to 16 of a-z A-Z 0-9 _ / % . -")
  return unit


def _line(text, what: str) -> str:
  if (not isinstance(text, str) or not 1 <= len(text) <= 200
      or "\n" in text or "\t" in text):
    raise ManifestError(f"{what}: 1 to 200 characters on one line")
  return text


def load_json(path: str):
  with open(path) as f:
    return json.load(f)


class Manifest:
  """The parsed ``BENCHMARK.json`` with look-ups by name."""

  def __init__(self, root: str = ROOT):
    self.root = root
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
      raise ManifestError(f"no BENCHMARK.json at {root}")
    self.doc = load_json(path)
    self._validate()

  # ---------------------------------------------------------- validation

  def _validate(self) -> None:
    doc = self.doc
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(doc) != want:
      raise ManifestError(f"BENCHMARK.json keys {sorted(doc)} != "
                          f"{sorted(want)}")
    for p in doc["paths"]:
      if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/")
          or ".." in p.split("/")):
        raise ManifestError(f"bad path {p!r}")
    if not (isinstance(doc["run_seconds"], int)
            and 1 <= doc["run_seconds"] <= 51):
      raise ManifestError("run_seconds is a whole number from 1 to 51")
    self.configs = {}
    files = set()
    for c in doc["configs"]:
      check_name(c["name"], "configuration")
      if set(c) != {"name", "source", "file", "reduced", "why"}:
        raise ManifestError(f"configuration {c['name']}: keys {sorted(c)}")
      _line(c["source"], f"configuration {c['name']} source")
      _line(c["why"], f"configuration {c['name']} why")
      if not self._under_paths(c["file"]) or c["file"] in files:
        raise ManifestError(f"configuration {c['name']}: file {c['file']}")
      files.add(c["file"])
      for key in c["reduced"]:
        check_name(key, f"configuration {c['name']} reduced key")
      if c["name"] in self.configs:
        raise ManifestError(f"two configurations named {c['name']}")
      self.configs[c["name"]] = c
    self.workloads = {}
    pairs = set()
    for w in doc["workloads"]:
      check_name(w["name"], "workload")
      if set(w) != {"name", "config", "traffic", "chips", "why"}:
        raise ManifestError(f"workload {w['name']}: keys {sorted(w)}")
      check_name(w["traffic"], f"workload {w['name']} traffic")
      _line(w["why"], f"workload {w['name']} why")
      if w["config"] not in self.configs:
        raise ManifestError(f"workload {w['name']}: unknown configuration "
                            f"{w['config']!r}")
      if w["chips"] not in (1, 4):
        raise ManifestError(f"workload {w['name']}: chips is 1 or 4")
      pair = (w["config"], w["traffic"])
      if pair in pairs or w["name"] in self.workloads:
        raise ManifestError(f"workload {w['name']} appears twice")
      pairs.add(pair)
      self.workloads[w["name"]] = w
    used = {w["config"] for w in doc["workloads"]}
    for name in self.configs:
      if name not in used:
        raise ManifestError(f"configuration {name} is used by no cell")
    self.metrics = {}
    for kind in ("end_to_end", "per_layer"):
      for m in doc[kind]:
        check_name(m["name"], "metric")
        check_unit(m["unit"], f"metric {m['name']}")
        allowed = {"name", "unit", "better", "source", "workloads"}
        need = {"name", "unit", "better", "source"}
        if kind == "end_to_end":
          allowed |= {"bound"}
          need |= {"bound"}
        else:
          allowed |= {"layer", "moves"}
          need |= {"layer", "moves"}
        if not need <= set(m) <= allowed:
          raise ManifestError(f"metric {m['name']}: keys {sorted(m)}")
        if m["better"] not in ("lower", "higher"):
          raise ManifestError(f"metric {m['name']}: better")
        if m["source"] not in SOURCES:
          raise ManifestError(f"metric {m['name']}: source {m['source']!r}")
        if kind == "end_to_end":
          if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"end-to-end metric {m['name']}: source")
          if not 0 < m["bound"] <= 0.1:
            raise ManifestError(f"metric {m['name']}: bound")
        else:
          _line(m["layer"], f"metric {m['name']} layer")
        for w in m.get("workloads", ()):
          if w not in self.workloads:
            raise ManifestError(f"metric {m['name']}: unknown cell {w!r}")
        if m["name"] in self.metrics:
          raise ManifestError(f"two metrics named {m['name']}")
        self.metrics[m["name"]] = dict(m, kind=kind)
    e2e = {m["name"] for m in doc["end_to_end"]}
    if "setup_s" not in e2e:
      raise ManifestError("no setup_s among the end-to-end metrics")
    for m in doc["per_layer"]:
      if m["moves"] not in e2e:
        raise ManifestError(f"metric {m['name']} moves {m['moves']!r}, "
                            "which is no end-to-end metric")

  def _under_paths(self, rel: str) -> bool:
    return any(rel == p or rel.startswith(p.rstrip("/") + "/")
               for p in self.doc["paths"])

  # ------------------------------------------------------------ look-ups

  @property
  def run_seconds(self) -> int:
    return self.doc["run_seconds"]

  def workload(self, name: str) -> dict:
    if name not in self.workloads:
      raise ManifestError(f"no workload {name!r} in BENCHMARK.json; it has "
                          f"{sorted(self.workloads)}")
    return self.workloads[name]

  def config_file(self, name: str) -> dict:
    return load_json(os.path.join(self.root, self.configs[name]["file"]))

  def cell_file(self, name: str) -> dict:
    """``perfbench/workloads/<cell>.json``: runner and sizes."""
    check_name(name, "workload")
    return load_json(os.path.join(self.root, "perfbench", "workloads",
                                  name + ".json"))

  def traffic_file(self, name: str) -> dict:
    """``perfbench/traffic/<mix>.json``: the mix's parameters."""
    check_name(name, "traffic")
    return load_json(os.path.join(self.root, "perfbench", "traffic",
                                  name + ".json"))

  def metrics_for(self, cell: str, kind: str) -> list:
    """The metrics of ``kind`` that ``cell`` reports, in file order."""
    out = []
    for m in self.doc[kind]:
      cells = m.get("workloads")
      if cells is None:
        if kind == "per_layer":
          moved = self.metrics[m["moves"]].get("workloads")
          if moved is not None and cell not in moved:
            continue
      elif cell not in cells:
        continue
      out.append(m)
    return out
