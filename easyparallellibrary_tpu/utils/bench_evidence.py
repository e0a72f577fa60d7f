"""Machine-readable benchmark evidence log.

Every successful measurement appends a full raw record (per-chain wall
times, config, timestamp) to ``BENCH_EVIDENCE.json`` at the repo root, so
a number quoted in prose can be audited against what was timed.  The
perf gate (observability/perfgate.py) reads the same file.  Nothing
reports a stored record in place of a measurement: ``bench.py`` measures
or fails.

Reference analog: none (BASELINE.md mandate; the reference publishes no
numeric baselines at all — SURVEY.md §6).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

_DEFAULT_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "BENCH_EVIDENCE.json")


def evidence_path() -> str:
  return os.environ.get("EPL_BENCH_EVIDENCE", _DEFAULT_PATH)


def run_context(sim: bool = False, **extra: Any) -> Dict[str, Any]:
  """The uniform context block every evidence writer stamps:
  ``host_cores`` (the honesty tag behind every "scaling" claim on a
  shared box) and ``provenance`` — ``"sim"`` for numbers produced by
  the cost-card simulator, ``"hardware"`` for measured ones.  A
  sim-derived record can then never be mistaken for a measurement:
  consumers (sim/replica.py calibration) filter on the tag, and :func:`append_record` back-fills it for writers that
  predate the tag — which also means an OLD record without the key is
  exactly as trustworthy as one stamped "hardware", because that is
  what it would have been stamped.  ``extra`` keys ride along
  (e.g. ``backend=...``)."""
  ctx: Dict[str, Any] = {"host_cores": os.cpu_count() or 1,
                         "provenance": "sim" if sim else "hardware"}
  ctx.update(extra)
  return ctx


def load_records(path: Optional[str] = None) -> List[Dict[str, Any]]:
  path = path or evidence_path()
  try:
    with open(path) as f:
      data = json.load(f)
  except (OSError, ValueError):
    return []
  return data.get("records", []) if isinstance(data, dict) else []


def _preserve_corrupt(path: str) -> None:
  """If `path` exists but does not parse, move it aside instead of
  letting a fresh write erase earlier (possibly recoverable) evidence."""
  if not os.path.exists(path):
    return
  try:
    with open(path) as f:
      json.load(f)
  except ValueError:
    os.replace(path, f"{path}.corrupt-{int(time.time())}")
  except OSError:
    pass


def append_record(record: Dict[str, Any],
                  path: Optional[str] = None) -> Dict[str, Any]:
  """Validate ``record`` against the evidence schema (below), then
  append it; atomic-rename write so a crash mid-dump cannot corrupt
  earlier evidence.  Raises ``ValueError`` listing every schema error —
  the ONE door every writer (the benchmarks via ``benchmarks/
  _evidence.py``, ``bench.py`` directly) goes through, so ``make
  perf-gate`` (which refuses malformed records) can never meet a
  ledger entry this process wrote and cannot trust."""
  path = path or evidence_path()
  _preserve_corrupt(path)
  records = load_records(path)
  record = dict(record)
  record.setdefault("unix_time", time.time())
  record.setdefault("utc", time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()))
  # Uniform honesty tags (run_context): a writer that did not stamp
  # them gets the truthful defaults — this process's core count, and
  # "hardware" (a sim writer MUST tag itself via run_context(sim=True);
  # the simulator's own writers all do).
  for key, val in run_context().items():
    record.setdefault(key, val)
  errors = validate_record(record)
  if errors:
    raise ValueError(
        f"malformed BENCH_EVIDENCE record for "
        f"{record.get('metric')!r}: " + "; ".join(errors)
        + " (schema: utils/bench_evidence.py validate_record)")
  records.append(record)
  tmp = path + ".tmp"
  with open(tmp, "w") as f:
    json.dump({"records": records}, f, indent=1)
  os.replace(tmp, path)
  return record


def latest_record(metric: str,
                  path: Optional[str] = None) -> Optional[Dict[str, Any]]:
  """Most recent record for `metric` (highest unix_time wins)."""
  matches = [r for r in load_records(path) if r.get("metric") == metric]
  if not matches:
    return None
  return max(matches, key=lambda r: r.get("unix_time", 0))


# --------------------------------------------------------- record schema

# Keys with fixed meaning; everything else in a record is metrics
# payload.  A record's shape is name (``metric``) / ts (``unix_time`` +
# ``utc``) / context (``config`` + the backend tags) / metrics (a
# numeric ``value`` and/or payload keys) — the schema ``make perf-gate``
# enforces before trusting a record (benchmarks/_evidence.py is the
# shared writer that validates at write time).
_NAME_KEY = "metric"
_TS_KEYS = ("unix_time", "utc")
_CONTEXT_KEYS = ("config", "backend", "device", "device_kind",
                 "host_cores", "provenance")
_HEADLINE_KEYS = ("value", "unit")


def validate_record(rec: Any) -> List[str]:
  """Schema errors for one evidence record ([] = valid).

  Required: a non-empty string ``metric`` (the name), a numeric
  ``unix_time`` (the ts), and a metrics payload — either a numeric
  ``value`` or at least one payload key beyond the name/ts/context/
  headline sets.  ``config`` (the context), when present, must be an
  object; ``value``, when present, must be numeric or null (null is the
  honest "measurement unavailable" bench.py emits).  The perf gate
  REFUSES malformed records instead of silently skipping them — an
  unreadable ledger entry must fail loudly, not vanish from the
  budget's view."""
  if not isinstance(rec, dict):
    return ["record is not a JSON object"]
  errs: List[str] = []
  name = rec.get(_NAME_KEY)
  if not isinstance(name, str) or not name:
    errs.append("missing/invalid 'metric' (the record's name)")
  ts = rec.get("unix_time")
  if not isinstance(ts, (int, float)) or isinstance(ts, bool):
    errs.append("missing/invalid 'unix_time' (the record's ts)")
  ctx = rec.get("config")
  if ctx is not None and not isinstance(ctx, dict):
    errs.append("'config' (the record's context) must be an object")
  value = rec.get("value")
  if value is not None and (isinstance(value, bool)
                            or not isinstance(value, (int, float))):
    errs.append("'value' must be numeric or null")
  reserved = set((_NAME_KEY,) + _TS_KEYS + _CONTEXT_KEYS + _HEADLINE_KEYS)
  has_payload = (isinstance(value, (int, float))
                 and not isinstance(value, bool)) or any(
      k not in reserved for k in rec)
  if not has_payload:
    errs.append("no metrics payload: need a numeric 'value' or at "
                "least one payload key")
  return errs
