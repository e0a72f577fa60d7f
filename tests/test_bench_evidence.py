"""Tests for the benchmark evidence log: the record store bench.py and
the benchmarks append raw measurements to, and the perf gate reads."""

import os

from easyparallellibrary_tpu.utils import bench_evidence


def test_append_and_latest(tmp_path):
  p = str(tmp_path / "ev.json")
  bench_evidence.append_record(
      {"metric": "m", "value": 0.4, "unix_time": 100}, path=p)
  bench_evidence.append_record(
      {"metric": "m", "value": 0.3, "unix_time": 200}, path=p)
  bench_evidence.append_record(
      {"metric": "other", "value": 9.9, "unix_time": 300}, path=p)
  rec = bench_evidence.latest_record("m", path=p)
  assert rec["value"] == 0.3  # latest by time, not highest
  assert bench_evidence.latest_record("absent", path=p) is None


def test_corrupt_file_preserved_aside(tmp_path):
  p = str(tmp_path / "ev.json")
  with open(p, "w") as f:
    f.write("{not json")
  assert bench_evidence.load_records(p) == []
  bench_evidence.append_record({"metric": "m", "value": 1.0}, path=p)
  assert len(bench_evidence.load_records(p)) == 1
  # The unparseable original must survive as a .corrupt-* sibling, not
  # be silently overwritten.
  corrupt = [f for f in os.listdir(tmp_path) if ".corrupt-" in f]
  assert len(corrupt) == 1
  with open(tmp_path / corrupt[0]) as f:
    assert f.read() == "{not json"


def test_timestamps_autofilled(tmp_path):
  p = str(tmp_path / "ev.json")
  bench_evidence.append_record({"metric": "m", "value": 1.0}, path=p)
  rec = bench_evidence.load_records(p)[0]
  assert rec["unix_time"] > 0 and rec["utc"].endswith("Z")
