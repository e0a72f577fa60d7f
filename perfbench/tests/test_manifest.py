"""The manifest loader refuses what the contract refuses; the real
``BENCHMARK.json`` passes; an unknown device kind is an error."""

import copy
import json
import os

import pytest

from perfbench.harness import device, manifest
from perfbench.tests import toy_checkout


def _write(tmp_path, doc):
  (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
  return manifest.Manifest(str(tmp_path))


@pytest.fixture
def toy_doc():
  with open(os.path.join(toy_checkout.HERE, "toy", "BENCHMARK.json")) as f:
    return json.load(f)


def test_real_manifest_loads_and_names_only_files_that_exist():
  man = manifest.Manifest()
  assert "setup_s" in man.metrics
  for name, w in man.workloads.items():
    cell = man.cell_file(name)
    man.traffic_file(w["traffic"])
    man.config_file(w["config"])
    assert os.path.exists(os.path.join(
        manifest.BENCH_DIR, "runners", cell["runner"] + ".py"))
    assert man.metrics_for(name, "end_to_end")
    assert man.metrics_for(name, "per_layer")
  for m in man.doc["per_layer"]:
    assert os.path.exists(os.path.join(
        manifest.BENCH_DIR, "layer_metrics", m["name"] + ".py")), m["name"]
  assert len(json.dumps(man.doc)) < 64 * 1024
  four = [w for w in man.doc["workloads"] if w["chips"] == 4]
  assert len(four) <= max(1, len(man.doc["workloads"]) // 4)


def test_toy_manifest_loads(tmp_path, toy_doc):
  man = _write(tmp_path, toy_doc)
  assert [m["name"] for m in man.metrics_for("toy-train", "end_to_end")] == [
      "train_tokens_per_s", "setup_s"]
  # a per-layer metric without "workloads" goes to the cells that report
  # the end-to-end metric it moves
  assert [m["name"] for m in man.metrics_for("toy-chat", "per_layer")] == [
      "loadgen.late_p95_ms", "sched.queue_p95_ms", "sched.host_ms.chat",
      "engine.step_ms.chat"]
  assert "engine.slot_occupancy" in [
      m["name"] for m in man.metrics_for("toy-backlog", "per_layer")]


@pytest.mark.parametrize("bad", ["tokens per s", "tokens,s", "a/b", "",
                                 "µs", "-lead", "x" * 65])
def test_bad_metric_name_refused(tmp_path, toy_doc, bad):
  toy_doc["end_to_end"][0]["name"] = bad
  with pytest.raises(manifest.ManifestError):
    _write(tmp_path, toy_doc)


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17,
                                 "ms,"])
def test_bad_unit_refused(tmp_path, toy_doc, bad):
  toy_doc["end_to_end"][0]["unit"] = bad
  with pytest.raises(manifest.ManifestError):
    _write(tmp_path, toy_doc)


def test_other_refusals(tmp_path, toy_doc):
  for edit in (
      lambda d: d["workloads"][0].update(chips=2),
      lambda d: d["workloads"][0].update(config="nope"),
      lambda d: d["end_to_end"][0].update(bound=0.2),
      lambda d: d["end_to_end"][0].update(source="program_span"),
      lambda d: d["per_layer"][0].update(moves="nope"),
      lambda d: d["per_layer"][0].update(why="a reason"),
      lambda d: d.update(run_seconds=52),
      lambda d: d["workloads"].append(dict(d["workloads"][0], name="twin")),
      lambda d: d["end_to_end"].pop(),               # no setup_s
      lambda d: d["configs"][0].update(file="elsewhere/x.json"),
  ):
    doc = copy.deepcopy(toy_doc)
    edit(doc)
    with pytest.raises(manifest.ManifestError):
      _write(tmp_path, doc)


def test_unknown_workload_and_device_kind(tmp_path, toy_doc):
  man = _write(tmp_path, toy_doc)
  with pytest.raises(manifest.ManifestError):
    man.workload("no-such-cell")
  assert device.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
  assert device.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
  for kind in ("TPU v9", "cpu", "_source"):
    with pytest.raises(KeyError):
      device.load_peaks(kind)
