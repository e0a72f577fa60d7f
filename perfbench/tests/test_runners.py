"""Every runner end to end at toy width on the CPU, each run in a process
of its own; the controls that show ``correct`` can come out false."""

import json
import os

import pytest

from perfbench.tests import toy_checkout

SEED = str(2 ** 31 + 77)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  return toy_checkout.make(str(tmp_path_factory.mktemp("co")))


def well_formed(doc, metrics):
  assert set(doc) >= {"correct", "attempted", "failed", "metrics", "device"}
  assert set(doc["metrics"]) == set(metrics)
  for m in doc["metrics"].values():
    assert isinstance(m["value"], float) and m["value"] > 0 and m["unit"]
  assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
      doc["device"])
  assert doc["attempted"] > 0


@pytest.mark.parametrize("cell,metrics", [
    ("toy-train", {"train_tokens_per_s", "setup_s"}),
    ("toy-chat", {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
    ("toy-backlog", {"serve_tokens_per_s", "setup_s"}),
])
def test_runner_end_to_end(checkout, cell, metrics):
  r = toy_checkout.run_cell(checkout, "--workload", cell, "--seed", SEED,
                            "--seconds", "2", "--trace", "0")
  assert r.returncode == 0, r.stderr[-2000:]
  doc = toy_checkout.last_line(r)
  well_formed(doc, metrics)
  assert doc["correct"] is True and doc["failed"] == 0, r.stdout[-2000:]
  # every number compared is printed beside its limit
  assert "correct? " in r.stdout and "(limit " in r.stdout


@pytest.mark.parametrize("cell", ["toy-train", "toy-chat", "toy-backlog"])
def test_traced_run_reports_layer_metrics(checkout, cell):
  r = toy_checkout.run_cell(
      checkout, "--workload", cell, "--seed", SEED, "--seconds", "2",
      "--trace", "1", prelude=toy_checkout.FAKE_TRACE % RECORDED)
  assert r.returncode == 0, r.stderr[-2000:]
  doc = toy_checkout.last_line(r)
  assert doc["metrics"], r.stdout[-1500:]
  assert doc["device"]["busy_s"] > 0 and doc["device"]["window_s"] > 0
  assert len(doc["breakdown"]["device_ops"]) <= 10


def test_without_a_chip_there_is_no_result_line(checkout):
  r = toy_checkout.run_cell(checkout, "--workload", "toy-train", "--seed",
                            SEED, "--seconds", "1", allow_cpu=False)
  assert r.returncode != 0
  assert not r.stdout.strip().splitlines()[-1].startswith("{")


def test_lower_precision_on_the_system_side_is_not_correct(checkout):
  """The toy cells hold float32 limits; the same cells with the program
  in bfloat16 must fail them."""
  for cell in ("toy-train", "toy-backlog"):
    path = os.path.join(checkout, "perfbench", "workloads", cell + ".json")
    with open(path) as f:
      doc = json.load(f)
    low = json.loads(json.dumps(doc))
    low["model"]["dtype"] = "bfloat16"
    with open(path, "w") as f:
      json.dump(low, f)
    try:
      r = toy_checkout.run_cell(checkout, "--workload", cell, "--seed", SEED,
                                "--seconds", "1.5")
    finally:
      with open(path, "w") as f:
        json.dump(doc, f)
    assert r.returncode == 0, r.stderr[-2000:]
    assert toy_checkout.last_line(r)["correct"] is False, cell


def test_control_reference_in_fp8_fails_the_limits(checkout):
  """The control: the reference itself, computed in fp8 in the program's
  place, must fail at least one of each cell's limits."""
  for cell in ("toy-train", "toy-backlog"):
    r = toy_checkout.run_cell(
        checkout, "--workload", cell, "--seeds", "5", "6", "--seconds", "1.5",
        "--control", "fp8", entry="control")
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads([l for l in r.stdout.splitlines()
                          if l.startswith("SUMMARY ")][-1][8:])
    with open(os.path.join(checkout, "perfbench", "workloads",
                           cell + ".json")) as f:
      limits = json.load(f)["check"]["limits"]
    def limit_of(name):
      if name.startswith("loss_gap_step"):
        return limits["loss_gap"][int(name[len("loss_gap_step"):]) - 1]
      return limits.get(name)
    failing = [n for n, row in summary.items()
               if "fp8" in row["control_min"] and limit_of(n) is not None
               and row["control_min"]["fp8"] > limit_of(n)]
    assert failing, (cell, summary)
    sound = [n for n, row in summary.items() if limit_of(n) is not None
             and row["sound_max"] > limit_of(n)]
    assert not sound, (cell, summary)


BROKEN_TRAIN = """
import functools
import easyparallellibrary_tpu.parallel as par
from easyparallellibrary_tpu.parallel import api
_real = api.parallelize
def broken(step_fn, mesh, shardings, **kw):
  step = _real(step_fn, mesh, shardings, donate_state=False, **kw)
  @functools.wraps(step)
  def unchanged(state, batch, rng):
    return state, step(state, batch, rng)[1]     # the update is dropped
  unchanged.jitted, unchanged.mesh = step.jitted, step.mesh
  return unchanged
api.parallelize = par.parallelize = broken
"""

BROKEN_SERVE = """
from easyparallellibrary_tpu.serving import engine
_real = engine.ContinuousBatchingEngine._build_step
def broken(self, donate, guard=False):
  fn = _real(self, donate, guard)
  vocab = self.model.cfg.vocab_size
  class Altered:
    def __call__(self, *a):
      out = fn(*a)
      return ((out[0] + 1) % vocab,) + tuple(out[1:])   # every token + 1
    def _cache_size(self):
      return fn._cache_size()
  return Altered()
engine.ContinuousBatchingEngine._build_step = broken
"""


@pytest.mark.parametrize("cell,prelude", [("toy-train", BROKEN_TRAIN),
                                          ("toy-chat", BROKEN_SERVE)])
def test_broken_timed_path_is_not_correct(checkout, cell, prelude):
  """Drive a whole run (all but the look for a chip) with the timed path
  broken underneath: a step that returns its state unchanged; a token
  altered where it is produced."""
  r = toy_checkout.run_cell(checkout, "--workload", cell, "--seed", SEED,
                            "--seconds", "1.5", prelude=prelude)
  assert r.returncode == 0, r.stderr[-2000:]
  assert toy_checkout.last_line(r)["correct"] is False, r.stdout[-1500:]
