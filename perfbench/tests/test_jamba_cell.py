"""The ``serve_family`` runner, the Jamba reference and glue, and the two
``ssm_scan`` readers PR 26 added: a toy Jamba configuration, mix and cell
laid into a temporary copy and run end to end on the CPU, and the readers
on hand-made ``ctx`` (present, absent -> ``None``)."""

import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.harness import ssm_cost
from perfbench.tests import toy_checkout
from perfbench.tests.test_loop_readers import steps

SEED = str(2 ** 31 + 91)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")
CELL = "toy-jamba-backlog"

TOY_CONFIG = {
    "model_type": "jamba", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "attn_layer_period": 4, "attn_layer_offset": 1,
    "num_attention_heads": 4, "num_key_value_heads": 1, "vocab_size": 4096,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input (the
    # embedding swamps every layer's output); 0.2 makes the layers matter
    "assumed": {"served_context": 96, "initializer_range": 0.2},
}
TOY_MIX = {
    "kind": "backlog", "population": 4000, "queue_target": 8,
    "prompt_len": {"dist": "uniform", "min": 6, "max": 24},
    "output_len": {"dist": "uniform", "min": 4, "max": 24},
    "max_total_len": 48, "token_law": {"dist": "uniform"},
    "ramp_s": 0.5, "ramp_fill": 12,
}
TOY_CELL = {
    "runner": "serve_family", "family": "jamba",
    "model": {"dtype": "float32", "param_dtype": "float32"},
    "epl_config": {}, "engine": {"num_slots": 6, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-4}},
}
NEW_METRICS = ["engine.ssm_scan_ms.backlog", "ssm_scan_roofline"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("jamba")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-jamba.json", TOY_CONFIG),
                   ("traffic/toy-reasoning.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-jamba", "source": "none (test)",
                         "file": "perfbench/configs/toy-jamba.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-jamba",
                           "traffic": "toy-reasoning", "chips": 1,
                           "why": "toy"})
  for m in doc["end_to_end"]:
    if m["name"] == "serve_tokens_per_s":
      m["workloads"].append(CELL)
  with open(os.path.join(toy_checkout.REPO, "BENCHMARK.json")) as f:
    real = {m["name"]: m for m in json.load(f)["per_layer"]}
  have = {m["name"] for m in doc["per_layer"]}
  for name in ("engine.dispatch_ms.backlog", "engine.stall_ms.backlog",
               *NEW_METRICS):
    if name not in have:
      doc["per_layer"].append(dict(real[name], **(
          {"workloads": [CELL]} if "workloads" in real[name] else {})))
  with open(path, "w") as f:
    json.dump(doc, f)
  return co


def test_toy_cell_end_to_end(checkout):
  r = toy_checkout.run_cell(checkout, "--workload", CELL, "--seed", SEED,
                            "--seconds", "2", "--trace", "0")
  assert r.returncode == 0, r.stderr[-2000:]
  doc = toy_checkout.last_line(r)
  assert set(doc["metrics"]) == {"serve_tokens_per_s", "setup_s"}
  assert doc["correct"] is True and doc["failed"] == 0, r.stdout[-2000:]
  assert doc["attempted"] > 0
  # every number compared is printed beside its limit; the weights'
  # checksum is among them
  assert "correct? served_logit_gap" in r.stdout
  assert "start from the same weights" in r.stdout


def test_traced_run_reports_the_span_readers(checkout):
  """The no-list readers that move ``serve_tokens_per_s`` report from
  the same spans as on the GPT cells; the ``ssm_scan`` readers find no
  such custom call in a CPU run's (recorded, foreign) trace and are left
  out, not null."""
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seed", SEED, "--seconds", "2",
      "--trace", "1", prelude=toy_checkout.FAKE_TRACE % RECORDED)
  assert r.returncode == 0, r.stderr[-2000:]
  doc = toy_checkout.last_line(r)
  for name in ("sched.host_ms.backlog", "engine.step_ms.backlog",
               "engine.dispatch_ms.backlog", "engine.stall_ms.backlog",
               "engine.slot_occupancy"):
    assert doc["metrics"][name]["value"] is not None, name
  assert not set(NEW_METRICS) & set(doc["metrics"])


def test_fp8_control_fails_the_toy_limit(checkout):
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seeds", "5", "6", "--seconds", "1.5",
      "--control", "fp8,bf16state", entry="control")
  assert r.returncode == 0, r.stderr[-2000:]
  summary = json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("SUMMARY ")][-1][8:])
  row = summary["served_logit_gap"]
  limit = TOY_CELL["check"]["limits"]["served_logit_gap"]
  assert row["sound_max"] <= limit < row["control_min"]["fp8"], row
  assert "bf16state" in row["control_min"], row


# ---------------------------------------------------------------- readers --


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


CONFIG = {"mamba_d_state": 16, "mamba_expand": 2, "hidden_size": 2560,
          "attn_layer_period": 14, "attn_layer_offset": 7,
          "num_hidden_layers": 28}


def serve_ctx(custom_calls, window_s=1.5, closing_s=0.25, period_ms=100.0):
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s]]},
          "spans": steps([period_ms] * 20), "active_slots": [128] * 12,
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "config": CONFIG, "model": {"dtype": "bfloat16"}}


def test_ssm_scan_readers_on_a_recorded_block():
  # 1.25 s of stepping at 100 ms = 12.5 steps; 26 calls a step, 0.1 s of
  # the kernel in all = 8 ms a step
  ctx = serve_ctx({"ssm_scan": (325.0, 0.1), "kv_write": (25.0, 0.01)})
  assert read(NEW_METRICS[0], ctx) == pytest.approx(8.0)
  _, nbytes = ssm_cost.step_cost(CONFIG, {"dtype": "bfloat16"}, 128)
  # 26 layers x 128 slots x 2 x 16 x 5120 x 4 B of state, and a little
  state = 26 * 128 * 2 * 16 * 5120 * 4
  assert state < nbytes < 1.1 * state
  want = 100 * (nbytes / 819e9) / 8e-3
  assert read(NEW_METRICS[1], ctx) == pytest.approx(want)
  assert 0 < want < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_ssm_scan_readers_find_nothing(metric):
  # the reference scan, or a parent commit: no such custom call
  assert read(metric, serve_ctx({"kv_write": (25.0, 0.01)})) is None
  assert read(metric, serve_ctx({})) is None
  # no serving/dispatch span; a train cell's ctx
  ctx = serve_ctx({"ssm_scan": (325.0, 0.1)})
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  assert read(metric, {"kind": "train"}) is None


def test_roofline_needs_the_configuration():
  # serve.py's ctx (a GPT cell) carries no configuration: nothing to read
  ctx = serve_ctx({"ssm_scan": (325.0, 0.1)})
  del ctx["config"]
  assert read("ssm_scan_roofline", ctx) is None


def test_required_bytes_are_a_floor():
  """What the kernel at hand moves for a full chunk of 8 positions is
  more than the requirement counts for one live position a slot."""
  _, floor = ssm_cost.ssm_scan_cost(128, 128, 16, 5120, 2)
  _, moved = ssm_cost.ssm_scan_cost(128, 128 * 8, 16, 5120, 2)
  assert floor < moved
  assert ssm_cost.mamba_layers(CONFIG) == 26
