"""The GLM-4.7-Flash reference and glue through the ``serve_family`` runner,
and the two ``moe_gmm`` readers: a toy configuration, mix and cell laid
into a temporary copy and run end to end on the CPU; the real manifest
with the new entries; ``moe_cost``'s arithmetic by hand; the readers on
hand-made ``ctx`` (present, absent -> ``None``)."""

import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.harness import manifest as manifest_lib, moe_cost
from perfbench.tests import toy_checkout
from perfbench.tests.test_loop_readers import steps

SEED = str(2 ** 31 + 93)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")
CELL = "toy-glm-backlog"
REAL_CELL = "glm47flash-agent-backlog"

TOY_CONFIG = {
    "model_type": "glm4_moe_lite", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
    "rope_theta": 1000000, "rms_norm_eps": 1e-5, "vocab_size": 4096,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input; 0.2 makes
    # the layers matter
    "assumed": {"served_context": 96, "initializer_range": 0.2,
                "e_score_correction_bias_std": 0.05},
}
TOY_MIX = {
    "kind": "backlog", "population": 4000, "queue_target": 8,
    "prompt_len": {"dist": "uniform", "min": 6, "max": 24},
    "output_len": {"dist": "uniform", "min": 4, "max": 24},
    "max_total_len": 48, "token_law": {"dist": "uniform"},
    "ramp_s": 0.5, "ramp_fill": 12,
}
TOY_CELL = {
    "runner": "serve_family", "family": "glm4_moe_lite",
    "model": {"dtype": "float32", "param_dtype": "float32"},
    "epl_config": {}, "engine": {"num_slots": 6, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-4}},
}
NEW_METRICS = ["engine.moe_gmm_ms.backlog", "moe_gmm_roofline"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("glm")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-glm.json", TOY_CONFIG),
                   ("traffic/toy-agent.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-glm", "source": "none (test)",
                         "file": "perfbench/configs/toy-glm.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-glm",
                           "traffic": "toy-agent", "chips": 1,
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
  assert "correct? served_logit_gap" in r.stdout
  assert "start from the same weights" in r.stdout


def test_traced_run_reports_the_span_readers(checkout):
  """The no-list readers that move ``serve_tokens_per_s`` report from the
  same spans as on the other cells; the ``moe_gmm`` readers find no such
  custom call in a CPU run's (recorded, foreign) trace and are left out,
  not null."""
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


def test_controls_are_read_on_the_toy_cell(checkout):
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seeds", "5", "6", "--seconds", "1.5",
      "--control", "fp8,bf16router", entry="control")
  assert r.returncode == 0, r.stderr[-2000:]
  summary = json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("SUMMARY ")][-1][8:])
  row = summary["served_logit_gap"]
  limit = TOY_CELL["check"]["limits"]["served_logit_gap"]
  assert row["sound_max"] <= limit < row["control_min"]["fp8"], row
  assert "bf16router" in row["control_min"], row


# ----------------------------------------------------------- the manifest --


def test_manifest_loads_with_the_new_entries():
  man = manifest_lib.Manifest()
  cell = man.workload(REAL_CELL)
  assert cell["chips"] == 1 and cell["config"] == "glm-4.7-flash"
  assert man.cell_file(REAL_CELL)["family"] == "glm4_moe_lite"
  assert man.traffic_file(cell["traffic"])["max_total_len"] == 3584
  names = [m["name"] for m in man.metrics_for(REAL_CELL, "per_layer")]
  assert set(NEW_METRICS) <= set(names)
  for name in ("engine.attn_ms.backlog", "engine.kv_write_ms.backlog",
               "engine.step_ms.backlog", "sched.host_ms.backlog",
               "engine.slot_occupancy", "engine.dispatch_ms.backlog",
               "engine.stall_ms.backlog"):
    assert name in names, name
  assert [m["name"] for m in man.metrics_for(REAL_CELL, "end_to_end")] == [
      "serve_tokens_per_s", "setup_s"]
  # no other cell gained a metric
  for other in ("gpt2m-offline-backlog", "jamba2-3b-reasoning-backlog"):
    assert not set(NEW_METRICS) & {
        m["name"] for m in man.metrics_for(other, "per_layer")}


def test_configuration_is_the_catalog_row_cut_in_depth_only():
  from perfbench.reference import glm4_moe_lite as glm
  man = manifest_lib.Manifest()
  doc = man.config_file("glm-4.7-flash")
  assert man.configs["glm-4.7-flash"]["reduced"] == ["num_hidden_layers"]
  assert doc["num_hidden_layers"] == 8
  assert doc["num_hidden_layers_published"] == 47
  cfg = glm.Glm4MoeLiteConfig.from_file(doc)
  assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
          cfg.vocab_size) == (64, 4, 154880)
  assert cfg.layer_kinds().count(glm.MOE) == 7
  # the issue's arithmetic: 5.166B parameters, 10.33 GB in bfloat16
  assert cfg.param_count() == pytest.approx(5.166e9, rel=1e-3)


# ---------------------------------------------------------------- readers --


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


CONFIG = {"num_hidden_layers": 8, "first_k_dense_replace": 1,
          "n_routed_experts": 64, "num_experts_per_tok": 4,
          "hidden_size": 2048, "moe_intermediate_size": 1536}


def serve_ctx(custom_calls, window_s=1.5, closing_s=0.25, period_ms=100.0):
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s]]},
          "spans": steps([period_ms] * 20), "active_slots": [96] * 12,
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "config": CONFIG, "model": {"dtype": "bfloat16"}}


def test_moe_cost_by_hand():
  """One small case: 2 experts of 4 x 3, 5 assignments, 2 B a value.
  Weights 2 x 3 x 4 x 3 x 2 = 144 B; rows 5 x (2 x 4 + 3 x 3) x 2 =
  170 B; flops 5 x 6 x 4 x 3 = 360."""
  assert moe_cost.layer_cost(5, 2, 4, 3, 2) == (360, 314)
  # the cell's: 7 layers x 64 experts x 18.87 MB of weights
  f, b = moe_cost.step_cost(CONFIG, {"dtype": "bfloat16"}, 96)
  weights = 7 * 64 * 3 * 2048 * 1536 * 2
  assert weights == pytest.approx(8.456e9, rel=1e-3)
  assert b == weights + 7 * 96 * 4 * (2 * 2048 + 3 * 1536) * 2
  assert f == 7 * 96 * 4 * 6 * 2048 * 1536
  assert moe_cost.expert_layers(CONFIG) == 7


def test_moe_gmm_readers_on_a_recorded_block():
  # 1.25 s of stepping at 100 ms = 12.5 steps; 14 calls a step, 0.25 s of
  # the kernel in all = 20 ms a step
  ctx = serve_ctx({"moe_gmm": (175.0, 0.25), "kv_write": (100.0, 0.01)})
  assert read(NEW_METRICS[0], ctx) == pytest.approx(20.0)
  _, nbytes = moe_cost.step_cost(CONFIG, {"dtype": "bfloat16"}, 96)
  want = 100 * (nbytes / 819e9) / 20e-3
  assert read(NEW_METRICS[1], ctx) == pytest.approx(want)
  assert 0 < want < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_moe_gmm_readers_find_nothing(metric):
  # the reference lowering, or a parent commit: no such custom call
  assert read(metric, serve_ctx({"kv_write": (25.0, 0.01)})) is None
  assert read(metric, serve_ctx({})) is None
  ctx = serve_ctx({"moe_gmm": (175.0, 0.25)})
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  assert read(metric, {"kind": "train"}) is None


def test_roofline_needs_an_expert_configuration():
  ctx = serve_ctx({"moe_gmm": (175.0, 0.25)})
  ctx["config"] = {"mamba_d_state": 16}       # another family's
  assert read("moe_gmm_roofline", ctx) is None
  del ctx["config"]
  assert read("moe_gmm_roofline", ctx) is None
