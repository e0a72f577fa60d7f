"""The LFM2-8B-A1B reference and glue through the ``serve_family`` runner
on an ``open_loop`` mix, and the two ``moe_gmm`` readers of its chat cell:
a toy configuration, mix and cell laid into a temporary copy and run end
to end on the CPU; the real manifest with the new entries, whose files
name each other; the parameter and cache arithmetic of ISSUE 32 from the
built tree; the readers on hand-made ``ctx`` (present, absent or too few
live slots -> ``None``)."""

import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.harness import manifest as manifest_lib, moe_cost
from perfbench.tests import toy_checkout
from perfbench.tests.test_loop_readers import steps

SEED = str(2 ** 31 + 95)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")
CELL = "toy-lfm2-chat"
REAL_CELL = "lfm2moe-chat-steady"
REAL_CONFIG = "lfm2-8b-a1b"

TOY_CONFIG = {
    "model_type": "lfm2_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_dense_layers": 1, "num_attention_heads": 8,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "num_experts": 8, "num_experts_per_tok": 2, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "rope_theta": 1000000, "norm_eps": 1e-5, "vocab_size": 4096,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input; 0.2 makes
    # the layers matter
    "assumed": {"served_context": 96, "initializer_range": 0.2,
                "expert_bias_std": 0.05},
}
TOY_MIX = {
    "kind": "open_loop",
    "arrivals": {"process": "gamma", "rate_per_s": 12.0, "cv": 1.0},
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                   "min": 4, "max": 40},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 16},
    "max_total_len": 64, "ramp_s": 0.5, "drain_margin_s": 0.2,
    "drain_limit_s": 5.0, "token_law": {"dist": "uniform"},
}
TOY_CELL = {
    "runner": "serve_family", "family": "lfm2_moe",
    "model": {"dtype": "float32", "param_dtype": "float32"},
    "epl_config": {}, "engine": {"num_slots": 6, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-4}},
}
NEW_METRICS = ["engine.moe_gmm_ms.chat", "moe_gmm_roofline.chat"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("lfm2")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-lfm2.json", TOY_CONFIG),
                   ("traffic/toy-rag.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-lfm2", "source": "none (test)",
                         "file": "perfbench/configs/toy-lfm2.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-lfm2",
                           "traffic": "toy-rag", "chips": 1, "why": "toy"})
  for m in doc["end_to_end"]:
    if m["name"] in ("ttft_p95_ms", "itl_p95_ms"):
      m["workloads"].append(CELL)
  with open(os.path.join(toy_checkout.REPO, "BENCHMARK.json")) as f:
    real = {m["name"]: m for m in json.load(f)["per_layer"]}
  have = {m["name"] for m in doc["per_layer"]}
  for name in ("engine.dispatch_ms.chat", "engine.stall_ms.chat",
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
  assert set(doc["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
  assert doc["correct"] is True and doc["failed"] == 0, r.stdout[-2000:]
  assert doc["attempted"] > 0
  assert "correct? served_logit_gap" in r.stdout
  assert "start from the same weights" in r.stdout


def test_traced_run_reports_the_span_readers(checkout):
  """The no-list readers that move the tails report from the same spans
  as on the other chat cell; the ``moe_gmm`` readers find no such custom
  call in a CPU run's (recorded, foreign) trace and are left out, not
  null."""
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seed", SEED, "--seconds", "2",
      "--trace", "1", prelude=toy_checkout.FAKE_TRACE % RECORDED)
  assert r.returncode == 0, r.stderr[-2000:]
  doc = toy_checkout.last_line(r)
  for name in ("sched.host_ms.chat", "engine.step_ms.chat",
               "engine.dispatch_ms.chat", "engine.stall_ms.chat",
               "sched.queue_p95_ms", "loadgen.late_p95_ms"):
    assert doc["metrics"][name]["value"] is not None, name
  assert not set(NEW_METRICS) & set(doc["metrics"])


def test_controls_are_read_on_the_toy_cell(checkout):
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seeds", "5", "6", "--seconds", "1.5",
      "--control", "fp8,bf16router,bf16conv", entry="control")
  assert r.returncode == 0, r.stderr[-2000:]
  summary = json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("SUMMARY ")][-1][8:])
  row = summary["served_logit_gap"]
  limit = TOY_CELL["check"]["limits"]["served_logit_gap"]
  assert row["sound_max"] <= limit < row["control_min"]["fp8"], row
  assert {"bf16router", "bf16conv"} <= set(row["control_min"]), row


# ----------------------------------------------------------- the manifest --


def test_manifest_loads_and_the_cells_files_name_each_other():
  man = manifest_lib.Manifest()
  cell = man.workload(REAL_CELL)
  assert (cell["chips"], cell["config"], cell["traffic"]) == (
      1, REAL_CONFIG, "rag-chat-steady")
  cell_file = man.cell_file(REAL_CELL)
  assert (cell_file["runner"], cell_file["family"]) == (
      "serve_family", "lfm2_moe")
  assert cell_file["engine"] == {"num_slots": 128, "prefill_chunk": 16}
  for kind, name in (("reference", "lfm2_moe"), ("runners", "epl_lfm2_moe"),
                     ("runners", "serve_family")):
    assert os.path.exists(os.path.join(toy_checkout.BENCH, kind,
                                       name + ".py"))
  mix = man.traffic_file(cell["traffic"])
  assert mix["kind"] == "open_loop" and mix["arrivals"]["cv"] == 1.0
  assert mix["arrivals"]["rate_per_s"] == int(mix["arrivals"]["rate_per_s"])
  assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 1.0, "min": 32, "max": 3072}
  assert mix["output_len"] == {"dist": "lognormal", "median": 96,
                               "sigma": 0.7, "min": 8, "max": 512}
  assert (mix["max_total_len"], mix["ramp_s"], mix["drain_margin_s"],
          mix["drain_limit_s"], mix["sampling"]) == (
              3584, 12.0, 0.0, 45.0, "greedy")
  names = [m["name"] for m in man.metrics_for(REAL_CELL, "per_layer")]
  assert set(NEW_METRICS) <= set(names)
  for name in ("engine.attn_ms.chat", "engine.kv_write_ms.chat",
               "engine.step_ms.chat", "sched.host_ms.chat",
               "engine.dispatch_ms.chat", "engine.stall_ms.chat",
               "sched.queue_p95_ms", "loadgen.late_p95_ms"):
    assert name in names, name
  assert [m["name"] for m in man.metrics_for(REAL_CELL, "end_to_end")] == [
      "ttft_p95_ms", "itl_p95_ms", "setup_s"]
  # no other cell gained a metric
  for other in ("gpt2m-chat-steady", "glm47flash-agent-backlog"):
    assert not set(NEW_METRICS) & {
        m["name"] for m in man.metrics_for(other, "per_layer")}


def test_configuration_is_the_catalog_row_cut_in_depth_only():
  from perfbench.reference import lfm2_moe as lfm
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  assert man.configs[REAL_CONFIG]["reduced"] == ["num_hidden_layers",
                                                 "layer_types"]
  assert doc["reduced"] == ["num_hidden_layers", "layer_types"]
  assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"]) == (
      14, 24)
  # the first 14 of the published 24: two leading dense layers (conv),
  # then three whole periods of (attention, conv, conv, conv)
  assert doc["layer_types"] == doc["layer_types_published"][:14]
  period = ["full_attention", "conv", "conv", "conv"]
  assert doc["layer_types"] == ["conv", "conv"] + 3 * period
  published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
               "intermediate_size": 7168, "max_position_embeddings": 128000,
               "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
               "norm_eps": 1e-05, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_dense_layers": 2,
               "num_experts": 32, "num_experts_per_tok": 4,
               "num_key_value_heads": 8, "rope_theta": 1000000,
               "routed_scaling_factor": 1, "use_expert_bias": True,
               "vocab_size": 65536}
  assert {k: doc[k] for k in published} == published
  cfg = lfm.Lfm2MoeConfig.from_file(doc)
  assert cfg.n_positions == 4096
  assert sum(not cfg.is_dense(i) for i in range(14)) == 12
  # the issue's arithmetic, each term
  assert cfg.mixer_params(lfm.CONV) == 16783360
  assert cfg.mixer_params(lfm.ATTENTION) == 10485888
  assert cfg.ff_params(True) == 44040192
  assert cfg.ff_params(False) == 352387104
  assert cfg.param_count() == doc["parameters"]["total"] == 4667077376
  assert doc["parameters"]["bytes_bfloat16"] == 2 * cfg.param_count()
  # whole, 24 layers: the published 8.3B only with the head tied
  whole = lfm.Lfm2MoeConfig.from_file(dict(
      doc, num_hidden_layers=24, layer_types=doc["layer_types_published"]))
  assert whole.param_count() == pytest.approx(8.34e9, rel=2e-3)
  assert 2 * whole.param_count() > 16e9       # does not fit one chip whole


def test_bytes_and_cache_reckoned_from_the_built_tree():
  """The weights as the program builds them (shapes only) and the cache
  the engine would allocate for the cell: the numbers in the
  configuration's and the cell's files."""
  import jax
  import jax.numpy as jnp
  from easyparallellibrary_tpu.serving import kv_cache as kv_lib
  from perfbench.runners import epl_lfm2_moe as glue
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  cell_file = man.cell_file(REAL_CELL)
  model, shell_of = glue.build_model(glue.ref_config(doc), cell_file["model"])
  leaves = jax.tree_util.tree_leaves(shell_of(jnp.zeros((1, 8), jnp.int32)))
  assert sum(x.size for x in leaves) == doc["parameters"]["total"]
  assert sum(x.size * x.dtype.itemsize for x in leaves) == doc[
      "parameters"]["bytes_as_built"] == 9335847936
  sizes = cell_file["engine"]
  layout = kv_lib.cache_layout(model.cfg, sizes["num_slots"],
                               sizes["prefill_chunk"])
  assert layout == {"kv_bytes": 3233808384, "kv_leaves": 6,
                    "state_bytes": 11534336, "state_leaves": 11,
                    "kv_order": "rows"}
  assert kv_lib.kv_leaf_shape(model.cfg, 128, 16) == (128, 4112, 512)
  # 9.34 GB + 3.23 GB + 0.01 GB: under the 15.0 GB at which ISSUE 32 takes
  # slots away, with ~1.5 GB for the step's temporaries
  assert 12.5e9 < 9335847936 + 3233808384 + 11534336 < 12.6e9


# ---------------------------------------------------------------- readers --


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


CONFIG = {"num_hidden_layers": 14, "num_dense_layers": 2, "num_experts": 32,
          "num_experts_per_tok": 4, "hidden_size": 2048,
          "moe_intermediate_size": 1792}


def serve_ctx(custom_calls, window_s=1.5, closing_s=0.25, period_ms=100.0,
              live=100):
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s]]},
          "spans": steps([period_ms] * 20), "active_slots": [live] * 12,
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "config": CONFIG, "model": {"dtype": "bfloat16"}}


def test_step_cost_by_hand():
  roofline = run_lib.load_module("layer_metrics", NEW_METRICS[1])
  assert roofline.expert_layers(CONFIG) == 12
  f, b = roofline.step_cost(CONFIG, {"dtype": "bfloat16"}, 100)
  weights = 12 * 32 * 3 * 2048 * 1792 * 2
  assert weights == pytest.approx(8.456e9, rel=1e-3)
  assert b == weights + 12 * 100 * 4 * (2 * 2048 + 3 * 1792) * 2
  assert f == 12 * 100 * 4 * 6 * 2048 * 1792
  assert (f, b) == tuple(12 * x for x in moe_cost.layer_cost(
      400, 32, 2048, 1792, 2))


def test_moe_gmm_readers_on_a_made_up_trace():
  # 1.25 s of stepping at 100 ms = 12.5 steps; 24 calls a step, 0.25 s of
  # the kernel in all = 20 ms a step
  ctx = serve_ctx({"moe_gmm": (300.0, 0.25), "kv_write": (100.0, 0.01)})
  assert read(NEW_METRICS[0], ctx) == pytest.approx(20.0)
  roofline = run_lib.load_module("layer_metrics", NEW_METRICS[1])
  _, nbytes = roofline.step_cost(CONFIG, {"dtype": "bfloat16"}, 100)
  want = 100 * (nbytes / 819e9) / 20e-3
  assert read(NEW_METRICS[1], ctx) == pytest.approx(want)
  assert 0 < want < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_moe_gmm_readers_find_nothing(metric):
  # the reference lowering, or a parent commit: no such custom call
  assert read(metric, serve_ctx({"kv_write": (25.0, 0.01)})) is None
  assert read(metric, serve_ctx({})) is None
  ctx = serve_ctx({"moe_gmm": (300.0, 0.25)})
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  assert read(metric, {"kind": "train"}) is None


def test_roofline_needs_this_configuration_and_every_expert_touched():
  calls = {"moe_gmm": (300.0, 0.25)}
  # 80 live slots x 4 = 10 x 32 assignments a layer: the least it reads
  assert read(NEW_METRICS[1], serve_ctx(calls, live=80)) is not None
  assert read(NEW_METRICS[1], serve_ctx(calls, live=79)) is None
  assert read(NEW_METRICS[0], serve_ctx(calls, live=79)) is not None
  ctx = serve_ctx(calls)
  ctx["config"] = {"n_routed_experts": 64, "first_k_dense_replace": 1}
  assert read(NEW_METRICS[1], ctx) is None        # another family's keys
  del ctx["config"]
  assert read(NEW_METRICS[1], ctx) is None
