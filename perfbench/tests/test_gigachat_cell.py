"""The GigaChat3.5 reference and glue through the ``serve_family_even``
runner on a ``backlog`` mix: a toy configuration, mix and cell laid into a
temporary copy and run end to end on the CPU; the real manifest with the
new entries, whose files name each other; the parameter and cache
arithmetic of ISSUE 50 from the reference's count and from the program's
built tree; ``harness/gdn_cost.py`` by hand for one slot and one position;
the two new readers on hand-made ``ctx`` and on the trace recorded on the
chip (which holds no ``gdn_scan``: ``None``, not a number)."""

import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.harness import gdn_cost, manifest as manifest_lib, moe_cost
from perfbench.tests import toy_checkout
from perfbench.tests.test_loop_readers import steps

SEED = str(2 ** 31 + 50)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")
CELL = "toy-gigachat-backlog"
REAL_CELL = "gigachat35-decode-backlog"
REAL_CONFIG = "gigachat3.5-432b-a28b"
MS = "engine.gdn_scan_ms.backlog"
ROOFLINE = "gdn_scan_roofline"
HELD = "moe_gmm_roofline.held"
NEW_METRICS = [MS, ROOFLINE]

TOY_CONFIG = {
    "model_type": "gigachat3_5", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "full_attention_layers": [1], "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "use_mla_scaling_factor": True, "gated_attention": True,
    "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
    "layernorm_gating_weight": 2,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 4,
    "linear_num_value_heads": 8,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-6,
    "swiglu_limit": 10, "n_routed_experts": 3,
    "n_routed_experts_published": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "vocab_size": 4096,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input; 0.2 makes
    # the layers matter
    "assumed": {"served_context": 128, "initializer_range": 0.2,
                "e_score_correction_bias_std": 0.05, "experts_first": 2},
}
TOY_MIX = {
    "kind": "backlog", "population": 256, "queue_target": 4,
    "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
    "output_len": {"dist": "uniform", "min": 4, "max": 16},
    "max_total_len": 64, "token_law": {"dist": "uniform"},
    "sampling": "greedy", "ramp_s": 0.5, "ramp_fill": 6,
}
TOY_CELL = {
    "runner": "serve_family_even", "family": "gigachat3_5",
    "model": {"dtype": "float32", "param_dtype": "float32"},
    "epl_config": {}, "engine": {"num_slots": 6, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-3}},
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("gigachat")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-gigachat.json", TOY_CONFIG),
                   ("traffic/toy-hybrid.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-gigachat", "source": "none (test)",
                         "file": "perfbench/configs/toy-gigachat.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-gigachat",
                           "traffic": "toy-hybrid", "chips": 1,
                           "why": "toy"})
  for m in doc["end_to_end"]:
    if m["name"] == "serve_tokens_per_s":
      m["workloads"].append(CELL)
  with open(os.path.join(toy_checkout.REPO, "BENCHMARK.json")) as f:
    real = {m["name"]: m for m in json.load(f)["per_layer"]}
  have = {m["name"] for m in doc["per_layer"]}
  for name in ("engine.dispatch_ms.backlog", "engine.stall_ms.backlog",
               HELD, *NEW_METRICS):
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
  """The no-list readers that move the throughput report from the same
  spans as on the other backlog cells; the kernels' readers find none of
  their custom calls in a CPU run's (recorded, foreign) trace and are left
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
  assert not {HELD, *NEW_METRICS} & set(doc["metrics"])


def test_controls_are_read_on_the_toy_cell(checkout):
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seeds", "5", "6", "--seconds", "1.5",
      "--control", "fp8,bf16router,bf16state", entry="control")
  assert r.returncode == 0, r.stderr[-2000:]
  summary = json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("SUMMARY ")][-1][8:])
  row = summary["served_logit_gap"]
  limit = TOY_CELL["check"]["limits"]["served_logit_gap"]
  assert row["sound_max"] <= limit < row["control_min"]["fp8"], row
  assert {"bf16router", "bf16state"} <= set(row["control_min"]), row


# ----------------------------------------------------------- the manifest --


def test_manifest_loads_and_the_cells_files_name_each_other():
  man = manifest_lib.Manifest()
  cell = man.workload(REAL_CELL)
  assert (cell["chips"], cell["config"], cell["traffic"]) == (
      1, REAL_CONFIG, "hybrid-decode-backlog")
  cell_file = man.cell_file(REAL_CELL)
  assert (cell_file["runner"], cell_file["family"]) == (
      "serve_family_even", "gigachat3_5")
  assert cell_file["engine"] == {"num_slots": 128, "prefill_chunk": 32}
  assert cell_file["epl_config"] == {}
  for kind, name in (("reference", "gigachat3_5"),
                     ("runners", "epl_gigachat3_5"),
                     ("runners", "serve_family_even"),
                     ("harness", "gdn_cost")):
    assert os.path.exists(os.path.join(toy_checkout.BENCH, kind,
                                       name + ".py"))
  mix = man.traffic_file(cell["traffic"])
  assert {k: mix[k] for k in mix if k != "why"} == {
      "kind": "backlog", "population": 4096, "queue_target": 32,
      "prompt_len": {"dist": "uniform", "min": 512, "max": 2560},
      "output_len": {"dist": "uniform", "min": 128, "max": 512},
      "max_total_len": 3072, "token_law": {"dist": "uniform"},
      "sampling": "greedy", "ramp_s": 24.0, "ramp_fill": 128}
  names = [m["name"] for m in man.metrics_for(REAL_CELL, "per_layer")]
  for name in (MS, ROOFLINE, HELD, "engine.kv_write_ms.backlog",
               "engine.attn_ms.backlog", "engine.moe_gmm_ms.backlog",
               "engine.host_turn_ms.backlog", "engine.step_ms.backlog",
               "sched.host_ms.backlog", "engine.dispatch_ms.backlog",
               "engine.stall_ms.backlog", "engine.slot_occupancy"):
    assert name in names, name
  # Readers that would find nothing, or count absent experts' rows.
  assert not {"engine.ssm_scan_ms.backlog", "moe_gmm_roofline",
              "engine.index_ms.backlog"} & set(names)
  assert [m["name"] for m in man.metrics_for(REAL_CELL, "end_to_end")] == [
      "serve_tokens_per_s", "setup_s"]
  # no other cell gained a metric
  for other in ("jamba2-3b-reasoning-backlog", "glm47flash-agent-backlog",
                "dots3note-longdoc-backlog"):
    assert not set(NEW_METRICS) & {
        m["name"] for m in man.metrics_for(other, "per_layer")}
  # the new cell takes one chip; the four-chip cells are as they were
  assert sum(w["chips"] == 4 for w in man.doc["workloads"]) == 2


def test_configuration_is_the_catalog_row_at_one_chips_share():
  from perfbench.reference import gigachat3_5 as giga
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  reduced = ["num_hidden_layers", "first_k_dense_replace",
             "full_attention_layers", "n_routed_experts", "vocab_size"]
  assert man.configs[REAL_CONFIG]["reduced"] == reduced == doc["reduced"]
  assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"]) == (
      5, 40)
  assert (doc["first_k_dense_replace"],
          doc["first_k_dense_replace_published"]) == (1, 3)
  assert doc["full_attention_layers"] == [1]
  assert doc["full_attention_layers_published"] == list(range(3, 40, 4))
  assert (doc["n_routed_experts"], doc["n_routed_experts_published"]) == (
      16, 256)
  assert (doc["vocab_size"], doc["vocab_size_published"]) == (16032, 128256)
  assert 8 * doc["vocab_size"] == doc["vocab_size_published"]
  # every width as published
  published = {
      "hidden_size": 7168, "intermediate_size": 18432,
      "moe_intermediate_size": 2048, "num_attention_heads": 64,
      "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
      "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_head_dim": 192,
      "linear_num_key_heads": 32, "linear_num_value_heads": 64,
      "linear_key_head_dim": 128, "linear_value_head_dim": 128,
      "linear_conv_kernel_dim": 4, "num_experts_per_tok": 8,
      "n_shared_experts": 1, "routed_scaling_factor": 2.5,
      "rope_theta": 100000, "swiglu_limit": 10,
      "layernorm_gating_weight": 2, "linear_sigmoid_gate_scale": 2,
      "max_position_embeddings": 262144, "num_nextn_predict_layers": 2,
      "model_type": "gigachat3_5"}
  assert {k: doc[k] for k in published} == published
  assert doc["rope_scaling"] == {
      "beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
      "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
      "type": "yarn"}
  for said in ("SIXTEEN", "16,032 of 128,256", "published layers 2-6",
               "4,731,722,752"):
    assert said in doc["deployment"], said
  for form in ("gated_norm", "model_norm", "attention_gate", "swiglu_limit",
               "mla_scaling_factor"):
    assert form in doc["assumed"] and f"{form}_other_reading" in doc[
        "assumed"], form
  cfg = giga.GigaChat35Config.from_file(doc)
  assert (cfg.n_positions, cfg.router_width, cfg.experts_first,
          cfg.n_routed_experts) == (4096, 256, 0, 16)
  # the issue's arithmetic, each line
  D = 7168
  assert D * (16384 + 8192) == 176_160_768                 # W_qkvz
  assert D * 128 == 917_504                                # W_ba
  assert 4 * 16384 == 65_536                               # the taps
  assert 8192 * D == 58_720_256                            # W_o
  assert cfg.linear_params() == 235_864_320
  assert cfg.latent_params() == {"mixer": 101_124_096, "gate": 58_720_256}
  assert cfg.expert_params() == 44_040_192
  assert 16 * cfg.expert_params() == 704_643_072
  assert D * 256 + 256 == 1_835_264                        # router and bias
  assert 3 * D * 18432 == 396_361_728                      # the dense MLP
  assert [cfg.layer_params(i) for i in range(5)] == [
      632_254_720, 910_391_552, 986_411_520, 986_411_520, 986_411_520]
  assert 2 * 16032 * D + D == 229_841_920
  assert cfg.param_count() == 4_731_722_752
  # a second period would not fit a chip
  assert (cfg.param_count() + 3 * 986_411_520 + 910_391_552) * 2 > 16e9
  # whole: 40 layers, every expert, the whole vocabulary
  whole = giga.GigaChat35Config.from_file(dict(
      doc, num_hidden_layers=40, first_k_dense_replace=3,
      full_attention_layers=doc["full_attention_layers_published"],
      n_routed_experts=256, vocab_size=128256))
  assert whole.param_count() == pytest.approx(430.5e9, rel=1e-3)


def test_bytes_and_cache_reckoned_from_the_built_tree():
  """The weights as the program builds them (shapes only) and the cache
  the engine would allocate for the cell: ISSUE 50's numbers."""
  import jax
  import jax.numpy as jnp
  from easyparallellibrary_tpu.serving import kv_cache as kv_lib
  from perfbench.runners import epl_gigachat3_5 as glue
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  cell_file = man.cell_file(REAL_CELL)
  model, shell_of = glue.build_model(glue.ref_config(doc), cell_file["model"])
  assert model.cfg.experts_held == (0, 16)
  shell = shell_of(jnp.zeros((1, 8), jnp.int32))
  leaves = jax.tree_util.tree_leaves(shell)
  assert sum(x.size for x in leaves) == 4_731_722_752
  count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
  assert count(shell["block_0"]["linear"]) == 235_864_320
  assert count(shell["block_1"]["latent"]) == 101_124_096 + 58_720_256
  assert count(shell["block_2"]["moe"]) == (704_643_072 + 44_040_192
                                            + 1_835_264)
  assert count(shell["block_0"]["mlp"]) == 396_361_728
  nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
  # bfloat16 but the routers, their biases, the norms and the decay rates
  assert 9.46e9 < nbytes < 9.48e9
  sizes = cell_file["engine"]
  slots, chunk = sizes["num_slots"], sizes["prefill_chunk"]
  layout = kv_lib.cache_layout(model.cfg, slots, chunk)
  assert layout == {
      "kv_bytes": 0, "kv_leaves": 0,
      "state_bytes": 4 * 128 * (4_194_304 + 98_304), "state_leaves": 8,
      "latent_bytes": 128 * 4224 * 576 * 2, "latent_leaves": 1,
      "kv_order": "positions"}
  # 17.17 MB of recurrent state a slot.  ISSUE 50's 4.76 MB of latent rows
  # a slot ((4096 + 32) x 1,152 B) are 4.87 MB: at 128 slots the leaf is
  # allocated 4224 rows long, whole lane tiles, or the chip would keep it
  # slot-minor and copy it twice a step (serving/kv_cache.py
  # ``kv_leaf_shape``; the same model at 96 slots keeps the issue's 4128).
  assert layout["state_bytes"] / slots == pytest.approx(17.17e6, rel=1e-3)
  assert layout["latent_bytes"] / slots == 4224 * 1152 == 4_866_048
  assert kv_lib.cache_layout(model.cfg, 96, chunk)["latent_bytes"] / 96 == (
      4128 * 1152) == pytest.approx(4.76e6, rel=1e-3)
  total = kv_lib.cache_bytes(model.cfg, slots, chunk)
  assert total == layout["state_bytes"] + layout["latent_bytes"]
  assert total == pytest.approx(2.20e9 + 0.62e9, rel=3e-3)
  # weights + cache: 12.29 GB before temporaries, over a quarter of a chip
  assert nbytes + total == pytest.approx(12.29e9, rel=2e-3)
  # the state's bytes do not depend on the served context
  import dataclasses
  longer = dataclasses.replace(model.cfg, max_seq_len=262144)
  assert kv_lib.cache_layout(longer, slots, chunk)["state_bytes"] == layout[
      "state_bytes"]
  assert kv_lib.recurrent_kinds(model.cfg) == ("gated_delta",)


# --------------------------------------------------------------- gdn_cost --


def test_one_slot_and_one_position_by_hand():
  """One slot advancing by one position: its state in and out, one row of
  the convolution's output in, ``g`` and ``beta``, one row out."""
  f, b = gdn_cost.gdn_scan_cost(1, 1, 32, 64, 128, 128, 2)
  state = 64 * 128 * 128 * 4
  assert state == 4_194_304
  assert b == 2 * state + (16384 + 8192) * 2 + 2 * 64 * 4
  assert f == 7 * 64 * 128 * 128
  # a second position of the same slot adds its activations alone
  f2, b2 = gdn_cost.gdn_scan_cost(1, 2, 32, 64, 128, 128, 2)
  assert b2 - b == (16384 + 8192) * 2 + 512 and f2 == 2 * f
  doc = manifest_lib.Manifest().config_file(REAL_CONFIG)
  assert gdn_cost.linear_layers(doc) == 4
  fs, bs = gdn_cost.step_cost(doc, {"dtype": "bfloat16"}, 128)
  assert (fs, bs) == (4 * 128 * f, 4 * 128 * b)
  # 4.3 GB a step at 128 decoding slots: the issue's "4.4 GB a step"
  assert bs == pytest.approx(4.32e9, rel=5e-3)


# ---------------------------------------------------------------- readers --


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


def serve_ctx(custom_calls, window_s=1.5, closing_s=0.25, period_ms=25.0,
              live=128, rate=4000.0):
  config = manifest_lib.Manifest().config_file(REAL_CONFIG)
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s]]},
          "spans": steps([period_ms] * 20), "active_slots": [live] * 12,
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "config": config, "model": {"dtype": "bfloat16"},
          "kind": "backlog", "num_slots": 128, "tokens_per_s": rate}


ALL_CALLS = {"gdn_scan": (200.0, 0.4), "moe_gmm": (400.0, 0.4),
             "slot_attn": (50.0, 0.05), "kv_write": (50.0, 0.005)}


def test_readers_on_a_made_up_trace():
  # 1.25 s of stepping at 25 ms = 50 steps: 4 calls of 2 ms a step
  ctx = serve_ctx(ALL_CALLS)
  assert read(MS, ctx) == pytest.approx(8.0)
  _, b = gdn_cost.step_cost(ctx["config"], ctx["model"], 128)
  want = 100 * (b / 819e9) / 8e-3
  assert read(ROOFLINE, ctx) == pytest.approx(want)
  assert 0 < want < 100


def test_held_roofline_counts_this_cells_held_experts():
  roofline = run_lib.load_module("layer_metrics", HELD)
  config = manifest_lib.Manifest().config_file(REAL_CONFIG)
  f, b = roofline.step_cost(config, {"dtype": "bfloat16"}, 128)
  weights = 4 * 16 * 3 * 7168 * 2048 * 2
  assert weights == pytest.approx(5.64e9, rel=1e-3)
  # 128 live slots x 8 choices x 16 / 256 fall on held experts
  assert (f, b) == tuple(4 * x for x in moe_cost.layer_cost(
      64, 16, 7168, 2048, 2))
  ctx = serve_ctx(ALL_CALLS)
  assert read(HELD, ctx) == pytest.approx(100 * (b / 819e9) / 8e-3)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing(metric):
  # the reference lowering, or a parent commit: no such custom call
  assert read(metric, serve_ctx({"kv_write": (25.0, 0.01)})) is None
  assert read(metric, serve_ctx({})) is None
  ctx = serve_ctx(ALL_CALLS)
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  assert read(metric, {"kind": "train"}) is None
  if metric == ROOFLINE:
    # another family's configuration
    ctx = serve_ctx(ALL_CALLS)
    ctx["config"] = {"mamba_d_state": 16, "hidden_size": 2560}
    assert read(metric, ctx) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_on_the_recorded_trace_planes(metric):
  """The trace recorded on the chip (GPT-2 medium serving) reduced as a
  run reduces it: no ``gdn_scan`` is in it, so each reader returns ``None``
  and does not raise."""
  from perfbench.harness import xplane
  with open(RECORDED) as f:
    planes = json.load(f)
  block = xplane.reduce(planes, host_spans=[], n_chips=1)
  ctx = serve_ctx({})
  ctx["trace"] = block
  assert "gdn_scan" not in block.get("custom_calls", {})
  assert read(metric, ctx) is None
