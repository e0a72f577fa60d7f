"""The GLM-5 reference and glue through the ``serve_family_even_counters``
runner on a mesh that divides the host over an ``expert`` axis: a toy
configuration, mix and cell laid into a temporary copy and run end to end
on four CPU devices; the real manifest with the new entries, whose files
name each other; the parameter and byte arithmetic of ISSUE 47 reckoned
from the program's built tree, a chip's and the host's; the mix's
quantiles by membership; ``harness/ep_cost.py`` by hand at one small
shape; the new readers on hand-made ``ctx``."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import dsa_cost, ep_cost, moe_cost
from perfbench.harness import manifest as manifest_lib
from perfbench.tests import toy_checkout

SEED = str(2 ** 31 + 47)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_4chip.json")
CELL = "toy-glm5-backlog-4chip"
REAL_CELL = "glm5-agentctx-backlog-4chip"
REAL_CONFIG = "glm-5"
FOUR = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}

TOY_CONFIG = {
    "model_type": "glm_moe_dsa", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_parameters": {"rope_theta": 1000000,
                                          "rope_type": "default"},
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": 4,
    "n_routed_experts": 8, "n_routed_experts_published": 16,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-5, "vocab_size": 4096,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input; 0.2 makes
    # the layers matter
    "assumed": {"served_context": 96, "initializer_range": 0.2,
                "e_score_correction_bias_std": 0.05, "experts_first": 4},
}
TOY_MIX = {
    "kind": "backlog", "population": 256, "queue_target": 8,
    "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
    "output_len": {"dist": "uniform", "min": 4, "max": 16},
    "max_total_len": 64, "token_law": {"dist": "uniform"},
    "sampling": "greedy", "ramp_s": 0.5, "ramp_fill": 8,
}
TOY_CELL = {
    "runner": "serve_family_even_counters", "family": "glm_moe_dsa",
    "model": {"dtype": "float32", "param_dtype": "float32"},
    "epl_config": {"cluster": {"mesh_shape": "expert:4"}},
    "engine": {"num_slots": 8, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-4}},
}
EP = ["moe_gmm_roofline.ep", "dsa_index_roofline.ep", "sel_attn_roofline.ep"]
NEW_METRICS = EP + ["engine.exchange_ms.backlog", "comm.exposed_pct.backlog",
                    "engine.chip_imbalance_pct"]
GAINED = ["engine.kv_write_ms.backlog", "engine.moe_gmm_ms.backlog",
          "engine.host_turn_ms.backlog", "engine.index_ms.backlog",
          "engine.sel_attn_ms.backlog"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("glm5")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-glm5.json", TOY_CONFIG),
                   ("traffic/toy-agentctx.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-glm5", "source": "none (test)",
                         "file": "perfbench/configs/toy-glm5.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-glm5",
                           "traffic": "toy-agentctx", "chips": 4,
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


def test_toy_cell_end_to_end_on_four_devices(checkout):
  r = toy_checkout.run_cell(checkout, "--workload", CELL, "--seed", SEED,
                            "--seconds", "2", "--trace", "0", env=FOUR)
  assert r.returncode == 0, r.stderr[-3000:]
  doc = toy_checkout.last_line(r)
  assert set(doc["metrics"]) == {"serve_tokens_per_s", "setup_s"}
  assert doc["correct"] is True and doc["failed"] == 0, r.stdout[-2000:]
  assert doc["attempted"] > 0 and doc["device"]["count"] == 4
  assert "start from the same weights" in r.stdout
  # the engine adopted the mesh the glue built and divided itself over it
  assert "divided over expert:4" in r.stderr + r.stdout


def test_traced_run_hands_the_counters_to_their_reader(checkout):
  """The span readers report as on the other backlog cells; the imbalance
  is a program counter and reads on the CPU too; the kernel readers find
  none of their names in the recorded (foreign) trace and are left out."""
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seed", SEED, "--seconds", "2",
      "--trace", "1", env=FOUR, prelude=toy_checkout.FAKE_TRACE % RECORDED)
  assert r.returncode == 0, r.stderr[-3000:]
  doc = toy_checkout.last_line(r)
  for name in ("sched.host_ms.backlog", "engine.step_ms.backlog",
               "engine.dispatch_ms.backlog", "engine.stall_ms.backlog",
               "engine.slot_occupancy", "engine.chip_imbalance_pct"):
    assert doc["metrics"][name]["value"] is not None, name
  assert doc["metrics"]["engine.chip_imbalance_pct"]["value"] >= 0.0
  assert not set(EP) & set(doc["metrics"])


def test_counters_runner_leaves_serve_family_as_it_found_it(monkeypatch):
  from perfbench.runners import serve_family, serve_family_even
  from perfbench.runners import serve_family_even_counters as runner
  events = [{"ph": "C", "name": "serving/chip_live_max",
             "args": {"value": v}} for v in (3, 5)] + [
                 {"ph": "C", "name": "other/x", "args": {"value": 1}},
                 {"ph": "B", "name": "serving/plan", "cat": "serving",
                  "tid": 1, "ts": 0.0}]
  def fake(**kw):
    serve_family._span_pairs(events, 0.0)
    return {"layer_ctx": {}, **kw}
  monkeypatch.setattr(serve_family_even, "run", fake)
  theirs = serve_family._span_pairs
  out = runner.run(seed=1)
  assert out["layer_ctx"]["counters"] == {"serving/chip_live_max": [3, 5]}
  assert serve_family._span_pairs is theirs
  monkeypatch.setattr(serve_family_even, "run", lambda **kw: {"correct": 1})
  assert runner.run(seed=1) == {"correct": 1}


# ----------------------------------------------------------- the manifest --


def test_manifest_loads_and_the_cells_files_name_each_other():
  man = manifest_lib.Manifest()
  cell = man.workload(REAL_CELL)
  assert (cell["chips"], cell["config"], cell["traffic"]) == (
      4, REAL_CONFIG, "agentctx-backlog")
  cell_file = man.cell_file(REAL_CELL)
  assert (cell_file["runner"], cell_file["family"]) == (
      "serve_family_even_counters", "glm_moe_dsa")
  assert cell_file["engine"] == {"num_slots": 128, "prefill_chunk": 32}
  assert cell_file["epl_config"] == {"cluster": {"mesh_shape": "expert:4"}}
  assert cell_file["check"]["sample"] == 4
  assert cell_file["check"]["limits"]["served_logit_gap"] > 0
  for kind, name in (("reference", "glm_moe_dsa"),
                     ("runners", "epl_glm_moe_dsa"),
                     ("runners", "serve_family_even"),
                     ("runners", "serve_family_even_counters"),
                     ("harness", "ep_cost")):
    assert os.path.exists(os.path.join(toy_checkout.BENCH, kind,
                                       name + ".py"))
  mix = man.traffic_file(cell["traffic"])
  assert {k: mix[k] for k in mix if k != "why"} == {
      "kind": "backlog", "population": 1024, "queue_target": 64,
      "prompt_len": {"dist": "uniform", "min": 4096, "max": 10240},
      "output_len": {"dist": "uniform", "min": 128, "max": 384},
      "max_total_len": 10624, "token_law": {"dist": "uniform"},
      "sampling": "greedy", "ramp_s": 40.0, "ramp_fill": 128}
  names = [m["name"] for m in man.metrics_for(REAL_CELL, "per_layer")]
  assert set(NEW_METRICS + GAINED) <= set(names)
  for name in ("engine.step_ms.backlog", "sched.host_ms.backlog",
               "engine.dispatch_ms.backlog", "engine.stall_ms.backlog",
               "engine.slot_occupancy"):
    assert name in names, name
  # Readers that would count the whole host's work against a chip's time,
  # or find nothing.
  assert not {"moe_gmm_roofline", "moe_gmm_roofline.held",
              "dsa_index_roofline", "sel_attn_roofline",
              "engine.attn_ms.backlog"} & set(names)
  assert [m["name"] for m in man.metrics_for(REAL_CELL, "end_to_end")] == [
      "serve_tokens_per_s", "setup_s"]
  # no other cell gained a metric
  for other in man.workloads:
    if other != REAL_CELL:
      assert not set(NEW_METRICS) & {
          m["name"] for m in man.metrics_for(other, "per_layer")}, other
  four = [w["name"] for w in man.doc["workloads"] if w["chips"] == 4]
  assert REAL_CELL in four and len(four) <= len(man.doc["workloads"]) // 4


def test_configuration_is_the_catalog_row_at_the_hosts_share():
  from perfbench.reference import glm_moe_dsa as glm
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  reduced = ["num_hidden_layers", "first_k_dense_replace",
             "n_routed_experts", "vocab_size"]
  assert man.configs[REAL_CONFIG]["reduced"] == reduced == doc["reduced"]
  for key, here, published in (("num_hidden_layers", 6, 78),
                               ("first_k_dense_replace", 1, 3),
                               ("n_routed_experts", 64, 256),
                               ("vocab_size", 19360, 154880)):
    assert (doc[key], doc[key + "_published"]) == (here, published)
  assert 8 * doc["vocab_size"] == doc["vocab_size_published"]
  published = {
      "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
      "head_dim": 64, "hidden_size": 6144, "index_head_dim": 128,
      "index_n_heads": 32, "index_topk": 2048,
      "indexer_rope_interleave": True, "intermediate_size": 12288,
      "kv_lora_rank": 512, "max_position_embeddings": 202752,
      "moe_intermediate_size": 2048, "moe_layer_freq": 1,
      "model_type": "glm_moe_dsa", "n_group": 1, "n_shared_experts": 1,
      "norm_topk_prob": True, "num_attention_heads": 64,
      "num_experts_per_tok": 8, "num_key_value_heads": 64,
      "num_nextn_predict_layers": 1, "q_lora_rank": 2048,
      "qk_head_dim": 256, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
      "rms_norm_eps": 1e-05, "rope_interleave": True,
      "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
      "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
      "tie_word_embeddings": False, "topk_group": 1,
      "topk_method": "noaux_tc", "v_head_dim": 256}
  assert {k: doc[k] for k in published} == published
  for said in ("16 chips share each layer", "chips 0-3", "stage 1 of 13",
               "19360 of 154880", "16 j"):
    assert said in doc["deployment"], said
  for key in ("rotary", "indexer_left_out", "e_score_correction_bias_why",
              "selection_ties", "served_context", "prediction_module",
              "float32_exceptions"):
    assert key in doc["assumed"], key
  cfg = glm.GlmMoeDsaConfig.from_file(doc)
  assert (cfg.n_positions, cfg.router_width, cfg.experts_held) == (
      10752, 256, (0, 64))
  # the issue's arithmetic, each term
  D = 6144
  parts = cfg.attention_params()
  assert (parts["mixer"], parts["indexer"]) == (165_022_208, 9_371_904)
  assert cfg.expert_params() == 3 * D * 2048 == 37_748_736
  assert D * 256 + 256 == 1_573_120                    # router and bias
  assert 3 * D * 12288 == 226_492_416                  # the dense MLP
  assert cfg.param_count(experts_a_layer=16) == 4_727_340_800   # a chip
  assert cfg.param_count() == 13_787_037_440                     # the host
  whole = glm.GlmMoeDsaConfig.from_file(dict(
      doc, num_hidden_layers=78, first_k_dense_replace=3,
      n_routed_experts=256, vocab_size=154880))
  assert whole.param_count() == pytest.approx(744e9, rel=2e-2)


def test_bytes_and_cache_reckoned_from_the_built_tree():
  """The weights as the program builds them (shapes only: the host's tree,
  of which a chip holds all but three quarters of the stacks) and the cache
  the engine would allocate for the cell: ISSUE 47's numbers."""
  import jax
  import jax.numpy as jnp
  from easyparallellibrary_tpu.serving import kv_cache as kv_lib
  from perfbench.runners import epl_glm_moe_dsa as glue
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  cell_file = man.cell_file(REAL_CELL)
  model, shell_of = glue.build_model(glue.ref_config(doc), cell_file["model"])
  assert model.cfg.experts_held == (0, 64)
  assert model.cfg.layer_kinds() == ("sparse_latent",) * 6
  shell = shell_of(jnp.zeros((1, 8), jnp.int32))
  size = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
  assert size(shell) == 13_787_037_440
  stacks = sum(size(shell[f"block_{i}"]["moe"][name]) for i in range(1, 6)
               for name in ("experts_gate_up", "experts_down"))
  assert stacks == 5 * 64 * 37_748_736
  chip = size(shell) - stacks * 3 // 4
  assert chip == 4_727_340_800
  # bfloat16 but the routers, their biases and the norms' gains
  nbytes = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shell)) - 2 * stacks * 3 // 4
  assert 9.45e9 < nbytes < 9.48e9
  sizes = cell_file["engine"]
  leaves = kv_lib.cache_leaves(model.cfg, sizes["num_slots"] // 4,
                               sizes["prefill_chunk"])
  shapes = {tuple(x.shape) for x in jax.tree_util.tree_leaves(leaves)}
  assert shapes == {(32, 10784, 1, 576), (32, 10784, 128)}
  cache = sum(x.size * x.dtype.itemsize
              for x in jax.tree_util.tree_leaves(leaves))
  assert cache == 32 * 6 * 10784 * 1408 == 2_915_303_424


def test_the_mixs_quantiles_by_membership():
  """Every seed is offered the mix's own 1024 prompt and output lengths
  (the quantiles of their uniform laws), in some order, within the served
  context, with ids from the host's slice of the vocabulary."""
  from perfbench.harness import traffic as traffic_lib
  from perfbench.runners import serve_family_even as even
  man = manifest_lib.Manifest()
  mix = man.traffic_file("agentctx-backlog")
  vocab = man.config_file(REAL_CONFIG)["vocab_size"]
  want_p = sorted(traffic_lib.length_quantiles(mix["prompt_len"],
                                               1024).tolist())
  want_o = sorted(traffic_lib.length_quantiles(mix["output_len"],
                                               1024).tolist())
  assert (want_p[0], want_p[-1]) == (4099, 10237) or (
      4096 <= want_p[0] and want_p[-1] <= 10240)
  for seed in (int(SEED), 7):
    reqs = even.backlog(mix, seed, vocab)
    assert sorted(len(r.prompt) for r in reqs) == want_p
    assert sorted(r.max_new_tokens for r in reqs) == want_o
    assert all(len(r.prompt) + r.max_new_tokens <= mix["max_total_len"]
               for r in reqs)
    assert max(int(r.prompt.max()) for r in reqs[:32]) < vocab
  # every prompt is 2 to 5 times the selection
  assert want_p[0] >= 2 * 2048 and want_p[-1] <= 5 * 2048


# ------------------------------------------------------------ the readers --


def test_ep_cost_by_hand():
  config = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
            "n_routed_experts": 8, "hidden_size": 4,
            "moe_intermediate_size": 2, "num_experts_per_tok": 2,
            "index_n_heads": 2, "index_head_dim": 8, "index_topk": 4,
            "kv_lora_rank": 6, "qk_rope_head_dim": 2,
            "num_attention_heads": 3}
  # two expert layers, two experts a chip of four; 40 held assignments a
  # step over layers and chips: five arrive at a chip's experts a layer
  f, b = ep_cost.moe_step_cost(config, {}, 4, 40.0)
  weights = 2 * 3 * 4 * 2 * 2
  rows = 5 * (2 * 4 + 3 * 2) * 2
  assert (f, b) == (2 * 5 * 6 * 4 * 2, 2 * (weights + rows))
  assert (f, b) == tuple(2 * x for x in moe_cost.layer_cost(5, 2, 4, 2))
  sizes = dsa_cost.sizes(ep_cost.all_selecting(config))
  assert sizes["dsa_index"] == dict(flops=2 * 2 * 8, row_bytes=16,
                                    most=None, union=True, layers=3)
  assert sizes["slot_attn_sel"] == dict(flops=2 * 3 * (8 + 6), row_bytes=16,
                                        most=4, union=False, layers=3)
  assert sizes["slot_attn_win"]["layers"] == 0


def test_readers_on_hand_made_ctx():
  from perfbench import run as run_lib
  read = lambda name, ctx: run_lib.load_module("layer_metrics",
                                               name).read(ctx)
  ctx = {"chips": 4, "counters": {
      "serving/chip_live_max": [30, 50], "serving/flat_positions": [80, 120]}}
  # mean fullest 40 against a mean chip of 100 / 4
  assert read("engine.chip_imbalance_pct", ctx) == pytest.approx(60.0)
  assert read("engine.chip_imbalance_pct", {"chips": 4}) is None
  assert read("engine.chip_imbalance_pct", dict(ctx, chips=1)) is None
  block = {"collective_s": 0.2, "exposed_collective_s": 0.1, "window_s": 2.0}
  assert read("comm.exposed_pct.backlog",
              {"chips": 4, "trace": block}) == pytest.approx(5.0)
  assert read("comm.exposed_pct.backlog", {"chips": 1, "trace": block}) is None
  # a shard_map body's own collectives carry the primitive's name on the
  # trace (my chip run, PR 47: ``all_to_all``, ``all_gather``)
  own = dict(block, collective_s=0.0, exposed_collective_s=0.0,
             op_seconds={"all_to_all": 0.05, "all_gather": 0.03,
                         "fusion": 1.0})
  assert read("comm.exposed_pct.backlog",
              {"chips": 4, "trace": own}) == pytest.approx(4.0)
  assert read("comm.exposed_pct.backlog", {"chips": 4, "trace": dict(
      own, op_seconds={"fusion": 1.0})}) is None
  assert read("engine.exchange_ms.backlog", {}) is None
  # two steps of 10 ms in a 20 ms window; 3 ms of all-to-all a chip
  spans = [("serving/dispatch", i * 10e6, i * 10e6 + 1e6) for i in range(3)]
  block = {"window_s": 0.02, "idle_gaps": [],
           "op_seconds": {"all_to_all": 0.002, "all-to-all": 0.001,
                          "all_gather": 0.004, "fusion": 0.01}}
  assert read("engine.exchange_ms.backlog",
              {"trace": block, "spans": spans}) == pytest.approx(1.5)
  for name in EP:
    assert read(name, {}) is None
    assert read(name, {"chips": 1, "config": {}, "peaks": {}}) is None
