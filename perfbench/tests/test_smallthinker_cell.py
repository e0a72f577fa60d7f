"""The SmallThinker reference and glue through the ``serve_family_even``
runner on a ``backlog`` mix, and the three readers of its cell: a toy
configuration, mix and cell laid into a temporary copy and run end to end on
the CPU; the real manifest with the new entries, whose files name each
other; the parameter, slot and ring arithmetic of ISSUE 42 from the built
tree; ``harness/kv_attn_cost.py`` by hand; the readers on hand-made ``ctx``
(present, absent -> ``None``)."""

import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.harness import kv_attn_cost, manifest as manifest_lib, moe_cost
from perfbench.tests import toy_checkout
from perfbench.tests.test_loop_readers import steps

SEED = str(2 ** 31 + 142)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")
CELL = "toy-smallthinker-backlog"
REAL_CELL = "smallthinker-mixedlen-backlog"
REAL_CONFIG = "smallthinker-21b-a3b"
NEW_METRICS = ["engine.kv_win_attn_ms.backlog", "kv_win_attn_roofline",
               "moe_gmm_roofline.primary"]

TOY_CONFIG = {
    "model_type": "smallthinker", "hidden_size": 64, "head_dim": 8,
    "num_attention_heads": 14, "num_key_value_heads": 2,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "rope_theta": 1500000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "vocab_size": 4096,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input; 0.2 makes
    # the layers matter
    "assumed": {"served_context": 96, "initializer_range": 0.2},
}
TOY_MIX = {
    "kind": "backlog", "population": 64, "queue_target": 4,
    "prompt_len": {"dist": "lognormal", "median": 14, "sigma": 0.8,
                   "min": 4, "max": 48},
    "output_len": {"dist": "uniform", "min": 4, "max": 16},
    "max_total_len": 64, "token_law": {"dist": "uniform"},
    "sampling": "greedy", "ramp_s": 0.5, "ramp_fill": 6,
}
TOY_CELL = {
    "runner": "serve_family_even", "family": "smallthinker",
    # ring_tile 4: rings of 12 rows at chunk 4, gone round by most requests
    "model": {"dtype": "float32", "param_dtype": "float32", "ring_tile": 4},
    "epl_config": {}, "engine": {"num_slots": 6, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-4}},
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("smallthinker")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-smallthinker.json", TOY_CONFIG),
                   ("traffic/toy-mixedlen.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-smallthinker", "source": "none (test)",
                         "file": "perfbench/configs/toy-smallthinker.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-smallthinker",
                           "traffic": "toy-mixedlen", "chips": 1,
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


def test_traced_run_reports_what_a_cpu_trace_holds(checkout):
  """The readers that move ``serve_tokens_per_s`` from spans report as on
  the other backlog cells; the three that need ``slot_attn_kvwin`` or
  ``moe_gmm`` find no such custom call in a CPU run's (recorded, foreign)
  trace and are left out, not null."""
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
      "--control", "fp8,no_window,rope_everywhere", entry="control")
  assert r.returncode == 0, r.stderr[-2000:]
  summary = json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("SUMMARY ")][-1][8:])
  row = summary["served_logit_gap"]
  limit = TOY_CELL["check"]["limits"]["served_logit_gap"]
  assert row["sound_max"] <= limit, row
  for control in ("fp8", "no_window", "rope_everywhere"):
    assert limit < row["control_min"][control], (control, row)


# ----------------------------------------------------------- the manifest --


def test_manifest_loads_and_the_cells_files_name_each_other():
  man = manifest_lib.Manifest()
  cell = man.workload(REAL_CELL)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (
      REAL_CONFIG, "mixedlen-backlog", 1)
  cell_file = man.cell_file(REAL_CELL)
  assert cell_file["family"] == "smallthinker"
  assert cell_file["engine"] == {"num_slots": 48, "prefill_chunk": 32}
  for key in ("engine_why", "runner_why"):
    assert cell_file[key]
  assert cell_file["check"]["limits_why"]
  assert cell_file["runner"] == "serve_family_even"       # ISSUE 42, item 5
  run_lib.load_module("runners", cell_file["runner"])
  mix = man.traffic_file("mixedlen-backlog")
  assert mix["kind"] == "backlog" and mix["population"] == 1024
  assert mix["queue_target"] == 32 and mix["ramp_s"] == 30.0
  assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                               "sigma": 1.0, "min": 256, "max": 14336}
  assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
  assert mix["max_total_len"] == 14848
  reports = {m["name"] for m in man.metrics_for(REAL_CELL, "per_layer")}
  assert set(NEW_METRICS) <= reports
  assert {"engine.kv_write_ms.backlog", "engine.attn_ms.backlog",
          "engine.moe_gmm_ms.backlog", "engine.host_turn_ms.backlog",
          "engine.step_ms.backlog"} <= reports
  assert {m["name"] for m in man.metrics_for(REAL_CELL, "end_to_end")} == {
      "serve_tokens_per_s", "setup_s"}
  for name in NEW_METRICS:
    entry = next(m for m in man.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [REAL_CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    run_lib.load_module("layer_metrics", name)


def test_the_mix_is_what_the_issue_says_of_it():
  """Mean 4,459; 39% of prompts and 73% of prompt tokens beyond the
  window; 6% at the upper clip; nothing cut by the total."""
  import numpy as np
  from perfbench.harness import traffic
  mix = manifest_lib.Manifest().traffic_file("mixedlen-backlog")
  p = traffic.length_quantiles(mix["prompt_len"], mix["population"])
  o = traffic.length_quantiles(mix["output_len"], mix["population"])
  assert p.mean() == pytest.approx(4459, abs=1)
  assert (p > 4096).mean() == pytest.approx(0.39, abs=0.005)
  assert p[p > 4096].sum() / p.sum() == pytest.approx(0.73, abs=0.005)
  assert (p == 14336).mean() == pytest.approx(0.06, abs=0.005)
  assert p.max() + o.max() == mix["max_total_len"]
  assert np.all(p >= 256)


def test_configuration_is_the_catalog_row_cut_in_depth_only():
  from perfbench.reference import smallthinker as st
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  cut = ["num_hidden_layers", "sliding_window_layout", "rope_layout"]
  assert man.configs[REAL_CONFIG]["reduced"] == doc["reduced"] == cut
  assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"]) == (
      8, 52)
  for key in ("sliding_window_layout", "rope_layout"):
    assert doc[key] == doc[key + "_published"][:8] == [0, 1, 1, 1] * 2
    assert doc[key + "_published"] == [0, 1, 1, 1] * 13
  published = {
      "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
      "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
      "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
      "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
      "num_attention_heads": 28, "num_key_value_heads": 4,
      "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
      "sliding_window_size": 4096, "tie_word_embeddings": False,
      "vocab_size": 151936}
  assert {k: doc[k] for k in published} == published
  for key in ("router_input", "rotary", "window", "biases",
              "served_context", "param_dtype", "compute_dtype",
              "float32_exceptions", "weights"):
    assert key in doc["assumed"], key
  cfg = st.SmallThinkerConfig.from_file(doc)
  assert cfg.n_positions == 16384
  # the issue's arithmetic, each term
  assert cfg.attention_params() == 20971520
  assert cfg.expert_params() == 5898240
  assert cfg.layer_params() == 398627840
  assert cfg.param_count() == doc["parameters"]["total"] == 3966937600
  assert doc["parameters"]["bytes_bfloat16"] == 2 * cfg.param_count()
  whole = st.SmallThinkerConfig.from_file(dict(
      doc, num_hidden_layers=52,
      sliding_window_layout=doc["sliding_window_layout_published"],
      rope_layout=doc["rope_layout_published"]))
  assert whole.param_count() == pytest.approx(21.51e9, rel=1e-3)


def test_bytes_slot_and_rings_reckoned_from_the_built_tree():
  """The weights as the program builds them (shapes only) and the cache
  the engine would allocate for the cell: the numbers in the
  configuration's and the cell's files."""
  import jax
  import jax.numpy as jnp
  from easyparallellibrary_tpu.serving import kv_cache as kv_lib
  from perfbench.runners import epl_smallthinker as glue
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  cell_file = man.cell_file(REAL_CELL)
  model, shell_of = glue.build_model(glue.ref_config(doc), cell_file["model"])
  leaves = jax.tree_util.tree_leaves(shell_of(jnp.zeros((1, 8), jnp.int32)))
  assert sum(x.size for x in leaves) == doc["parameters"]["total"]
  assert sum(x.size * x.dtype.itemsize for x in leaves) == doc[
      "parameters"]["bytes_as_built"] == 7936583680
  sizes = cell_file["engine"]
  slots, chunk = sizes["num_slots"], sizes["prefill_chunk"]
  assert model.cfg.ring_length(chunk) == 4224
  layout = kv_lib.cache_layout(model.cfg, slots, chunk)
  assert layout == {"kv_bytes": slots * 2 * 2 * 16416 * 1024,
                    "kv_leaves": 4, "state_bytes": 0, "state_leaves": 0,
                    "window_bytes": slots * 6 * 2 * 4224 * 1024,
                    "window_leaves": 12, "kv_order": "rows"}
  # A slot: 67.2 MB of full leaves + 51.9 MB of rings = 119.1 MB; 269.0
  # MB had the six window layers kept the whole context.
  one = kv_lib.cache_bytes(model.cfg, 1, chunk)
  assert one == 2 * 16416 * 2048 + 6 * 4224 * 2048 == 119144448
  assert 8 * 16416 * 2048 == pytest.approx(269.0e6, rel=1e-3)
  assert kv_lib.kv_leaf_shape(model.cfg, slots, chunk) == (slots, 16416, 512)
  assert kv_lib.kv_leaf_shape(model.cfg, slots, chunk, ring=True) == (
      slots, 4224, 512)
  # 7.94 GB + 48 x 119.1 MB = 13.66 GB of the chip's 16
  assert 13.6e9 < 7936583680 + slots * one < 13.7e9


# ---------------------------------------------------------------- readers --


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


CONFIG = {"num_hidden_layers": 8, "sliding_window_layout": [0, 1, 1, 1] * 2,
          "num_attention_heads": 28, "num_key_value_heads": 4,
          "head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
          "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6,
          "sliding_window_size": 4096}
SMALL = dict(CONFIG, sliding_window_layout=[0, 1, 1, 1],
             sliding_window_size=5)


def serve_ctx(custom_calls, window_s=1.5, closing_s=0.25, period_ms=100.0,
              live=48, rate=1300.0):
  """As the runner hands it over for the real cell: its configuration,
  slots and kind of traffic, by which the roofline's reader finds the
  cell's chunk and mix."""
  config = manifest_lib.Manifest().config_file(REAL_CONFIG)
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s]]},
          "spans": steps([period_ms] * 20), "active_slots": [live] * 12,
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "config": config, "model": {"dtype": "bfloat16"},
          "kind": "backlog", "num_slots": 48, "tokens_per_s": rate}


def test_costs_by_hand():
  assert kv_attn_cost.window_layers(CONFIG) == 6
  assert kv_attn_cost.row_bytes(CONFIG) == 2048
  k = kv_attn_cost.kernel(CONFIG)
  assert k == {"flops": 4 * 28 * 128, "row_bytes": 2048, "most": 4096,
               "union": True, "layers": 6}
  assert k["flops"] / k["row_bytes"] == 7.0     # a decoding slot's, a byte
  primary = run_lib.load_module("layer_metrics", NEW_METRICS[2])
  f, b = primary.step_cost(CONFIG, {"dtype": "bfloat16"}, 48)
  weights = 8 * 64 * 3 * 2560 * 768 * 2
  assert weights == pytest.approx(6.04e9, rel=1e-3)
  assert b == weights + 8 * 48 * 6 * (2 * 2560 + 3 * 768) * 2
  assert (f, b) == tuple(8 * x for x in moe_cost.layer_cost(
      48 * 6, 64, 2560, 768, 2))


def test_request_work_by_hand():
  """A request of 6 prompt and 3 output tokens in chunks of 4 feeds
  positions 0-7 in steps [0, 4), [4, 6), then 6 and 7; behind a window of
  5 a step reads from 4 behind its first query: what the program's
  ``serving/kv_window_rows`` sums, ``min(end, 5 - 1 + end - start)``."""
  from perfbench.harness import dsa_cost
  steps_ = [(0, 4), (4, 6), (6, 7), (7, 8)]
  f, b = dsa_cost.request_work(kv_attn_cost.kernel(SMALL), 6, 3, 4)
  assert f == 4 * 28 * 128 * sum(min(t + 1, 5) for t in range(8))
  assert b == 2048 * sum(min(e, 4 + e - s) for s, e in steps_)


def test_mix_mean_is_over_every_pair_cut_at_the_total():
  import numpy as np
  from perfbench.harness import dsa_cost, traffic
  mix = {"prompt_len": {"dist": "uniform", "min": 8, "max": 40},
         "output_len": {"dist": "uniform", "min": 4, "max": 16},
         "max_total_len": 48}
  (f, b), mean_out = kv_attn_cost.mix_mean_work(SMALL, mix, 4)
  P = traffic.length_quantiles(mix["prompt_len"], dsa_cost.GRID)
  O = traffic.length_quantiles(mix["output_len"], dsa_cost.GRID)
  pairs = [(int(p), int(min(o, 48 - p))) for p in P for o in O]
  assert mean_out == pytest.approx(np.mean([o for _, o in pairs]))
  assert mean_out < np.mean(O)                        # the cut binds here
  k = kv_attn_cost.kernel(SMALL)
  want = np.mean([dsa_cost.request_work(k, p, o, 4) for p, o in pairs],
                 axis=0)
  assert (f, b) == (pytest.approx(3 * want[0]), pytest.approx(3 * want[1]))


def test_mean_work_of_the_cells_mix():
  """What a request of the cell costs the six window layers: its rows under
  the window are 61% of its rows under the bound (the rings spare the
  window layers the rest), and the requirement is memory-bound."""
  man = manifest_lib.Manifest()
  config, mix = man.config_file(REAL_CONFIG), man.traffic_file(
      "mixedlen-backlog")
  (f, b), mean_out = kv_attn_cost.mix_mean_work(config, mix, 32)
  assert mean_out == pytest.approx(320, abs=0.5)    # the total never cuts
  whole = dict(config, sliding_window_size=1 << 20)
  (_, b_whole), _ = kv_attn_cost.mix_mean_work(whole, mix, 32)
  assert 0.3 < b / b_whole < 0.9
  assert f / 197e12 < b / 819e9


def test_readers_on_a_made_up_trace():
  # 1.25 s of stepping at 25 ms = 50 steps; 12 calls a step, 0.2 s of the
  # kernel in all = 4 ms a step; 0.5 s of moe_gmm = 10 ms a step
  ctx = serve_ctx({"slot_attn_kvwin": (600.0, 0.2), "moe_gmm": (800.0, 0.5),
                   "kv_write": (400.0, 0.01)}, period_ms=25.0)
  assert read(NEW_METRICS[0], ctx) == pytest.approx(4.0)
  # 1300 tokens/s over a mean output of 320 is 4.06 requests/s, each
  # bringing the mix's mean work; the kernel is busy 4 ms of a 25 ms period
  man = manifest_lib.Manifest()
  (f, b), mean_out = kv_attn_cost.mix_mean_work(
      ctx["config"], man.traffic_file("mixedlen-backlog"), 32)
  per_s = 1300.0 / mean_out
  want = 100 * max(per_s * f / 197e12, per_s * b / 819e9) / 0.16
  assert read(NEW_METRICS[1], ctx) == pytest.approx(want)
  assert 0 < want < 100
  primary = run_lib.load_module("layer_metrics", NEW_METRICS[2])
  _, nbytes = primary.step_cost(ctx["config"], {"dtype": "bfloat16"}, 48)
  want = 100 * (nbytes / 819e9) / 10e-3
  assert read(NEW_METRICS[2], ctx) == pytest.approx(want)
  assert 0 < want < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing(metric):
  # the reference lowering, or a parent commit: no such custom call
  assert read(metric, serve_ctx({"kv_write": (25.0, 0.01)})) is None
  assert read(metric, serve_ctx({})) is None
  ctx = serve_ctx({"slot_attn_kvwin": (150.0, 0.05),
                   "moe_gmm": (400.0, 0.25)})
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  assert read(metric, {"kind": "train"}) is None


def test_rooflines_need_this_configuration_and_this_cell():
  calls = {"slot_attn_kvwin": (150.0, 0.05), "moe_gmm": (400.0, 0.25)}
  for change in ({"num_slots": 40}, {"kind": "open_loop"},
                 {"tokens_per_s": None}):
    ctx = dict(serve_ctx(calls), **change)
    assert read(NEW_METRICS[1], ctx) is None, change
    assert read(NEW_METRICS[0], ctx) is not None
  other = serve_ctx(calls)
  other["config"] = {"n_routed_experts": 64, "first_k_dense_replace": 1,
                     "num_hidden_layers": 8}
  assert read(NEW_METRICS[1], other) is None
  assert read(NEW_METRICS[2], other) is None
