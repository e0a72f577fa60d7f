"""The dots3-note-prev reference and glue through the ``serve_family_even``
runner (``serve_family`` on a backlog taken in an even order) on a
``backlog`` mix, the order itself, and the seven readers of its cell: a toy
configuration, mix and cell laid into a temporary copy and run end to end
on the CPU; the real manifest with the new entries, whose files name each
other; the parameter and cache arithmetic of ISSUE 39 from the program's
built tree; ``harness/dsa_cost.py`` by hand at one small shape; every new
reader on hand-made ``ctx`` and on the trace recorded on the chip (which
holds none of the new kernels: ``None``, not a number)."""

import json
import os

import numpy as np
import pytest

from perfbench import run as run_lib
from perfbench.harness import dsa_cost, manifest as manifest_lib, moe_cost
from perfbench.tests import toy_checkout
from perfbench.tests.test_loop_readers import steps

SEED = str(2 ** 31 + 39)
RECORDED = os.path.join(toy_checkout.HERE, "data", "trace_planes_1chip.json")
CELL = "toy-dots3-backlog"
REAL_CELL = "dots3note-longdoc-backlog"
REAL_CONFIG = "dots3-note-prev"

PERIOD = ["full_attention", "full_attention", "sliding_attention",
          "sliding_attention", "sliding_attention"]
TOY_CONFIG = {
    "model_type": "dots3_note", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "layer_types": PERIOD, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 80000000, "index_n_heads": 2, "index_head_dim": 16,
    "index_topk": 4, "sliding_window_size": 5,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 48, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    "n_routed_experts": 3, "n_routed_experts_published": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "vocab_size": 4096,
    "max_position_embeddings": 4096,
    # N(0, 0.02) at width 64 gives a model that copies its input; 0.2 makes
    # the layers matter
    "assumed": {"served_context": 96, "initializer_range": 0.2,
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
    "runner": "serve_family_even", "family": "dots3_note",
    "model": {"dtype": "float32", "param_dtype": "float32", "ring_tile": 8},
    "epl_config": {}, "engine": {"num_slots": 6, "prefill_chunk": 4},
    "trace_seconds": 0.5,
    # float32 on both sides: rounding apart, the served token is the
    # reference's best
    "check": {"sample": 64, "limits": {"served_logit_gap": 1e-4}},
}
MS = ["engine.index_ms.backlog", "engine.sel_attn_ms.backlog",
      "engine.win_attn_ms.backlog"]
ROOFLINES = ["dsa_index_roofline", "sel_attn_roofline", "win_attn_roofline"]
HELD = "moe_gmm_roofline.held"
NEW_METRICS = MS + ROOFLINES + [HELD]
KERNEL_OF = dict(zip(MS + ROOFLINES, 2 * [dsa_cost.DSA_INDEX,
                                          dsa_cost.SEL_ATTN,
                                          dsa_cost.WIN_ATTN]))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  co = toy_checkout.make(str(tmp_path_factory.mktemp("dots3")))
  bench = os.path.join(co, "perfbench")
  for rel, doc in (("configs/toy-dots3.json", TOY_CONFIG),
                   ("traffic/toy-longdoc.json", TOY_MIX),
                   (f"workloads/{CELL}.json", TOY_CELL)):
    with open(os.path.join(bench, rel), "w") as f:
      json.dump(doc, f)
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  doc["configs"].append({"name": "toy-dots3", "source": "none (test)",
                         "file": "perfbench/configs/toy-dots3.json",
                         "reduced": [], "why": "CPU test size"})
  doc["workloads"].append({"name": CELL, "config": "toy-dots3",
                           "traffic": "toy-longdoc", "chips": 1,
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
  """The no-list readers that move the throughput report from the same
  spans as on the other backlog cells; the seven new readers find none of
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
  assert not set(NEW_METRICS) & set(doc["metrics"])


def test_controls_are_read_on_the_toy_cell(checkout):
  r = toy_checkout.run_cell(
      checkout, "--workload", CELL, "--seeds", "5", "6", "--seconds", "1.5",
      "--control", "fp8,bf16router,bf16index", entry="control")
  assert r.returncode == 0, r.stderr[-2000:]
  summary = json.loads([l for l in r.stdout.splitlines()
                        if l.startswith("SUMMARY ")][-1][8:])
  row = summary["served_logit_gap"]
  limit = TOY_CELL["check"]["limits"]["served_logit_gap"]
  assert row["sound_max"] <= limit < row["control_min"]["fp8"], row
  assert {"bf16router", "bf16index"} <= set(row["control_min"]), row


# -------------------------------------------------------- the even order --


@pytest.mark.parametrize("n", [1024, 256, 1000, 7])
def test_even_order_is_a_permutation_balanced_at_every_scale(n):
  from perfbench.runners import serve_family_even as even
  order = even.even_order(n, np.random.default_rng(n))
  assert sorted(order.tolist()) == list(range(n))
  if n & (n - 1):
    return
  for j in range(1, n.bit_length()):
    w = 1 << j
    slices = order.reshape(-1, w) * w // n
    assert (np.sort(slices, 1) == np.arange(w)).all(), j


def test_even_backlog_is_the_mixs_population_in_another_order():
  """The same lengths as ``traffic.backlog`` gives (every seed's set), the
  seed still deciding the order, the pairing and the ids; and what the
  order is for: any 45 consecutive requests are the same work within 5%,
  where a free permutation's differ by 10% and more."""
  from perfbench.harness import traffic as traffic_lib
  from perfbench.runners import serve_family_even as even
  mix = manifest_lib.Manifest().traffic_file("longdoc-backlog")
  free = traffic_lib.backlog(mix, int(SEED), 19008)
  a, b = (even.backlog(mix, int(SEED) + i, 19008) for i in range(2))
  sizes = lambda reqs: (sorted(len(r.prompt) for r in reqs),
                        sorted(r.max_new_tokens for r in reqs))
  assert sizes(a) == sizes(b) == sizes(free)
  assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
  assert [r.uid for r in a] == list(range(1024))
  assert all(r.prompt.max() < 19008 and r.due_s == 0.0 for r in a)
  assert np.array_equal(a[0].prompt,
                        even.backlog(mix, int(SEED), 19008)[0].prompt)

  def swing(reqs, of):
    sums = np.convolve([of(r) for r in reqs[:256]], np.ones(45), "valid")
    return (sums.max() - sums.min()) / sums.mean()
  for of in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
    assert swing(a, of) < 0.05 < swing(free, of), (swing(a, of),
                                                    swing(free, of))


def test_even_runner_leaves_serve_family_as_it_found_it(monkeypatch):
  from perfbench.runners import serve_family, serve_family_even as even
  seen = {}
  monkeypatch.setattr(serve_family, "run", lambda **kw: seen.update(
      kw, generator=serve_family.traffic_lib.backlog))
  theirs = serve_family.traffic_lib
  even.run(seed=1)
  assert seen == {"seed": 1, "generator": even.backlog}
  assert serve_family.traffic_lib is theirs


# ----------------------------------------------------------- the manifest --


def test_manifest_loads_and_the_cells_files_name_each_other():
  man = manifest_lib.Manifest()
  cell = man.workload(REAL_CELL)
  assert (cell["chips"], cell["config"], cell["traffic"]) == (
      1, REAL_CONFIG, "longdoc-backlog")
  cell_file = man.cell_file(REAL_CELL)
  assert (cell_file["runner"], cell_file["family"]) == (
      "serve_family_even", "dots3_note")
  assert cell_file["engine"] == {"num_slots": 32, "prefill_chunk": 32}
  assert cell_file["trace_seconds"] == 1.5
  assert cell_file["check"]["sample"] == 4
  for kind, name in (("reference", "dots3_note"),
                     ("runners", "epl_dots3_note"),
                     ("runners", "serve_family"),
                     ("runners", "serve_family_even"),
                     ("harness", "dsa_cost")):
    assert os.path.exists(os.path.join(toy_checkout.BENCH, kind,
                                       name + ".py"))
  mix = man.traffic_file(cell["traffic"])
  assert {k: mix[k] for k in mix if k != "why"} == {
      "kind": "backlog", "population": 1024, "queue_target": 32,
      "prompt_len": {"dist": "uniform", "min": 4096, "max": 12288},
      "output_len": {"dist": "uniform", "min": 128, "max": 512},
      "max_total_len": 12800, "token_law": {"dist": "uniform"},
      "sampling": "greedy", "ramp_s": 30.0, "ramp_fill": 32}
  names = [m["name"] for m in man.metrics_for(REAL_CELL, "per_layer")]
  assert set(NEW_METRICS) <= set(names)
  for name in ("engine.kv_write_ms.backlog", "engine.moe_gmm_ms.backlog",
               "engine.host_turn_ms.backlog", "engine.step_ms.backlog",
               "sched.host_ms.backlog", "engine.dispatch_ms.backlog",
               "engine.stall_ms.backlog", "engine.slot_occupancy"):
    assert name in names, name
  # Readers that would find nothing, or count absent experts' rows.
  assert not {"engine.attn_ms.backlog", "moe_gmm_roofline"} & set(names)
  assert [m["name"] for m in man.metrics_for(REAL_CELL, "end_to_end")] == [
      "serve_tokens_per_s", "setup_s"]
  # no other cell gained a metric
  for other in ("gpt2m-offline-backlog", "glm47flash-agent-backlog",
                "lfm2moe-chat-steady"):
    assert not set(NEW_METRICS) & {
        m["name"] for m in man.metrics_for(other, "per_layer")}
  # 7 of 24 cells, one of them on four chips
  assert len(man.doc["workloads"]) == 7
  assert sum(w["chips"] == 4 for w in man.doc["workloads"]) == 1


def test_configuration_is_the_catalog_row_at_one_chips_share():
  from perfbench.reference import dots3_note as dots
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  reduced = ["num_hidden_layers", "layer_types", "n_routed_experts",
             "vocab_size"]
  assert man.configs[REAL_CONFIG]["reduced"] == reduced == doc["reduced"]
  assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"]) == (
      5, 46)
  # the leading dense layer and one whole period
  assert doc["layer_types"] == doc["layer_types_published"][:5] == PERIOD
  assert len(doc["layer_types_published"]) == 46
  assert doc["layer_types_published"].count("full_attention") == 13
  assert (doc["n_routed_experts"], doc["n_routed_experts_published"]) == (
      32, 256)
  assert (doc["vocab_size"], doc["vocab_size_published"]) == (19008, 152064)
  assert 8 * doc["vocab_size"] == doc["vocab_size_published"]
  published = {
      "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
      "attention_gate_type": "headwise", "first_k_dense_replace": 1,
      "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
      "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
      "kv_lora_rank": 512, "max_position_embeddings": 524288,
      "model_type": "dots3_note", "moe_intermediate_size": 1536,
      "moe_layer_freq": 1, "n_shared_experts": 1, "norm_topk_prob": True,
      "num_attention_heads": 128, "num_experts_per_tok": 8,
      "num_key_value_heads": 128, "q_lora_rank": 1024,
      "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
      "rope_scaling": None, "rope_theta": 80000000,
      "routed_scaling_factor": 1, "scoring_func": "sigmoid",
      "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
      "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
      "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
      "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
      "swa_rope_theta": 50000, "swa_v_head_dim": 128,
      "tie_word_embeddings": False, "topk_method": "noaux_tc",
      "v_head_dim": 128}
  assert {k: doc[k] for k in published} == published
  for said in ("eight", "32 a chip", "19008 of 152064", "twelve"):
    assert said in doc["deployment"].lower() or said in doc["deployment"]
  cfg = dots.Dots3NoteConfig.from_file(doc)
  assert (cfg.n_positions, cfg.router_width, cfg.experts_first,
          cfg.n_routed_experts) == (12800, 256, 0, 32)
  # the issue's arithmetic, each term
  D, Fe = 5120, 1536
  assert 3 * D * Fe == 23_592_960                      # one expert
  assert 3 * D * 13824 == 212_336_640                  # the dense MLP
  assert D * 256 + 256 == 1_310_976                    # router and bias
  assert cfg.param_count() == 4_087_154_176
  # whole: 46 layers, every expert, the whole vocabulary
  whole = dots.Dots3NoteConfig.from_file(dict(
      doc, num_hidden_layers=46, layer_types=doc["layer_types_published"],
      n_routed_experts=256, vocab_size=152064))
  assert whole.param_count() == pytest.approx(279.6e9, rel=1e-3)


def test_bytes_and_cache_reckoned_from_the_built_tree():
  """The weights as the program builds them (shapes only) and the cache
  the engine would allocate for the cell: ISSUE 39's numbers."""
  import jax
  import jax.numpy as jnp
  from easyparallellibrary_tpu.serving import kv_cache as kv_lib
  from perfbench.runners import epl_dots3_note as glue
  man = manifest_lib.Manifest()
  doc = man.config_file(REAL_CONFIG)
  cell_file = man.cell_file(REAL_CELL)
  model, shell_of = glue.build_model(glue.ref_config(doc), cell_file["model"])
  assert model.cfg.experts_held == (0, 32)
  leaves = jax.tree_util.tree_leaves(shell_of(jnp.zeros((1, 8), jnp.int32)))
  assert sum(x.size for x in leaves) == 4_087_154_176
  nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
  # bfloat16 but the routers, their biases and the norms' gains
  assert 8.17e9 < nbytes < 8.19e9
  sizes = cell_file["engine"]
  layout = kv_lib.cache_layout(model.cfg, sizes["num_slots"],
                               sizes["prefill_chunk"])
  assert layout == {
      "kv_bytes": 0, "kv_leaves": 0, "state_bytes": 0, "state_leaves": 0,
      "latent_bytes": 2 * 32 * 12832 * 576 * 2, "latent_leaves": 2,
      "index_bytes": 2 * 32 * 12832 * 128 * 2, "index_leaves": 2,
      "window_bytes": 3 * 32 * 640 * 1088 * 2, "window_leaves": 3,
      "kv_order": "positions"}
  slot = kv_lib.cache_bytes(model.cfg, 32, 32) / 32
  assert slot == 2 * 12832 * 1408 + 3 * 640 * 2176 == pytest.approx(40.3e6,
                                                                    rel=2e-3)
  # 8.17 GB + 1.29 GB = 9.46 GB before temporaries, 59% of the chip
  assert 9.45e9 < nbytes + 32 * slot < 9.48e9
  # the windows' bytes do not depend on the served context
  import dataclasses
  longer = dataclasses.replace(model.cfg, max_seq_len=131072)
  assert kv_lib.cache_layout(longer, 32, 32)["window_bytes"] == layout[
      "window_bytes"]


def test_selecting_layers_draw_absorbs_the_rescale():
  """The reference's ONE rule for the draw: a FULL layer's ``q_b`` and
  ``kv_b`` are narrower than ``initializer_range`` by the constant their
  latent is rescaled by; every other matrix of either layer type has the
  range itself."""
  from perfbench.reference import dots3_note as dots
  cfg = dots.Dots3NoteConfig.from_file(TOY_CONFIG)
  for layer_type in (dots.FULL, dots.SLIDING):
    z = cfg.sizes(layer_type)
    by = {"q_b": (cfg.hidden_size / z.q_rank) ** 0.5,
          "kv_b": (cfg.hidden_size / z.kv_rank) ** 0.5}
    p = dots.init_attention(cfg, dots.seed_key(5), layer_type)
    for name in ("q_a", "q_b", "kv_a", "kv_b"):
      want = cfg.initializer_range / (
          by.get(name, 1.0) if layer_type == dots.FULL else 1.0)
      got = float(np.std(np.asarray(p[name], np.float32)))
      assert abs(got / want - 1.0) < 0.06, (layer_type, name, got, want)


def test_planted_selection_faults_are_controls_that_move_the_logits():
  """``recent`` and ``loose`` (the reference's planted selection faults)
  change nothing before the selection discards and the logits after it."""
  import jax
  from perfbench.reference import dots3_note as dots
  cfg = dots.Dots3NoteConfig.from_file(TOY_CONFIG)
  params = dots.init_params(cfg, dots.seed_key(7))
  S = min(cfg.n_positions, cfg.index_topk + 2 * dots.FAULT_BLOCK)
  assert S > cfg.index_topk + 1
  ids = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab_size)
  want = np.asarray(dots.logits(cfg, params, ids))[0]
  for fault in ("recent", "loose"):
    got = np.asarray(dots.logits(cfg, params, ids, fault))[0]
    np.testing.assert_array_equal(got[:cfg.index_topk],
                                  want[:cfg.index_topk])
    assert np.abs(got[-1] - want[-1]).max() > 1e-4, fault


def test_the_witness_widens_both_trees_alike():
  """``selection_witness.py --draw uniform``: a full layer's ``q_b`` and
  ``kv_b`` get the common range back in the reference's tree and in the
  program's, to the same bfloat16 values; nothing else moves."""
  import jax
  import jax.numpy as jnp
  from perfbench import selection_witness as witness
  from perfbench.reference import dots3_note as dots
  from perfbench.runners import epl_dots3_note as glue
  cfg = dots.Dots3NoteConfig.from_file(TOY_CONFIG)
  key = dots.seed_key(11)
  rp = dots.init_params(cfg, key)
  _, shell_of = glue.build_model(cfg, {"dtype": "float32",
                                       "param_dtype": "float32"})
  pp = glue.program_params(cfg, key, shell_of(jnp.zeros((1, 8), jnp.int32)))
  wide_r, wide_p = witness._widen(cfg, rp), witness._widen(cfg, pp)
  assert float(glue.sum_of_squares(wide_r)) == pytest.approx(
      float(glue.sum_of_squares(wide_p)), rel=1e-6)
  std = lambda x: float(np.std(np.asarray(x, np.float32)))
  for i, layer_type in enumerate(cfg.layer_types):
    was, now = rp["layers"][i]["att"], wide_r["layers"][i]["att"]
    for name in was:
      if layer_type == dots.FULL and name in ("q_b", "kv_b"):
        assert std(now[name]) == pytest.approx(cfg.initializer_range,
                                               rel=0.06), (i, name)
      else:
        assert (np.asarray(was[name], np.float32)
                == np.asarray(now[name], np.float32)).all(), (i, name)
  same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), pp, wide_p)
  changed = sorted(jax.tree_util.keystr(k) for k, v in
                   jax.tree_util.tree_leaves_with_path(same) if not v)
  assert len(changed) == 2 * sum(t == dots.FULL for t in cfg.layer_types)
  assert all("q_b" in k or "kv_b" in k for k in changed), changed


# --------------------------------------------------------------- dsa_cost --

SMALL = {"layer_types": PERIOD, "index_n_heads": 2, "index_head_dim": 16,
         "index_topk": 4, "num_attention_heads": 4, "kv_lora_rank": 32,
         "qk_rope_head_dim": 8, "swa_num_attention_heads": 2,
         "swa_kv_lora_rank": 48, "swa_qk_rope_head_dim": 8,
         "sliding_window_size": 5}


def test_sizes_at_the_published_widths():
  doc = manifest_lib.Manifest().config_file(REAL_CONFIG)
  s = dsa_cost.sizes(doc)
  assert s[dsa_cost.DSA_INDEX]["flops"] == 2 * 64 * 128
  assert s[dsa_cost.DSA_INDEX]["row_bytes"] == 256
  assert s[dsa_cost.SEL_ATTN]["flops"] == 2 * 128 * (576 + 512)
  assert s[dsa_cost.WIN_ATTN]["flops"] == 2 * 64 * (1088 + 1024)
  assert [s[k]["layers"] for k in (dsa_cost.DSA_INDEX, dsa_cost.SEL_ATTN,
                                   dsa_cost.WIN_ATTN)] == [2, 2, 3]


def test_request_work_by_hand():
  """A request of 6 prompt and 3 output tokens in chunks of 4 feeds
  positions 0-7: steps [0, 4), [4, 6), then 6 and 7."""
  s = dsa_cost.sizes(SMALL)
  steps_ = [(0, 4), (4, 6), (6, 7), (7, 8)]
  f, b = dsa_cost.request_work(s[dsa_cost.DSA_INDEX], 6, 3, 4)
  assert f == 2 * 2 * 16 * sum(t + 1 for t in range(8))
  assert b == 32 * sum(end for _, end in steps_)
  f, b = dsa_cost.request_work(s[dsa_cost.SEL_ATTN], 6, 3, 4)
  assert f == 2 * 4 * (40 + 32) * sum(min(t + 1, 4) for t in range(8))
  assert b == 80 * sum(min(end, 4) for _, end in steps_)       # a floor
  f, b = dsa_cost.request_work(s[dsa_cost.WIN_ATTN], 6, 3, 4)
  assert f == 2 * 2 * (56 + 48) * sum(min(t + 1, 5) for t in range(8))
  # the union of a step's windows: from 4 behind its first query
  assert b == 112 * sum(end - max(0, start - 4) for start, end in steps_)


def test_mix_mean_is_over_every_pair_of_quantiles():
  mix = {"prompt_len": {"dist": "uniform", "min": 8, "max": 40},
         "output_len": {"dist": "uniform", "min": 4, "max": 16}}
  work, mean_out = dsa_cost.mix_mean_work(SMALL, mix, 4)
  from perfbench.harness import traffic
  P = traffic.length_quantiles(mix["prompt_len"], dsa_cost.GRID)
  O = traffic.length_quantiles(mix["output_len"], dsa_cost.GRID)
  assert mean_out == pytest.approx(np.mean(O)) == pytest.approx(10, abs=0.1)
  kernel = dsa_cost.sizes(SMALL)[dsa_cost.WIN_ATTN]
  want = np.mean([dsa_cost.request_work(kernel, int(p), int(o), 4)[0]
                  for p in P for o in O])
  assert work[dsa_cost.WIN_ATTN][0] == pytest.approx(3 * want)


# ---------------------------------------------------------------- readers --


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


def serve_ctx(custom_calls, window_s=1.5, closing_s=0.25, period_ms=100.0,
              live=30, rate=200.0):
  config = manifest_lib.Manifest().config_file(REAL_CONFIG)
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s]]},
          "spans": steps([period_ms] * 20), "active_slots": [live] * 12,
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "config": config, "model": {"dtype": "bfloat16"},
          "kind": "backlog", "num_slots": 32, "tokens_per_s": rate}


ALL_CALLS = {"dsa_index": (25.0, 0.025), "slot_attn_sel": (25.0, 0.25),
             "slot_attn_win": (37.5, 0.125), "moe_gmm": (100.0, 0.125),
             "kv_write": (62.5, 0.01)}


def test_ms_readers_on_a_made_up_trace():
  # 1.25 s of stepping at 100 ms = 12.5 steps
  ctx = serve_ctx(ALL_CALLS)
  assert read(MS[0], ctx) == pytest.approx(2.0)
  assert read(MS[1], ctx) == pytest.approx(20.0)
  assert read(MS[2], ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("metric", ROOFLINES)
def test_rooflines_on_a_made_up_trace(metric):
  """200 tokens/s over a mean output of 320 is 0.625 requests/s, each
  bringing the mix's mean work; the kernel is busy ms / 100 ms of the
  period."""
  ctx = serve_ctx(ALL_CALLS)
  man = manifest_lib.Manifest()
  mix = man.traffic_file("longdoc-backlog")
  work, mean_out = dsa_cost.mix_mean_work(ctx["config"], mix, 32)
  assert mean_out == 320
  kernel = KERNEL_OF[metric]
  f, b = (0.625 * x for x in work[kernel])
  busy = read(MS[ROOFLINES.index(metric)], ctx) / 100.0
  want = 100 * max(f / 197e12, b / 819e9) / busy
  assert read(metric, ctx) == pytest.approx(want)
  assert 0 < want < 100


def test_a_roofline_takes_the_running_cells_sizes_or_none():
  """The run's context names no cell: the reader takes the chunk and the
  mix of the listed cell whose configuration, slots and kind of traffic are
  the run's, and reports nothing where none or two match."""
  ctx = serve_ctx(ALL_CALLS)
  cell_file, mix = dsa_cost.cell_of(ROOFLINES[0], ctx)
  assert cell_file["engine"]["prefill_chunk"] == 32
  assert mix["prompt_len"]["max"] == 12288
  for change in ({"num_slots": 16}, {"kind": "open_loop"},
                 {"config": dict(ctx["config"], index_topk=1024)}):
    other = dict(ctx, **change)
    assert dsa_cost.cell_of(ROOFLINES[0], other) is None
    assert read(ROOFLINES[0], other) is None


def test_mean_work_of_the_cells_mix():
  """What a request of the cell costs the three kernels (all their
  layers): the selection cuts the attend's work to about a quarter of
  attending every row, which is what the index scores cost 1/7 of."""
  man = manifest_lib.Manifest()
  work, _ = dsa_cost.mix_mean_work(
      man.config_file(REAL_CONFIG), man.traffic_file("longdoc-backlog"), 32)
  tf = {k: v[0] / 1e12 for k, v in work.items()}
  assert tf[dsa_cost.DSA_INDEX] == pytest.approx(1.28, rel=0.02)
  assert tf[dsa_cost.SEL_ATTN] == pytest.approx(8.54, rel=0.02)
  assert tf[dsa_cost.WIN_ATTN] == pytest.approx(3.43, rel=0.02)


def test_held_roofline_counts_the_held_experts_rows():
  roofline = run_lib.load_module("layer_metrics", HELD)
  config = manifest_lib.Manifest().config_file(REAL_CONFIG)
  f, b = roofline.step_cost(config, {"dtype": "bfloat16"}, 30)
  weights = 4 * 32 * 3 * 5120 * 1536 * 2
  assert weights == pytest.approx(6.04e9, rel=1e-3)
  # 30 live slots x 8 choices x 32 / 256 fall on held experts
  assert (f, b) == tuple(4 * x for x in moe_cost.layer_cost(
      30, 32, 5120, 1536, 2))
  assert b == weights + 4 * 30 * (2 * 5120 + 3 * 1536) * 2
  ctx = serve_ctx(ALL_CALLS)
  want = 100 * (b / 819e9) / 10e-3
  assert read(HELD, ctx) == pytest.approx(want)
  assert 0 < want < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing(metric):
  # the reference lowering, or a parent commit: no such custom call
  assert read(metric, serve_ctx({"kv_write": (25.0, 0.01)})) is None
  assert read(metric, serve_ctx({})) is None
  ctx = serve_ctx(ALL_CALLS)
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  assert read(metric, {"kind": "train"}) is None
  if metric not in MS:
    # another family's configuration
    ctx = serve_ctx(ALL_CALLS)
    ctx["config"] = {"n_routed_experts": 64, "first_k_dense_replace": 1}
    assert read(metric, ctx) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_on_the_recorded_trace_planes(metric):
  """The trace recorded on the chip (GPT-2 medium serving) reduced as a
  run reduces it: none of the new kernels' names is in it, so each reader
  returns ``None`` and does not raise."""
  from perfbench.harness import xplane
  with open(RECORDED) as f:
    planes = json.load(f)
  block = xplane.reduce(planes, host_spans=[], n_chips=1)
  ctx = serve_ctx({})
  ctx["trace"] = block
  assert not {"dsa_index", "slot_attn_sel", "slot_attn_win"} & set(
      block.get("custom_calls", {}))
  assert read(metric, ctx) is None
