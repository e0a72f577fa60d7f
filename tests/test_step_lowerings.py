"""One record of what a fused step was lowered to
(``kv_cache.step_lowerings``): the engine holds it as ONE mapping, records
its resolved entries as trace metadata under their own names and hands it
whole to the diagnostic bundle, for every served family alike.  Its last
entry, ``tile_attn_out``, is no lowering but what follows from them: where
the attends of a model that selects its rows or sits behind a latent window
read their queries and write their result."""

import importlib
import logging
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import GPT, GPTConfig  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, kv_cache as kv_lib)

LOWERINGS = ["kv_write_impl", "slot_attn_impl", "kv_win_write_impl",
             "kv_win_attn_impl", "dsa_index_impl", "ssm_scan_impl",
             "gdn_scan_impl", "moe_gmm_impl"]
NAMES = LOWERINGS + ["tile_attn_out"]
# A family's tiny configuration is its own test file's (``REF_CFG``
# through the benchmark's glue; GPT's is built here) and what its rules
# resolve: the K/V pair's two and those of its own kinds of layer.
KV = {"kv_write_impl", "slot_attn_impl"}
FAMILIES = {
    "gpt": (None, None, KV),
    # Its pools take a scatter of flat rows and the paged attend: nothing
    # of the slot cache's is lowered.
    "gpt-paged": (None, None, set()),
    "jamba": ("test_jamba", "epl_jamba", KV | {"ssm_scan_impl"}),
    "glm_moe": ("test_glm_moe", "epl_glm4_moe_lite", KV | {"moe_gmm_impl"}),
    "lfm2_moe": ("test_lfm2_moe", "epl_lfm2_moe", KV | {"moe_gmm_impl"}),
    "dots3_note": ("test_dots3_note", "epl_dots3_note",
                   KV | {"dsa_index_impl", "moe_gmm_impl"}),
    "glm_moe_dsa": ("test_glm_dsa_mesh", "epl_glm_moe_dsa",
                    KV | {"dsa_index_impl", "moe_gmm_impl"}),
    "smallthinker": ("test_smallthinker", "epl_smallthinker",
                     KV | {"kv_win_write_impl", "kv_win_attn_impl",
                           "moe_gmm_impl"}),
    "gigachat3_5": ("test_gigachat", "epl_gigachat3_5",
                    KV | {"gdn_scan_impl", "moe_gmm_impl"}),
}
# The two whose layers select their rows or sit behind a latent window: at
# the full width a toy engine has (and under the reference lowerings) their
# attends take ``[slots, chunk]``-ordered operands.
TILE_FORMS = {"dots3_note", "glm_moe_dsa"}


def _build(family):
  epl.init()
  tests, glue, _ = FAMILIES[family]
  if tests is None:
    model = GPT(GPTConfig(vocab_size=64, num_layers=2, num_heads=4,
                          d_model=32, d_ff=64, max_seq_len=48,
                          dtype=jnp.float32))
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
  tests = importlib.import_module(tests)
  glue = importlib.import_module(f"perfbench.runners.{glue}")
  model, shell_of = glue.build_model(tests.REF_CFG, tests.F32)
  return model, jax.tree_util.tree_map(
      lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
      shell_of(jnp.zeros((1, 8), jnp.int32)))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_engine_keeps_one_record_and_says_it_three_ways(family, caplog):
  model, params = _build(family)
  want = FAMILIES[family][2]
  paged = family == "gpt-paged"
  from easyparallellibrary_tpu.utils.logging import get_logger
  logger = get_logger()
  propagated = logger.propagate
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    logger.propagate = True  # the repo logger is handler-only by default
    with caplog.at_level(logging.INFO, logger=logger.name):
      eng = ContinuousBatchingEngine(
          model, params, num_slots=2, prefill_chunk=4,
          **(dict(paged=True, block_size=8) if paged else {}))
    events = tracer.events()
  finally:
    trace_lib.install(None)
    logger.propagate = propagated
  assert list(eng.lowerings) == NAMES
  assert eng.lowerings == (dict.fromkeys(NAMES) if paged else
                           kv_lib.step_lowerings(model.cfg, 2, 4))
  assert kv_lib.resolved(eng.lowerings) == dict.fromkeys(want, "reference")
  out = "slots" if family in TILE_FORMS else None
  assert eng.lowerings["tile_attn_out"] == out
  said = dict(dict.fromkeys(want, "reference"),
              **({} if out is None else {"tile_attn_out": out}))
  assert kv_lib.recorded(eng.lowerings) == said
  # The start-up line names every entry that says something, in the
  # record's order.
  (line,) = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("serving engine:")]
  if not paged:
    assert ", ".join(f"{name} {said[name]}" for name in NAMES
                     if name in said) in line
  # The trace: one metadata event a resolved lowering, under its own name,
  # beside the ones that are no lowerings.
  meta = {ev["name"]: ev["args"] for ev in events
          if ev["ph"] == "M" and ev["name"].startswith("serving/")}
  others = {"serving/step_overlap"} | (set() if paged else {
      "serving/cache_layout", "serving/flat_width"}) | (
          {"serving/experts_held"} if eng.experts_held is not None else set())
  assert set(meta) == {f"serving/{name}" for name in said} | others
  assert all(meta[f"serving/{name}"] == {"impl": impl}
             for name, impl in said.items())
  # The diagnostic bundle: the same eight keys with the same values.
  bundle = eng._capture_context()["serving"]
  assert {name: bundle[name] for name in NAMES} == eng.lowerings
  eng.close()


@pytest.mark.parametrize("family", sorted(TILE_FORMS))
@pytest.mark.parametrize("backend,width,out", [
    ("pallas", 512, "flat"), ("interpret", 512, "flat"),
    # every position of every slot: the map is a reshape
    ("pallas", 1024, "slots"), ("pallas", None, "slots"),
    # the reference lowering takes [slots, chunk] order at any width
    ("reference", 512, "slots")])
def test_the_flat_form_follows_the_kernel_and_the_width(monkeypatch, family,
                                                        backend, width, out):
  """``tile_attn_out`` at a serving cell's geometry (32 slots x chunk 32 a
  chip, 512 rows): ``flat`` exactly where the tile kernels were resolved
  and the flat batch is narrower than ``slots x chunk``; what the mixer
  asks (kernels/slot_attention.py:tile_attn_out) is what the record says."""
  from easyparallellibrary_tpu.kernels import slot_attention
  for mod in ("kv_write", "slot_attention", "dsa_index", "moe_gmm"):
    monkeypatch.setattr(
        importlib.import_module(f"easyparallellibrary_tpu.kernels.{mod}"),
        "_backend_impl", lambda: backend)
  # The decoder as its serving cell builds it, at the published widths.
  from perfbench.harness import manifest as manifest_lib
  man = manifest_lib.Manifest(os.path.join(os.path.dirname(__file__), ".."))
  cell = {"dots3_note": "dots3note-longdoc-backlog",
          "glm_moe_dsa": "glm5-agentctx-backlog-4chip"}[family]
  glue = importlib.import_module(f"perfbench.runners.epl_{family}")
  epl.init()
  cfg = glue.build_model(
      glue.ref_config(man.config_file(man.workload(cell)["config"])),
      man.cell_file(cell)["model"])[0].cfg
  record = kv_lib.step_lowerings(cfg, 32, 32, width=width)
  assert record["slot_attn_impl"] == backend
  assert record["tile_attn_out"] == out == slot_attention.tile_attn_out(
      backend, width is not None and width < 32 * 32)
  assert "tile_attn_out" not in kv_lib.resolved(record)


# Every serving cell of the benchmark at its own geometry (a chip's share of
# GLM-5's 128 slots): where its one-leaf attends read and write, and the
# tile ``serving/attn_tile_positions`` counts by.  GigaChat3.5's plain leaf
# takes the tile grid (64 heads x chunk 32: 2,048 query rows a slot on the
# first grid); GLM-4.7-Flash's keeps the first grid (20 heads are no whole
# sublane tiles, a chunk of 8 is one tile); the K/V cells have no such leaf.
CELLS = {
    "gpt2m-chat-steady": (96, None, None),
    "gpt2m-offline-backlog": (96, None, None),
    "jamba2-3b-reasoning-backlog": (128, None, None),
    "glm47flash-agent-backlog": (96, None, None),
    "lfm2moe-chat-steady": (128, None, None),
    "dots3note-longdoc-backlog": (32, "flat", (8, 1)),
    "smallthinker-mixedlen-backlog": (48, None, None),
    "glm5-agentctx-backlog-4chip": (32, "flat", (8, 1)),
    "gigachat35-decode-backlog": (128, "flat", (8, 1)),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_one_leaf_attends_follow_its_shapes(monkeypatch, cell):
  from easyparallellibrary_tpu.serving.engine import flat_width
  from perfbench.harness import manifest as manifest_lib
  for mod in ("kv_write", "slot_attention", "dsa_index", "moe_gmm",
              "ssm_scan", "gdn_scan"):
    monkeypatch.setattr(
        importlib.import_module(f"easyparallellibrary_tpu.kernels.{mod}"),
        "_backend_impl", lambda: "pallas")
  man = manifest_lib.Manifest(os.path.join(os.path.dirname(__file__), ".."))
  cell_file = man.cell_file(cell)
  config_file = man.config_file(man.workload(cell)["config"])
  epl.init()
  if cell_file["runner"] == "serve":
    from perfbench.reference import gpt2
    from perfbench.runners import epl_gpt
    cfg = epl_gpt.gpt_config(gpt2.GPT2Config.from_file(config_file),
                             cell_file["model"])
  else:
    glue = importlib.import_module(
        f"perfbench.runners.epl_{cell_file['family']}")
    cfg = glue.build_model(glue.ref_config(config_file),
                           cell_file["model"])[0].cfg
  slots, out, tile = CELLS[cell]
  chunk = cell_file["engine"]["prefill_chunk"]
  assert slots * man.workload(cell)["chips"] == cell_file["engine"][
      "num_slots"]
  T = flat_width(slots, chunk)
  record = kv_lib.step_lowerings(cfg, slots, chunk, width=T)
  assert record["slot_attn_impl"] == "pallas"
  assert record["tile_attn_out"] == out
  assert kv_lib.attn_tile(cfg, record, chunk) == tile
  # the first grid's walk is counted where a leaf still takes it
  walk = kv_lib.slot_attn_walk(cfg, slots, chunk, record["tile_attn_out"])
  assert (walk is None) == (out == "flat")
  # at full width, and under the reference lowerings, no plain leaf does
  for full in (kv_lib.step_lowerings(cfg, slots, chunk),
               kv_lib.step_lowerings(cfg, slots, chunk, width=slots * chunk)):
    assert full["tile_attn_out"] == (
        None if cell.startswith("gigachat") or out is None else "slots")
