"""One record of what a fused step was lowered to
(``kv_cache.step_lowerings``): the engine holds it as ONE mapping, records
its resolved entries as trace metadata under their own names and hands it
whole to the diagnostic bundle, for every served family alike."""

import importlib
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

NAMES = ["kv_write_impl", "slot_attn_impl", "kv_win_write_impl",
         "kv_win_attn_impl", "dsa_index_impl", "ssm_scan_impl",
         "moe_gmm_impl"]
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
    "smallthinker": ("test_smallthinker", "epl_smallthinker",
                     KV | {"kv_win_write_impl", "kv_win_attn_impl",
                           "moe_gmm_impl"}),
}


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
def test_the_engine_keeps_one_record_and_says_it_three_ways(family):
  model, params = _build(family)
  want = FAMILIES[family][2]
  paged = family == "gpt-paged"
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    eng = ContinuousBatchingEngine(
        model, params, num_slots=2, prefill_chunk=4,
        **(dict(paged=True, block_size=8) if paged else {}))
    events = tracer.events()
  finally:
    trace_lib.install(None)
  assert list(eng.lowerings) == NAMES
  assert eng.lowerings == (dict.fromkeys(NAMES) if paged else
                           kv_lib.step_lowerings(model.cfg, 2, 4))
  assert kv_lib.resolved(eng.lowerings) == dict.fromkeys(want, "reference")
  # The trace: one metadata event a resolved lowering, under its own name,
  # beside the ones that are no lowerings.
  meta = {ev["name"]: ev["args"] for ev in events
          if ev["ph"] == "M" and ev["name"].startswith("serving/")}
  others = {"serving/step_overlap"} | (set() if paged else {
      "serving/cache_layout", "serving/flat_width"}) | (
          {"serving/experts_held"} if eng.experts_held is not None else set())
  assert set(meta) == {f"serving/{name}" for name in want} | others
  assert all(meta[f"serving/{name}"] == {"impl": "reference"}
             for name in want)
  # The diagnostic bundle: the same seven keys with the same values.
  bundle = eng._capture_context()["serving"]
  assert {name: bundle[name] for name in NAMES} == eng.lowerings
  eng.close()
