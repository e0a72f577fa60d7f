"""An engine built on the live-rows attend (kernels/slot_attention.py)
and the window write beside it (kernels/kv_write.py).

The kernels' own contract is tests/test_slot_attention.py's; here an
engine built on them (interpreted) commits the same greedy tokens as one
built on the reference lowerings, under the fused, the speculative and
the draft-model step, compiles once, and says which lowering and which
ORDER of leaf it ran: every case in both orders a leaf is kept in
(serving/kv_cache.py, order note), ``positions`` on the narrow tier-1 cut
and ``rows`` on the same cut with heads that fill a lane tile.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.profiler.serving import ServingStats
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving.speculative import (
    DraftModelDrafter, NgramDrafter)

sa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.slot_attention")
kvw = importlib.import_module("easyparallellibrary_tpu.kernels.kv_write")

in_both_orders = pytest.mark.parametrize("order", ("positions", "rows"))


def _backend_takes(monkeypatch, impl):
  """What a test steers: the lowering the backend would take, for the
  attend and for the write beside it."""
  monkeypatch.setattr(sa, "_backend_impl", lambda: impl)
  monkeypatch.setattr(kvw, "_backend_impl", lambda: impl)


SERVE = GPTConfig(vocab_size=64, num_layers=2, num_heads=2, d_model=32,
                  d_ff=64, max_seq_len=256, dtype=jnp.float32)
# The same cut with heads that fill a lane tile (2 x 64 = 128), so its
# leaves are kept in rows: the tier-1 cuts are narrower and never fold.
SERVE_IN = {"positions": SERVE,
            "rows": dataclasses.replace(SERVE, d_model=128, d_ff=256)}
PROMPTS = (118, 3, 121, 40, 126)


def _model(order="positions"):
  model = GPT(SERVE_IN[order])
  params = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]
  r = np.random.RandomState(0)
  prompts = [r.randint(0, 64, (n,)).astype(np.int32) for n in PROMPTS]
  return model, params, prompts


def _serve(monkeypatch, impl, drafter=None, order="positions"):
  """Five greedy requests over three slots: prefill chunks and decode
  tokens share steps, decode cursors walk through a tile boundary one
  row at a time, a slot idles while the others finish."""
  _backend_takes(monkeypatch, impl)
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    model, params, prompts = _model(order)
    eng = ContinuousBatchingEngine(
        model, params, num_slots=3, prefill_chunk=8,
        drafter=drafter(model, params) if drafter else None,
        stats=ServingStats())
    for i, p in enumerate(prompts):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=14))
    out = eng.run()
    return eng, {u: np.asarray(t) for u, t in out.items()}, tracer.events()
  finally:
    trace_lib.install(None)


DRAFTERS = {
    "fused_step": None,
    "speculative_step": lambda model, params: NgramDrafter(k=3, ngram_max=3),
    "draft_model": lambda model, params: DraftModelDrafter(model, params,
                                                           k=2),
}


@pytest.mark.parametrize("drafter", sorted(DRAFTERS))
@in_both_orders
def test_engine_commits_the_same_greedy_tokens_under_either_attend(
    monkeypatch, order, drafter):
  """Also the engine-level proof of the rows form: a GPT cut whose heads
  fill a lane tile serves the same tokens with both rows kernels
  interpreted as with the reference lowerings, and says which order it
  kept its cache in."""
  epl.init()
  eng_k, out_k, events = _serve(monkeypatch, "interpret", DRAFTERS[drafter],
                                order)
  eng_r, out_r, _ = _serve(monkeypatch, "reference", DRAFTERS[drafter],
                           order)
  # Which attend each run timed is on record, not inferred.
  for eng, impl in ((eng_k, "interpret"), (eng_r, "reference")):
    assert kv_lib.resolved(eng.lowerings) == {
        "kv_write_impl": impl, "slot_attn_impl": impl}
  facts = [e["args"] for e in events
           if e["ph"] == "M" and e["name"] == "serving/slot_attn_impl"]
  assert facts == [{"impl": "interpret"}]
  assert eng_k._capture_context()["serving"]["slot_attn_impl"] == \
      "interpret"
  # ... and which order the leaves it ran over are kept in.
  layouts = [e["args"] for e in events
             if e["ph"] == "M" and e["name"] == "serving/cache_layout"]
  assert [l["kv_order"] for l in layouts] == [order] * len(layouts) != []
  assert eng_k.cache_layout["kv_order"] == order
  assert eng_k._capture_context()["serving"]["kv_order"] == order
  assert {x.ndim for x in jax.tree_util.tree_leaves(eng_k._kv)} == {
      3 if order == "rows" else 4}
  assert sorted(out_k) == sorted(out_r) == list(range(len(PROMPTS)))
  for uid in out_k:
    np.testing.assert_array_equal(out_k[uid], out_r[uid])
  # Each step compiled once under its lowering.
  assert eng_k._step_fn._cache_size() == eng_r._step_fn._cache_size() == 1


@in_both_orders
def test_greedy_idle_greedy_compiles_once_and_equals_generate(monkeypatch,
                                                              order):
  """Requests, a drained engine whose every slot idles, requests again:
  one compile, and the kernel-built engine still equals the one-request
  oracle (whose decode takes the kernel too, ``C = 1``, no bound, over
  leaves of its own that stay in positions)."""
  epl.init()
  _backend_takes(monkeypatch, "interpret")
  model, params, prompts = _model(order)
  eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                 prefill_chunk=8)
  eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=6))
  first = eng.run()
  assert eng.scheduler.num_active == 0
  eng.submit(Request(uid=1, prompt=prompts[2], max_new_tokens=14))
  eng.submit(Request(uid=2, prompt=prompts[1], max_new_tokens=4))
  second = eng.run()
  assert eng._step_fn._cache_size() == 1
  assert eng._compile_sentinel.recompiles == 0
  for uid, prompt, n in ((0, prompts[0], 6), (1, prompts[2], 14)):
    got = np.asarray({**first, **second}[uid])
    want = np.asarray(generate(model, params,
                               jnp.asarray(prompt)[None], n))[0]
    np.testing.assert_array_equal(got, want)


def test_live_kv_rows_is_counted_every_step(monkeypatch):
  """``serving/live_kv_rows`` beside ``serving/active_slots``: the sum
  over the step's fed slots of cursor + num_valid, from the plan; its
  share of the cache's rows in ``ServingStats.summary()``."""
  epl.init()
  eng, _, events = _serve(monkeypatch, "reference")
  rows = [e["args"]["value"] for e in events
          if e["ph"] == "C" and e["name"] == "serving/live_kv_rows"]
  slots = [e for e in events
           if e["ph"] == "C" and e["name"] == "serving/active_slots"]
  assert len(rows) == len(slots) == eng._steps
  # The first step feeds a chunk of 8, 3 and 8 tokens to three fresh
  # slots; no step's bound passes what a request can hold.
  assert rows[0] == 8 + 3 + 8
  assert max(rows) <= 3 * (max(PROMPTS) + 14)
  cap = 3 * kv_lib.cache_length(SERVE, 8)
  assert eng.stats.summary()["kv_read_share"] == pytest.approx(
      sum(rows) / (len(rows) * cap))
  # The rows the device cursors say were fed, step by step: every token
  # of every request, once.
  fed = sum(n + 14 - 1 for n in PROMPTS)
  assert sum(b - a for a, b in zip([0] + rows, rows) if b > a) <= fed * 3


@in_both_orders
def test_attn_rows_read_is_the_walks_own_sum(monkeypatch, order):
  """``serving/attn_rows_read`` beside ``serving/live_kv_rows``, a step's
  pair: each live bound up to the walk's granule (a lane tile of
  positions, a sublane tile of float32 rows), which is the piece list's
  own sum of rows; never under the live rows; absent from a step built on
  the einsums.  The step compiled once over steps whose live slots and
  bounds all differ."""
  epl.init()
  eng, _, events = _serve(monkeypatch, "interpret", order=order)
  counter = lambda name: [e["args"]["value"] for e in events
                          if e["ph"] == "C" and e["name"] == name]
  read, live = counter("serving/attn_rows_read"), counter(
      "serving/live_kv_rows")
  granule = {"positions": 128, "rows": 8}[order]
  Lc = kv_lib.cache_length(SERVE_IN[order], 8)
  assert eng._attn_walk == (granule, {"positions": 384, "rows": Lc}[order])
  assert len(read) == len(live) == eng._steps
  assert all(r >= l and r % granule == 0 for r, l in zip(read, live))
  assert len(set(live)) > 5 and eng._step_fn._cache_size() == 1
  # The first step feeds 8, 3 and 8 positions to three fresh slots.
  bound = jnp.asarray([8, 3, 8], jnp.int32)
  pieces = sa.live_pieces(bound, eng._attn_walk[1], 128, granule)
  assert read[0] == int(np.sum(pieces[2])) == 3 * max(granule, 8)
  # A scripted plan: an idle slot, a bound on a granule, one past it.
  from easyparallellibrary_tpu.serving.engine import _walk_rows
  resident = np.asarray([40, 120, 128, 0], np.int32)
  feeds = np.asarray([0, 8, 1, 5], np.int32)
  want = sa.live_pieces(jnp.asarray([0, 128, 129, 5], jnp.int32), 264, 128,
                        granule)[2]
  assert _walk_rows(resident, feeds, granule, 264) == int(np.sum(want))
  # ... and a full slot of a leaf that holds no whole number of granules.
  assert _walk_rows(np.asarray([8192], np.int32), np.asarray([8], np.int32),
                    16, 8200) == 8200 == int(np.sum(sa.live_pieces(
                        jnp.asarray([8200], jnp.int32), 8200, 1024, 16)[2]))
  _, _, events = _serve(monkeypatch, "reference", order=order)
  assert not [e for e in events if e["name"] == "serving/attn_rows_read"]


def test_the_layers_of_a_step_share_one_piece_list(monkeypatch):
  """The piece list reads the bounds and the leaf's geometry alone: in the
  compiled step of a two-layer model it stands once, not once a layer."""
  from easyparallellibrary_tpu.observability import device as device_lib
  epl.init()
  _backend_takes(monkeypatch, "interpret")
  model, params, prompts = _model("rows")
  eng = ContinuousBatchingEngine(model, params, num_slots=3, prefill_chunk=8)
  specs = []
  real, note = eng._step_fn, eng._note_step_specs
  eng._note_step_specs = lambda args: (
      specs.append(device_lib.specs_of(args)), note(args))[1]
  eng.submit(Request(uid=0, prompt=prompts[1], max_new_tokens=2))
  eng.run()
  assert model.cfg.num_layers == 2
  text = real.lower(*specs[0]).compile().as_text()
  # A piece list takes three vectors by slot (``jnp.take``): its gathers
  # stand in the program, every call inlined, three times and not six.
  takes = [l for l in text.splitlines()
           if " gather(" in l and "slot_attn_pieces" in l]
  assert len(takes) == 3, takes


@in_both_orders
def test_engine_on_a_mesh_of_chips_takes_the_reference(monkeypatch, order):
  _backend_takes(monkeypatch, "interpret")
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  cfg = SERVE_IN[order]
  assert len(kv_lib.kv_leaf_shape(cfg, 3, 8)) == (3 if order == "rows"
                                                  else 4)
  assert kv_lib.slot_attn_impl(cfg, 3, 8, mesh) == "reference"
  assert kv_lib.slot_attn_impl(cfg, 3, 8, None) == "interpret"
