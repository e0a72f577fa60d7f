"""GLM-5's decoder (models/glm_moe.py with its indexer on) against the
benchmark's plain reference (perfbench/reference/glm_moe_dsa.py), and the
engine DIVIDED over a mesh axis: the slots and the held experts over the
chips of ``expert``, the fused step under a ``shard_map``, expert rows
exchanged between the chips (models/moe.py ``exchanged_experts``).

Toy widths with every mechanism: hidden 64; 4 heads (16 | 8 | 16 on ranks
32 / 32) with an indexer of 2 heads of 16 that keeps 4 rows, in EVERY
layer; 16 routed experts top-2 of which the host holds 8 (4 .. 11: 2 a
chip of four) beside a shared one; one dense + two expert layers;
vocabulary 256.  float32 on both sides, matmuls at ``highest``.

Tolerances: logits are O(1-10) (weights N(0, 0.2), as tests/test_glm_moe.py
argues), program and reference differ by float32 rounding in another order
of the same sums, so ``3e-4`` absolute on logits is far below what a wrong
term gives (a dropped expert term moves logits by 1e-1 and more).  The
seed is one with no near-tie, within float32 rounding, at the 4th index
score or the 2nd expert.  The divided layer against the one-chip layer
sums the same float32 terms, a round's at a time: ``1e-5`` absolute.
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import moe as moe_lib  # noqa: E402
from easyparallellibrary_tpu.models.layer_kinds import SPARSE_LATENT  # noqa: E402
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, engine as engine_lib,
    kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import ROADMAP_DIVIDED  # noqa: E402
from easyparallellibrary_tpu.serving.scheduler import FCFSScheduler  # noqa: E402
from easyparallellibrary_tpu.utils.compat import shard_map  # noqa: E402
from perfbench.reference import glm_moe_dsa as ref  # noqa: E402
from perfbench.runners import epl_glm_moe_dsa as glue  # noqa: E402

REF_CFG = ref.GlmMoeDsaConfig(
    num_hidden_layers=3, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, heads=4, q_rank=32, kv_rank=32, nope=16,
    rope=8, value=16, theta=1e6, index_n_heads=2, index_head_dim=16,
    index_topk=4, router_width=16, experts_first=4, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
    vocab_size=256, n_positions=128, initializer_range=0.2, bias_std=0.05)
F32 = {"dtype": "float32", "param_dtype": "float32"}
LOGIT_TOL = 3e-4
S = 40
CHIPS = 4


def _mesh(n=CHIPS):
  return Mesh(np.array(jax.devices()[:n]), ("expert",))


@pytest.fixture(scope="module")
def both():
  """(program model, its params, reference params) from one seed."""
  epl.init()
  key = ref.seed_key(2 ** 31 + 47)
  model, shell_of = glue.build_model(REF_CFG, F32)
  params = glue.program_params(
      REF_CFG, key, shell_of(jnp.zeros((1, 8), jnp.int32)))
  return model, params, jax.jit(lambda k: ref.init_params(REF_CFG, k))(key)


@pytest.fixture(scope="module")
def ids():
  return jax.random.randint(jax.random.PRNGKey(0), (3, S), 0, 256)


@pytest.fixture(scope="module")
def want(both, ids):
  return ref.logits(REF_CFG, both[2], ids)


def _through_the_cache(model, params, ids, chunk=8, prefill=24):
  """``ids`` through slot mode: ``prefill`` positions ``chunk`` at a time,
  then one a step (decode); the logits of every position [B, S, V]."""
  B, S_ = ids.shape
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, chunk)
  outs, s = [], 0
  step = jax.jit(lambda kv, block, cur, nv: slot_step_logits(
      model, params, kv, block, cur, num_valid=nv))
  with jax.default_matmul_precision("highest"):
    while s < S_:
      n = min(chunk, prefill - s) if s < prefill else 1
      nv = jnp.full((B,), n, jnp.int32)
      block = jnp.zeros((B, chunk), jnp.int32).at[:, :n].set(ids[:, s:s + n])
      lg, kv = step(kv, block, cur, nv)
      cur, s = cur + nv, s + n
      outs.append(lg[:, :n])
  return jnp.concatenate(outs, 1)


# ------------------------------------------ (a) decoder against reference --


@pytest.mark.parametrize("path", ["full_forward", "prefill_then_decode"])
def test_decoder_equals_the_reference_beyond_the_selection(both, ids, want,
                                                           path):
  """Logits at every one of 40 positions, ten times ``index_topk``: from
  position 4 on every query of every layer discards rows."""
  model, params, _ = both
  assert model.cfg.layer_kinds() == (SPARSE_LATENT,) * 3
  assert model.cfg.experts_held == (4, 8)
  with jax.default_matmul_precision("highest"):
    got = (jax.jit(lambda p, x: model.apply({"params": p}, x))(params, ids)
           if path == "full_forward"
           else _through_the_cache(model, params, ids))
  np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_reference_is_given_any_share(both, ids):
  """``experts_held`` as an argument: the host's share by default, a
  chip's or an absent share when handed in; different shares differ."""
  _, _, ref_params = both
  logits = jax.jit(lambda p, x, held=None: ref.logits(
      REF_CFG, p, x, experts_held=held), static_argnums=2)
  host = logits(ref_params, ids[:1, :12])
  same = logits(ref_params, ids[:1, :12], (4, 8))
  chip = logits(ref_params, ids[:1, :12], (4, 2))
  np.testing.assert_array_equal(host, same)
  assert float(jnp.max(jnp.abs(host - chip))) > 1e-2


# ------------------------------------------------------ (b) the exchange --


def _layer(both, i=1):
  """Expert layer ``i`` of the seeded model: (module config, its params,
  the reference's weights of it)."""
  model, params, ref_params = both
  return model.cfg, params[f"block_{i}"]["moe"], ref_params["layers"][i]["ff"]


def _uneven(layer_params, cfg, chips=CHIPS):
  """The layer's parameters with a selection bias that sends most
  assignments to the FIRST chip's experts and none to the last's."""
  first, held = cfg.experts_held
  per = held // chips
  bias = np.zeros(cfg.n_routed_experts, np.float32)
  bias[first:first + per] = 5.0
  bias[first + held - per:first + held] = -5.0
  return dict(layer_params, e_score_correction_bias=jnp.asarray(bias))


def _divided(cfg, layer_params, x, live, chips=CHIPS):
  """``DroplessMoE`` over an axis of ``chips``: ``x`` ``[chips x N, D]``
  divided by position, the stacks by expert; returns ``(y, sown stats
  summed or maxed over chips)``."""
  layer = moe_lib.DroplessMoE(cfg, moe_gmm_impl="reference",
                              expert_axis="expert")
  specs = jax.tree_util.tree_map_with_path(
      lambda path, leaf: P("expert") if path[-1].key in (
          "experts_gate_up", "experts_down") else P(), layer_params)

  def local(p, x, live):
    y, mut = layer.apply({"params": p}, x[:, None], live[:, None],
                         mutable=["stats"])
    stats = {k: v[0] for k, v in mut["stats"].items()}
    return y[:, 0], {
        k: (jax.lax.pmax if k in ("exchange_rounds", "expert_load")
            else jax.lax.psum)(v, "expert") for k, v in stats.items()}
  return jax.jit(shard_map(local, _mesh(chips),
                           in_specs=(specs, P("expert"), P("expert")),
                           out_specs=(P("expert"), P())))(layer_params, x,
                                                          live)


def _one_chip(cfg, layer_params, x, live):
  layer = moe_lib.DroplessMoE(cfg, moe_gmm_impl="reference")
  y, mut = layer.apply({"params": layer_params}, x[:, None], live[:, None],
                       mutable=["stats"])
  return y[:, 0], {k: v[0] for k, v in mut["stats"].items()}


@pytest.mark.parametrize("routing", ["even", "uneven"])
def test_divided_layer_equals_the_one_chip_layer(both, routing):
  """Position for position, also under a routing so uneven that one chip
  receives most rows and one none: no assignment to a held expert is
  dropped."""
  cfg, layer_params, _ = _layer(both)
  if routing == "uneven":
    layer_params = _uneven(layer_params, cfg)
  r = np.random.RandomState(3)
  x = jnp.asarray(r.randn(CHIPS * 24, 64), jnp.float32)
  live = jnp.asarray(r.rand(CHIPS * 24) < 0.85)
  with jax.default_matmul_precision("highest"):
    got, stats = _divided(cfg, layer_params, x, live)
    want, one = _one_chip(cfg, layer_params, x, live)
  np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
  # every assignment the one-chip layer computed was computed
  assert float(stats["held_assignments"]) == float(one["held_assignments"])
  assert float(stats["exchange_rows_out"]) == float(
      stats["exchange_rows_in"]) > 0
  assert float(stats["experts_touched"]) == float(one["experts_touched"])
  assert float(stats["exchange_rounds"]) == 1
  if routing == "uneven":
    # all of the first chip's experts are touched, none of the last's
    assert float(one["experts_touched"]) <= 6
    assert float(stats["held_assignments"]) > 1.5 * float(jnp.sum(live))


@pytest.mark.parametrize("rows", [8, 16, 128])
def test_exchange_takes_the_rounds_its_fullest_pair_needs(rows):
  """``exchanged_experts`` with ``rows`` a pair and round against
  ``dropless_experts`` holding the same 4 x 3 experts (the router's 2 ..
  13 of 24), under choices that send one chip most rows and one none: the
  same terms in however many rounds, every held assignment computed."""
  first, per, N, k, D, F = 2, 3, 16, 4, 32, 16
  r = np.random.RandomState(0)
  pool = np.array([2, 3, 4, 5, 8, 0, 1, 20, 21])   # none of 11 .. 13
  prob = np.where(pool < 5, 6.0, 1.0)
  chosen = jnp.asarray(np.stack([
      r.choice(pool, k, replace=False, p=prob / prob.sum())
      for _ in range(CHIPS * N)]), jnp.int32)
  x = jnp.asarray(r.randn(CHIPS * N, D), jnp.float32)
  weights = jnp.asarray(r.rand(CHIPS * N, k), jnp.float32)
  live = jnp.asarray(r.rand(CHIPS * N) < 0.8)
  w_gate_up = jnp.asarray(0.2 * r.randn(CHIPS * per, D, 2 * F), jnp.float32)
  w_down = jnp.asarray(0.2 * r.randn(CHIPS * per, F, D), jnp.float32)
  args = (x, chosen, weights, live, w_gate_up, w_down)

  def local(*a):
    y, sizes, sent, left, took = moe_lib.exchanged_experts(
        *a, axis="expert", rows=rows, impl="reference", first=first)
    return y, sizes, sent[None], left[None], took[None]
  with jax.default_matmul_precision("highest"):
    y, sizes, sent, left, took = jax.jit(shard_map(
        local, _mesh(), in_specs=(P("expert"),) * 6,
        out_specs=(P("expert"),) * 5))(*args)
    want, want_sizes = moe_lib.dropless_experts(*args, impl="reference",
                                                first=first)
  np.testing.assert_allclose(y, want, atol=1e-5, rtol=0)
  np.testing.assert_array_equal(sizes, want_sizes)
  assert int(sent.sum()) == int(want_sizes.sum())
  by_chip = np.asarray(want_sizes).reshape(CHIPS, per).sum(1)
  assert by_chip[0] > 2 * by_chip[1:].sum() and by_chip[3] == 0
  # what the fullest (source chip, destination chip) pair sends, in rows
  dest = (np.asarray(chosen) - first) // per
  sends = [[int(((dest[c * N:(c + 1) * N] == d)
                 & np.asarray(live)[c * N:(c + 1) * N, None]).sum())
            for d in range(CHIPS)] for c in range(CHIPS)]
  rounds = -(-max(map(max, sends)) // rows)
  assert took.tolist() == [rounds] * CHIPS
  assert rounds > 2 if rows == 8 else rounds >= 1


def test_exchange_rows_is_half_again_the_mean_in_whole_tiles():
  # the cell's: 512 positions a chip, 8 a token, 16 of 256 experts a chip
  assert moe_lib.exchange_rows(512, 8, 16, 256) == 384
  assert moe_lib.exchange_rows(256, 8, 16, 256) == 256
  assert moe_lib.exchange_rows(32, 2, 2, 16) == 128


# ------------------------------------- (c) the shares tied to the model --


def test_shares_add_up_to_the_uncut_reference_layer(both):
  """The four chips' terms (the divided layer, its shared expert counted
  once) and the absent shares' terms (the reference run once a share,
  without the shared expert) add up to the uncut reference layer."""
  cfg, layer_params, ff = _layer(both)
  r = np.random.RandomState(4)
  x = jnp.asarray(r.randn(CHIPS * 8, 64), jnp.float32)
  live = jnp.ones((CHIPS * 8,), bool)
  with jax.default_matmul_precision("highest"):
    host, _ = _divided(cfg, layer_params, x, live)
    absent = sum(ref.moe(REF_CFG, x, ff, "float32", held=share, shared=False)
                 for share in ((0, 4), (12, 4)))
    uncut = ref.moe(REF_CFG, x, ff, "float32", held=(0, 16))
    a_chip = ref.moe(REF_CFG, x, ff, "float32", held=(4, 2), shared=False)
  np.testing.assert_allclose(host + absent, uncut, atol=2e-5, rtol=0)
  assert float(jnp.max(jnp.abs(a_chip))) > 1e-3


# ------------------------------------------------ (d) the divided engine --


def _requests(n, lo=5, hi=60, seed=0):
  rng = np.random.default_rng(seed)
  return [Request(uid=i, prompt=rng.integers(0, 256, int(rng.integers(
      lo, hi))).astype(np.int32), max_new_tokens=int(rng.integers(3, 10)))
          for i in range(n)]


def _serve(model, params, reqs, slots, chunk, mesh=None, stats=None):
  eng = ContinuousBatchingEngine(model, params, num_slots=slots,
                                 prefill_chunk=chunk, mesh=mesh, stats=stats)
  for r in reqs:
    assert eng.submit(r)
  eng.run()
  assert eng._step_fn._cache_size() == 1
  return {u: np.asarray(f.tokens) for u, f in eng.finished.items()}, eng


@pytest.mark.parametrize("slots,chunk,chips", [(16, 8, 4), (128, 8, 2)],
                         ids=["one_width", "two_widths"])
def test_divided_engine_emits_the_one_chip_engines_tokens(both, slots, chunk,
                                                          chips):
  """An engine on a mesh of four emits, request for request, the tokens of
  four one-chip engines of a quarter of the slots each, holding the same
  experts (greedy; float32, so the exchange's other order of the same sums
  moves no argmax on this seed).  64 slots x 8 a chip is the geometry with
  a second width (256 / 128 rows a chip; two chips of it, for the suite's
  time): every chip must take the same side of the width's conditionals,
  whichever its own positions fit."""
  model, params, _ = both
  reqs = _requests(24 if slots == 16 else 64, hi=60)
  stats = ServingStats()
  got, eng = _serve(model, params, reqs, slots, chunk, _mesh(chips), stats)
  assert eng.slot_axis == ("expert", chips)
  assert eng.slots_a_chip == slots // chips
  assert set(got) == {r.uid for r in reqs}
  for j in range(chips):
    part = reqs[j::chips]
    want, one = _serve(model, params, part, slots // chips, chunk)
    assert one.slot_axis is None
    assert (one.flat_width, one.flat_narrow) == (eng.flat_width,
                                                 eng.flat_narrow)
    for r in part:
      np.testing.assert_array_equal(got[r.uid], want[r.uid], str(r.uid))
  summary = stats.summary()
  assert summary["exchange_rows_out_per_step"] > 0
  assert summary["exchange_rows_out_per_step"] == summary[
      "exchange_rows_in_per_step"]
  assert summary["chip_live_max_per_step"] >= summary[
      "chip_live_min_per_step"]
  assert summary["exchange_extra_rounds"] >= 0
  if slots == 128:
    assert eng.flat_narrow < eng.flat_width
    assert 0 < stats.flat_narrow_steps < stats.steps


def test_divided_engine_attends_the_flat_batch_where_it_lies(monkeypatch):
  """The selected attend's flat form under the divided step's ``shard_map``:
  an engine on two chips, four slots x chunk 16 a chip on a flat batch of
  40 rows with a second width of 16 (named here: the rule gives so few
  positions their full width), at widths the interpreted kernels take (8
  heads, an index key of one lane tile).  Each chip's attends read and
  write its own flat batch where it lies (``tile_attn_out`` ``flat``), on
  narrow steps and on wide ones, and the host commits what it commits
  under the reference lowerings, which move rows to ``[slots, chunk]`` and
  back."""
  epl.init()
  cfg = dataclasses.replace(REF_CFG, heads=8, index_head_dim=128)
  model, shell_of = glue.build_model(cfg, F32)
  params = glue.program_params(
      cfg, ref.seed_key(2 ** 31 + 47), shell_of(jnp.zeros((1, 8), jnp.int32)))
  monkeypatch.setattr(engine_lib, "flat_width", lambda slots, chunk: 40)
  monkeypatch.setattr(engine_lib, "narrow_width", lambda width, slots: 16)
  reqs = _requests(10, hi=50)
  got = {}
  for impl, form in (("reference", "slots"), ("interpret", "flat")):
    for mod in ("kv_write", "slot_attention", "dsa_index", "moe_gmm"):
      monkeypatch.setattr(
          importlib.import_module(f"easyparallellibrary_tpu.kernels.{mod}"),
          "_backend_impl", lambda: impl)
    stats = ServingStats()
    with jax.default_matmul_precision("highest"):
      got[impl], eng = _serve(model, params, reqs, 8, 16, _mesh(2), stats)
    assert eng.slot_axis == ("expert", 2)
    assert (eng.flat_width, eng.flat_narrow) == (40, 16)
    assert eng.lowerings["slot_attn_impl"] == impl
    assert eng.lowerings["tile_attn_out"] == form
    assert 0 < stats.flat_narrow_steps < stats.steps
  for uid, toks in got["reference"].items():
    np.testing.assert_array_equal(got["interpret"][uid], toks, str(uid))


def test_divided_engine_records_what_it_is(both):
  from easyparallellibrary_tpu.observability import trace as trace_lib
  model, params, _ = both
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    _, eng = _serve(model, params, _requests(6), 8, 4, _mesh())
    events = tracer.events()
  finally:
    trace_lib.install(None)
  meta = {ev["name"]: ev["args"] for ev in events if ev["ph"] == "M"
          and ev["name"].startswith("serving/")}
  assert meta["serving/slot_axis"] == {"axis": "expert", "chips": 4,
                                       "slots_a_chip": 2}
  assert meta["serving/experts_held"] == {
      "first": 4, "count": 8, "published": 16, "chips": 4, "a_chip": 2}
  counters = {ev["name"] for ev in events if ev["ph"] == "C"}
  assert {"serving/exchange_rows_out", "serving/exchange_rows_in",
          "serving/chip_live_max", "serving/chip_live_min",
          "serving/held_assignments"} <= counters
  # every kernel rule was resolved for what ONE chip holds
  assert eng.lowerings == kv_lib.step_lowerings(model.cfg, 2, 4, None)


@pytest.mark.parametrize("what,kw", [
    ("the paged cache", dict(paged=True)),
    ("prefix caching", dict(paged=True, prefix_cache=True)),
    ("the guarded step", dict(resilience=True)),
    ("7 slots over 4 chips", dict(num_slots=7))])
def test_divided_engine_refuses_in_one_message(both, what, kw):
  model, params, _ = both
  kw = {"num_slots": 8, **kw}
  with pytest.raises(ValueError) as err:
    ContinuousBatchingEngine(model, params, prefill_chunk=4, mesh=_mesh(),
                             **kw)
  # the latent cache's own refusal comes first for a twin it refuses too
  assert ROADMAP_DIVIDED in str(err.value) or "ROADMAP item R5" in str(
      err.value)
  if what == "7 slots over 4 chips":
    assert what in str(err.value) and "R9" in str(err.value)


def test_a_model_without_the_axis_is_refused(both):
  from easyparallellibrary_tpu.serving._capabilities import check_divided

  class NoAxis:
    cfg = both[0].cfg

    def __call__(self, ids, decode=False, slot_cursors=None):
      return ids
  off = dict(paged=False, prefix_cache=False, speculative=False,
             resilient=False)
  with pytest.raises(ValueError, match="takes no expert_axis"):
    check_divided(NoAxis(), _mesh(), 8, **off)
  check_divided(both[0], _mesh(), 8, **off)
  check_divided(NoAxis(), _mesh(1), 8, **off)     # nothing is divided


# --------------------------------------------------- (e) a mesh of one --


def test_a_mesh_of_one_is_the_program_it_was(both):
  """An ``expert`` axis of one chip divides nothing: no slot axis, the
  rules as without a mesh, no exchange in the step, the same tokens."""
  model, params, _ = both
  reqs = _requests(8)
  want, plain = _serve(model, params, reqs, 8, 4)
  got, eng = _serve(model, params, reqs, 8, 4, _mesh(1))
  assert eng.slot_axis is None and kv_lib.slot_axis(_mesh(1)) is None
  assert eng.lowerings == plain.lowerings
  assert (eng.flat_width, eng.flat_narrow) == (plain.flat_width,
                                               plain.flat_narrow)
  for u in want:
    np.testing.assert_array_equal(got[u], want[u])
  text = eng._step_fn.lower(*eng._step_args(
      eng.scheduler.plan_step() or _idle_plan(eng), None)).as_text()
  assert "all_to_all" not in text and "all-to-all" not in text


def _idle_plan(eng):
  eng.submit(Request(uid="again", prompt=np.arange(5, dtype=np.int32),
                     max_new_tokens=2))
  return eng.scheduler.plan_step()


# ------------------------------------------- the scheduler's runs of slots --


def test_scheduler_holds_each_run_of_slots_to_the_width():
  """Two runs of four slots, 16 rows each: a run's prefill grants share
  its own rows; the other run's are its own."""
  sched = FCFSScheduler(num_slots=8, prefill_chunk=8, max_seq_len=64,
                        width=16, slot_groups=2)
  for i in range(8):
    sched.submit(Request(uid=i, prompt=np.arange(30, dtype=np.int32),
                         max_new_tokens=2))
  plan = sched.plan_step()
  by_run = plan.num_valid.reshape(2, 4).sum(axis=1)
  assert by_run.tolist() == [16, 16]
  assert plan.flat_trimmed == 8 * 8 - 32
  with pytest.raises(ValueError, match="do not divide"):
    FCFSScheduler(num_slots=6, prefill_chunk=8, max_seq_len=64, width=16,
                  slot_groups=4)
