"""The token-flat fused step (models/slot_core.py ``SlotRows``,
serving/engine.py ``flat_width``): the position-wise layers run on ``T``
rows, the live positions of the step's slots one after another, and the
head on the one row a slot samples from.

What must hold, on each of the four decoders: every step the engine runs
commits the tokens, the cursors, the cache rows under each cursor and the
recurrent state that ``slot_step_logits`` at FULL width (every position of
every slot, the oracle of the model tests) gives for the same plan, over a
run that mixes prefill chunks, decodes, idle slots, slots used again
(``reset``), steps planned past an uncommitted one (``from_prev``) and
grants the width trimmed; a request finishes with the same tokens whatever
the width; a step with exactly ``T`` live rows, and one with none, read
what they should.

Toy widths, float32: 64 slots x chunk 8 is 512 positions on a flat batch
of 256 (half of them, a multiple of 128; a toy engine whose half
rounds up past ``slots x chunk`` runs at full width).

The same program holds a second width, ``narrow_width(256, 64)`` = 128,
which a step whose live positions fit it runs its layers on
(``slot_layers``): every engine here has both, so every comparison
above is also one of the two-width step, and the one-width engine (the
derivation patched in the test) must serve the same tokens.
"""

import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import GPT, GPTConfig  # noqa: E402
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, engine as engine_lib,
    kv_cache as kv_lib)
from easyparallellibrary_tpu.testing import chaos  # noqa: E402
from perfbench.reference import dots3_note as dots3_ref  # noqa: E402
from perfbench.reference import glm4_moe_lite as glm_ref  # noqa: E402
from perfbench.reference import jamba as jamba_ref  # noqa: E402
from perfbench.reference import lfm2_moe as lfm2_ref  # noqa: E402
from perfbench.runners import epl_dots3_note as dots3_glue  # noqa: E402
from perfbench.reference import smallthinker as st_ref  # noqa: E402
from perfbench.runners import epl_smallthinker as st_glue  # noqa: E402
from perfbench.runners import epl_glm4_moe_lite as glm_glue  # noqa: E402
from perfbench.runners import epl_jamba as jamba_glue  # noqa: E402
from perfbench.runners import epl_lfm2_moe as lfm2_glue  # noqa: E402

VOCAB = 256
SLOTS, CHUNK = 64, 8
WIDTH = 256                       # flat_width(64, 8): half of 512
NARROW = 128                      # narrow_width(256, 64): half again
F32 = {"dtype": "float32", "param_dtype": "float32"}
GPT_CFG = GPTConfig(vocab_size=VOCAB, num_layers=2, num_heads=4, d_model=32,
                    d_ff=64, max_seq_len=128, dtype=jnp.float32)
# The toy cuts of tests/test_step_overlap.py, with room for longer prompts.
JAMBA_CFG = jamba_ref.JambaConfig(
    num_hidden_layers=4, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=1, vocab_size=VOCAB,
    attn_layer_period=4, attn_layer_offset=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, n_positions=128,
    initializer_range=0.2)
GLM_CFG = glm_ref.Glm4MoeLiteConfig(
    num_hidden_layers=3, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, vocab_size=VOCAB, n_positions=128,
    initializer_range=0.2, bias_std=0.05)
LFM2_CFG = lfm2_ref.Lfm2MoeConfig(
    layer_types=("conv", "full_attention", "conv"), hidden_size=128,
    intermediate_size=128, moe_intermediate_size=64, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, vocab_size=VOCAB, n_positions=128,
    initializer_range=0.1, bias_std=0.05)
# tests/test_dots3_note.py's toy cut: a selecting layer that keeps 4 rows,
# window layers behind 5, 3 of 8 experts held.
DOTS3_CFG = dots3_ref.Dots3NoteConfig(
    layer_types=(dots3_ref.FULL, dots3_ref.FULL, dots3_ref.SLIDING,
                 dots3_ref.SLIDING, dots3_ref.SLIDING),
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    full=dots3_ref.LatentSizes(heads=4, q_rank=32, kv_rank=32, nope=16,
                               rope=8, value=16, theta=8e7),
    swa=dots3_ref.LatentSizes(heads=2, q_rank=32, kv_rank=48, nope=24,
                              rope=8, value=16, theta=5e4),
    index_n_heads=2, index_head_dim=16, index_topk=4, sliding_window_size=5,
    router_width=8, experts_first=2, n_routed_experts=3, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, vocab_size=VOCAB,
    n_positions=128, initializer_range=0.2, bias_std=0.05)
DECODERS = ("gpt2", "hybrid", "glm-experts", "lfm2")
TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(autouse=True)
def _no_ambient_tracer():
  yield
  trace_lib.reset()


FAMILIES = {"hybrid": (jamba_glue, jamba_ref, JAMBA_CFG),
            "glm-experts": (glm_glue, glm_ref, GLM_CFG),
            "lfm2": (lfm2_glue, lfm2_ref, LFM2_CFG)}
# Served in the two-width cases only: the shadow and the oracles above are
# the four older families'.
DOTS3 = (dots3_glue, dots3_ref, DOTS3_CFG)
# tests/test_smallthinker.py's toy cut, one period: a full layer without
# positions and three window layers whose K/V pair is a ring (14 heads on 2
# of 8, window 8), 8 experts top-3 routed from the layer's input.
ST_CFG = st_ref.SmallThinkerConfig(
    hidden_size=64, num_attention_heads=14, num_key_value_heads=2, head_dim=8,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, sliding_window_size=8,
    sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
    vocab_size=VOCAB, n_positions=128, initializer_range=0.2)
LATER = {"dots3": DOTS3, "smallthinker": (st_glue, st_ref, ST_CFG)}
# What each family's own test file allows between the program's logits and
# its plain reference's (tests/test_jamba.py, test_glm_moe.py,
# test_lfm2_moe.py); the GPT-2 block against its own ``[B, S]`` forward.
LOGIT_TOL = {"gpt2": 2e-5, "hybrid": 2e-4, "glm-experts": 3e-4, "lfm2": 3e-4}
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def decoders():
  """``{name: (model, params)}``, built once."""
  epl.init()
  gpt = GPT(GPT_CFG)
  out = {"gpt2": (gpt, gpt.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))["params"])}
  for name, (glue, ref, cfg) in dict(FAMILIES, **LATER).items():
    model, shell_of = glue.build_model(
        cfg, dict(F32, ring_tile=8) if name in LATER else F32)
    out[name] = (model, glue.program_params(
        cfg, ref.seed_key(SEED), shell_of(jnp.zeros((1, 8), jnp.int32))))
  return out


@pytest.fixture(scope="module")
def plain_logits(decoders):
  """``{name: ids [B, S] -> logits [B, S, vocab]}`` of the plain reference
  (perfbench/reference, float32, whole sequence, no cache) on the weights
  the program serves; the GPT-2 block's own ``[B, S]`` forward."""
  gpt, gpt_params = decoders["gpt2"]
  out = {"gpt2": jax.jit(lambda ids: gpt.apply({"params": gpt_params}, ids))}
  for name, (_, ref, cfg) in FAMILIES.items():
    ref_params = jax.jit(lambda k, ref=ref, cfg=cfg: ref.init_params(cfg, k))(
        ref.seed_key(SEED))
    out[name] = jax.jit(lambda ids, ref=ref, cfg=cfg, p=ref_params:
                        ref.logits(cfg, p, ids))
  return out


def _engine(decoders, name, **kwargs):
  epl.init()
  model, params = decoders[name]
  return ContinuousBatchingEngine(model, params, num_slots=SLOTS,
                                  prefill_chunk=CHUNK, stats=ServingStats(),
                                  **kwargs)


REQUESTS = 90


def _requests():
  """90 requests over 64 slots.  The first 64 bring prompts of two to six
  chunks, so the first steps hold more prefill than the width takes; the
  rest bring prompts from under a chunk to five of them into slots used a
  second time, so later steps mix chunks with decodes."""
  rng = np.random.RandomState(3)
  lengths = np.concatenate([
      rng.randint(2 * CHUNK + 1, 6 * CHUNK, SLOTS),
      rng.randint(3, 5 * CHUNK, REQUESTS - SLOTS)])
  return [Request(uid=i,
                  prompt=rng.randint(0, VOCAB, (n,)).astype(np.int32),
                  max_new_tokens=int(new))
          for i, (n, new) in enumerate(zip(
              lengths, rng.randint(2, 9, REQUESTS)))]


def _under_cursor(path) -> bool:
  return any(getattr(k, "key", "").startswith("cached_") for k in path)


def _assert_same_state(kv, want_kv, cursors):
  """Recurrent state whole; K/V and latent rows under each slot's cursor
  (beyond it a window holds a dead position's garbage, which differs by
  construction and is read by nothing)."""
  cursors = np.asarray(cursors)
  flat, _ = jax.tree_util.tree_flatten_with_path(kv)
  for (path, got), want in zip(flat, jax.tree_util.tree_leaves(want_kv)):
    got, want = np.asarray(got), np.asarray(want)
    if not _under_cursor(path):
      np.testing.assert_allclose(got, want, err_msg=str(path), **TOL)
      continue
    rows = np.arange(got.shape[1])[None] < cursors[:, None]
    rows = rows.reshape(rows.shape + (1,) * (got.ndim - 2))
    np.testing.assert_allclose(np.where(rows, got, 0),
                               np.where(rows, want, 0), err_msg=str(path),
                               **TOL)


class _Shadow(chaos._StepFnWrapper):
  """Over an engine's compiled step: every call also runs the same plan at
  FULL width through ``slot_step_logits`` on a cache of the wrapper's own,
  and compares what the two commit.  ``seen``: per plan ``(live positions,
  slots fed, any from_prev, any reset)``."""

  def __init__(self, eng):
    super().__init__(eng)
    model, C = eng.model, eng.chunk
    lowerings = kv_lib.resolved(eng.lowerings)

    @jax.jit
    def full(params, kv, cursors, tokens, num_valid, reset, prev, from_prev):
      tokens = tokens.at[:, 0].set(jnp.where(from_prev, prev, tokens[:, 0]))
      cursors = jnp.where(reset, 0, cursors)
      state = dict(reset=reset) if eng._recurrent else {}
      logits, kv = slot_step_logits(
          model, params, kv, tokens, cursors, num_valid=num_valid, **state,
          **lowerings)
      last = jnp.take_along_axis(
          logits, jnp.clip(num_valid - 1, 0, C - 1)[:, None, None],
          axis=1)[:, 0]
      return jnp.argmax(last, -1).astype(jnp.int32), kv, cursors + num_valid

    self.full = full
    self.kv, self.cursors = kv_lib.allocate_kv_cache(
        model.cfg, eng.num_slots, C)
    self.prev = jnp.zeros_like(self.cursors)
    self.seen = []

  def __call__(self, params, kv, cursors, tokens, num_valid, reset, prev,
               from_prev, *sampling):
    want_tok, self.kv, self.cursors = self.full(
        params, self.kv, self.cursors, tokens, num_valid, reset, self.prev,
        from_prev)
    out = self.inner(params, kv, cursors, tokens, num_valid, reset, prev,
                     from_prev, *sampling)
    got_tok, got_kv, got_cursors = out[0], out[-2], out[-1]
    fed = np.asarray(num_valid) > 0
    np.testing.assert_array_equal(np.asarray(got_tok)[fed],
                                  np.asarray(want_tok)[fed])
    np.testing.assert_array_equal(np.asarray(got_cursors),
                                  np.asarray(self.cursors))
    _assert_same_state(got_kv, self.kv, got_cursors)
    # an idle slot's sample is garbage on both sides and read by nothing
    self.prev = jnp.where(jnp.asarray(fed), want_tok, got_tok)
    self.seen.append((int(np.asarray(num_valid).sum()), int(fed.sum()),
                      bool(np.asarray(from_prev).any()),
                      bool(np.asarray(reset).any())))
    return out


def _drive(eng, requests):
  """Submit a slot's worth at once, the rest one a step; run to the
  end."""
  later = list(requests)
  for req in later[:SLOTS]:
    eng.submit(req)
  del later[:SLOTS]
  while eng.has_work or later:
    if later:
      eng.submit(later.pop(0))
    eng.step()
  return {uid: np.asarray(fin.tokens) for uid, fin in eng.finished.items()}


@pytest.mark.parametrize("name", DECODERS)
def test_every_step_commits_what_full_width_commits(decoders, name):
  eng = _engine(decoders, name)
  assert eng.flat_width == WIDTH < SLOTS * CHUNK
  assert eng.scheduler.width == WIDTH
  seen = _Shadow(eng).seen
  outputs = _drive(eng, _requests())
  assert len(outputs) == REQUESTS
  live = [s[0] for s in seen]
  # the first plans hold all the width takes and no more ...
  assert max(live) == live[0] == live[1] == WIDTH
  assert eng.stats.flat_trimmed_steps >= 2
  # ... later ones mix chunks with decodes, leave slots idle, start slots
  # again and read the step before's sample off the device
  assert any(0 < fed < SLOTS for _, fed, _, _ in seen)
  assert any(ahead for _, _, ahead, _ in seen)
  assert sum(reset for _, _, _, reset in seen) > 3
  assert eng._step_fn._cache_size() == 1


def _twin(decoders, name, twin):
  from easyparallellibrary_tpu.serving.speculative import NgramDrafter
  return _engine(decoders, name, **{
      "plain": {}, "guarded": dict(resilience=True),
      "spec": dict(drafter=NgramDrafter(k=3, ngram_max=3))}[twin])


def _two_bursts(eng):
  """``_requests()``, whose first plans fill the width and whose later
  ones fit the narrow one, then a second slot's worth of long prompts at
  once into the drained engine: the plans cross ``NARROW`` downwards and
  upwards again."""
  _drive(eng, _requests())
  rng = np.random.RandomState(5)
  return _drive(eng, [
      Request(uid=REQUESTS + i, max_new_tokens=3,
              prompt=rng.randint(0, VOCAB, (n,)).astype(np.int32))
      for i, n in enumerate(rng.randint(2 * CHUNK + 1, 5 * CHUNK, SLOTS))])


# Against the engine at FULL width (the plan is never trimmed), and, two
# widths against ONE (``narrow_width`` patched to give the width back: the
# same plans, the same tokens), each family and each twin.
WIDTH_CASES = [(name, "plain", "full") for name in DECODERS] + [
    (name, "plain", "one") for name in DECODERS + tuple(LATER)] + [
        ("gpt2", "spec", "one"), ("gpt2", "guarded", "one")]


@pytest.mark.parametrize("name,twin,oracle", WIDTH_CASES)
def test_requests_finish_the_same_whatever_the_width(decoders, name, twin,
                                                     oracle, monkeypatch):
  if oracle == "one":
    tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  flat = _twin(decoders, name, twin)
  assert (flat.flat_width, flat.flat_narrow) == (WIDTH, NARROW)
  lives = []
  real = flat._step_fn
  flat._step_fn = lambda *a: (lives.append(int(np.asarray(a[4]).sum())),
                              real(*a))[1]
  got = _two_bursts(flat) if oracle == "one" else _drive(flat, _requests())
  assert flat.stats.flat_trimmed > 0
  assert real._cache_size() == 1
  if oracle == "full":
    monkeypatch.setattr(engine_lib, "flat_width",
                        lambda slots, chunk: slots * chunk)
    other = _twin(decoders, name, twin)
    assert other.flat_width == other.scheduler.width == SLOTS * CHUNK
    want = _drive(other, _requests())
    assert other.stats.flat_trimmed == 0
    # the trimmed requests took more steps and no other tokens
    assert flat._steps >= other._steps
  else:
    # ``serving/flat_narrow`` counts exactly the steps whose live positions
    # fit the narrow width, and the plans crossed it in both directions
    narrow = [int(live <= NARROW) for live in lives]
    assert [e["args"]["value"] for e in tracer.events()
            if e["ph"] == "C" and e["name"] == "serving/flat_narrow"] == narrow
    moves = set(zip(narrow, narrow[1:]))
    assert {(0, 1), (1, 0)} <= moves
    assert flat.stats.flat_narrow_steps == sum(narrow)
    assert flat.stats.summary()["flat_narrow_step_share"] == pytest.approx(
        sum(narrow) / len(narrow))
    monkeypatch.setattr(engine_lib, "narrow_width",
                        lambda width, num_slots: width)
    other = _twin(decoders, name, twin)
    assert other.flat_narrow == other.flat_width == WIDTH
    want = _two_bursts(other)
    assert other.stats.flat_narrow_steps == 0
    assert other._steps == flat._steps
  assert got.keys() == want.keys()
  for uid in want:
    np.testing.assert_array_equal(got[uid], want[uid], err_msg=str(uid))


@pytest.mark.parametrize("name", DECODERS)
def test_a_step_with_exactly_the_width_live_and_one_with_nothing(decoders,
                                                                 name):
  """Direct calls: ``num_valid`` summing to exactly ``T`` (the last live
  position sits on the last row), then an all-idle step, which changes no
  state under a cursor and no recurrent state."""
  eng = _engine(decoders, name)
  model, params = decoders[name]
  state = dict(reset=jnp.zeros((SLOTS,), bool)) if eng._recurrent else {}
  call = jax.jit(
      lambda kv, cur, tokens, nv, width, head_pos=None: slot_step_logits(
          model, params, kv, tokens, cur, num_valid=nv, width=width,
          head_pos=head_pos, **state), static_argnums=4)
  rng = np.random.RandomState(11)
  tokens = jnp.asarray(rng.randint(0, VOCAB, (SLOTS, CHUNK)), jnp.int32)
  # 31 whole chunks, a decode, 7 positions, idle slots: 256 exactly, the
  # last fed slot ending on row T - 1
  nv = np.zeros((SLOTS,), np.int32)
  nv[:31], nv[48], nv[57] = CHUNK, 1, 7
  assert nv.sum() == WIDTH
  nv = jnp.asarray(nv)
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, SLOTS, CHUNK)
  want, want_kv = call(kv, cur, tokens, nv, None)
  head_pos = jnp.clip(nv - 1, 0, CHUNK - 1)
  got, got_kv = call(kv, cur, tokens, nv, WIDTH, head_pos)
  assert want.shape == (SLOTS, CHUNK, VOCAB) and got.shape == (SLOTS, VOCAB)
  fed = np.asarray(nv) > 0
  want_last = np.take_along_axis(
      np.asarray(want), np.asarray(head_pos)[:, None, None], axis=1)[:, 0]
  np.testing.assert_allclose(np.asarray(got)[fed], want_last[fed], **TOL)
  _assert_same_state(got_kv, want_kv, nv)
  # K+1 rows a slot through the same argument (the speculating step's)
  some = jnp.clip(head_pos[:, None] - jnp.arange(3)[None], 0, CHUNK - 1)
  rows, _ = call(kv, cur, tokens, nv, WIDTH, some)
  assert rows.shape == (SLOTS, 3, VOCAB)
  full_rows = np.take_along_axis(np.asarray(want),
                                 np.asarray(some)[:, :, None], axis=1)
  np.testing.assert_allclose(np.asarray(rows)[fed], full_rows[fed], **TOL)
  # nothing live: nothing under a cursor moves, no state advances
  idle = jnp.zeros((SLOTS,), jnp.int32)
  _, after = call(got_kv, nv, tokens, idle, WIDTH, head_pos)
  _assert_same_state(after, got_kv, nv)


# Layers whose mixer owns a leaf that grows with the context and stands
# outside the conditionals (models/slot_core.py:SplitLayer): the hybrid's one
# attention layer of four, every latent layer, LFM2's attention layer
# between two convolutions.  A GPT-2 block's K/V pair stands inside.
SPLIT = {"gpt2": 0, "hybrid": 1, "glm-experts": 3, "lfm2": 1, "dots3": 5,
         "smallthinker": 4}


def _conditionals(jaxpr):
  """Every ``cond`` equation of ``jaxpr``, those of inner jaxprs too."""
  for eqn in jaxpr.eqns:
    if eqn.primitive.name == "cond":
      yield eqn
    for sub in jax.core.jaxprs_in_params(eqn.params):
      yield from _conditionals(sub)


@pytest.mark.parametrize("name", DECODERS + tuple(LATER))
def test_the_second_width_stands_round_the_layers_and_nothing_else(
    decoders, name, monkeypatch):
  """What the second width may cost in set-up is the position-wise layers,
  traced and lowered at two row counts (PERF.md, PR 41); everything else is
  in the program once.  In the step as the engine builds it: one
  conditional more than the split layers (the runs between their mixers),
  above what the one-width step has (none where the derivation gives the
  width back); no cache leaf of a split layer is what a conditional
  returns, so none is copied for one; the head's matrix product and the
  sampler's sort stand there once, on ``[slots, ..]`` rows."""
  from easyparallellibrary_tpu.observability import device as device_lib

  def program():
    eng = _engine(decoders, name)
    specs = []
    real, note = eng._step_fn, eng._note_step_specs
    eng._note_step_specs = lambda args: (
        specs.append(device_lib.specs_of(args)), note(args))[1]
    eng.submit(Request(uid=0, prompt=np.arange(1, 7, dtype=np.int32),
                       max_new_tokens=3))
    eng.run()
    assert real._cache_size() == 1
    text = real.lower(*specs[0]).as_text()
    count = lambda pattern: len(re.findall(pattern, text))
    conds = list(_conditionals(jax.make_jaxpr(real)(*specs[0]).jaxpr))
    return eng, conds, (
        count(rf"stablehlo\.dot_general .* -> tensor<{SLOTS}x{VOCAB}xf32>"),
        count(r"stablehlo\.sort"), count(r"stablehlo\.case"))

  eng, conds, (heads, sorts, cases) = program()
  assert (eng.flat_width, eng.flat_narrow) == (WIDTH, NARROW)
  split_leaves = {
      leaf.shape for path, leaf in jax.tree_util.tree_leaves_with_path(
          eng._kv) if SPLIT[name] and re.search(
              r"attn|latent", jax.tree_util.keystr(path))}
  assert bool(split_leaves) == bool(SPLIT[name])
  returned = {v.aval.shape for eqn in conds for v in eqn.outvars}
  assert not split_leaves & returned, split_leaves & returned
  if name == "gpt2":        # what the check sees where a leaf does stand inside
    assert {leaf.shape for leaf in jax.tree_util.tree_leaves(eng._kv)
            } <= returned
  monkeypatch.setattr(engine_lib, "narrow_width",
                      lambda width, num_slots: width)
  eng, _, (heads_one, sorts_one, cases_one) = program()
  assert eng.flat_narrow == WIDTH
  assert heads == heads_one == 1
  if name in ("gpt2", "hybrid"):     # an expert layer sorts its assignments
    assert sorts == sorts_one == 1
  assert cases - cases_one == SPLIT[name] + 1


@pytest.mark.parametrize("name", DECODERS + tuple(LATER))
def test_a_step_reads_its_parameters_without_evaluating_an_initializer(
    decoders, name, monkeypatch):
  """Tracing a family's fused step asks flax's own ``Scope.param`` for
  nothing: every module that declares parameters reads the ones that exist
  through ``ops/layers.py:HeldParams`` (tests/test_held_params.py), so no
  initializer is evaluated abstractly a parameter an access, at either
  width.  A module that declares one without the mixin is named here."""
  from flax.core import scope as scope_lib
  seen, real = [], scope_lib.Scope.param

  def param(self, name, *args, **kwargs):
    seen.append("/".join(self.path + (name,)))
    return real(self, name, *args, **kwargs)
  monkeypatch.setattr(scope_lib.Scope, "param", param)
  eng = _engine(decoders, name)
  eng.submit(Request(uid=0, prompt=np.arange(1, 7, dtype=np.int32),
                     max_new_tokens=2))
  eng.run()
  assert eng._step_fn._cache_size() == 1
  assert not seen, sorted(set(seen))


# ------------------------------------------------------------ the scheduler --


def test_the_width_follows_slots_and_chunk_alone():
  """One rule in ``num_slots`` and ``chunk`` for every model and twin:
  half the positions up to a multiple of 128, never under one row a slot,
  never above every position."""
  width = engine_lib.flat_width
  assert (width(96, 16), width(128, 8), width(96, 8), width(128, 16)) == (
      768, 512, 384, 1024)
  assert width(SLOTS, CHUNK) == WIDTH
  # one row a slot at the least (a chunk of one or two positions) ...
  assert (width(256, 1), width(300, 2), width(130, 2)) == (256, 384, 256)
  # ... and full, where the map is a reshape, wherever the half rounds up
  # past every position
  assert (width(4, 8), width(8, 16), width(7, 16)) == (32, 128, 112)
  # the second width: half of the first up to a multiple of 128, never
  # under a row a slot up to one; the serving cells' five geometries
  narrow = lambda slots, chunk: engine_lib.narrow_width(
      width(slots, chunk), slots)
  assert [narrow(*g) for g in ((96, 16), (128, 8), (96, 8), (128, 16),
                               (32, 32))] == [384, 256, 384, 512, 256]
  assert narrow(SLOTS, CHUNK) == NARROW
  # where a row a slot, or half the width, rounds up to MORE than half the
  # width (the expert cell's 384 -> 256 above, 72 x 8 the same) or to the
  # width itself there is one width, and the step is built without a
  # conditional
  for slots, chunk in ((96, 8), (72, 8), (256, 1), (300, 2), (130, 2),
                       (4, 8), (8, 16)):
    assert narrow(slots, chunk) == width(slots, chunk)
  assert narrow(200, 4) == 256 < width(200, 4)
  from easyparallellibrary_tpu.models.slot_core import slot_rows
  some = jnp.ones((130,), jnp.int32)
  assert slot_rows(some, some, 130, 2, width=256, narrow=256).narrow is None
  assert slot_rows(some, some, 130, 2, width=256, narrow=128).narrow == 128
  for slots, chunk in ((96, 16), (300, 2), (17, 16), (1, 1)):
    assert slots <= width(slots, chunk) <= slots * chunk


def _random_drive(sched, ahead, steps=400, seed=5):
  """Random arrivals into ``sched``, planned one step ahead of the commit
  or not; yields every plan."""
  rng = np.random.RandomState(seed)
  outstanding, uid = None, 0
  for step in range(steps):
    # a burst first, so every slot prefills at once; then a trickle
    for _ in range(100 if step == 0 else rng.poisson(1.2)):
      sched.submit(Request(
          uid=uid, max_new_tokens=int(rng.randint(1, 12)),
          prompt=rng.randint(0, VOCAB, (rng.randint(1, 90),)).astype(
              np.int32)))
      uid += 1
    plan = sched.plan_step(ahead=ahead and outstanding is not None)
    if outstanding is not None and ahead:
      sched.commit(rng.randint(0, VOCAB, (sched.num_slots,)))
    if plan is not None:
      yield plan
      if not ahead:
        sched.commit(rng.randint(0, VOCAB, (sched.num_slots,)))
    outstanding = plan if ahead else None


@pytest.mark.parametrize("ahead", [False, True], ids=["serial", "ahead"])
@pytest.mark.parametrize("budget", [0, 64, 4096],
                         ids=["uncapped", "budget-under", "budget-over"])
def test_no_plan_holds_more_than_the_width(ahead, budget):
  """Decoding slots first, one row each and never held back; the prefill
  grants share the rest in admission order, the one that reaches the last
  row cut short, the ones behind it waiting; a configured budget under
  what the width leaves binds first and the width then cuts nothing."""
  from easyparallellibrary_tpu.serving.scheduler import FCFSScheduler
  sched = FCFSScheduler(num_slots=SLOTS, prefill_chunk=CHUNK,
                        max_seq_len=128, prefill_token_budget=budget,
                        width=WIDTH)
  worst = fullest = trimmed = 0
  for plan in _random_drive(sched, ahead):
    live = int(plan.num_valid.sum())
    assert live == plan.prefill_tokens + plan.decode_tokens <= WIDTH
    worst, fullest = max(worst, live), max(fullest, plan.prefill_tokens)
    trimmed += plan.flat_trimmed
    for slot, state in sched.active.items():
      if state.planned_pos >= len(state.prefix) and not plan.prefilling[slot]:
        # past its prompt: it decodes this step, whatever the width, unless
        # its last token is already on the device
        assert plan.num_valid[slot] == 1 or (
            state.planned_generated >= state.req.max_new_tokens)
    if plan.flat_trimmed:
      # every row is taken, and the slots were served in admission order:
      # whole grants, then at most one cut short, then nothing
      assert live == WIDTH
      grants = [int(plan.num_valid[slot]) for slot in sched._admit_order
                if slot in sched.active
                and (plan.prefilling[slot] or sched.active[slot].planned_pos
                     < len(sched.active[slot].prefix))]
      wants = [min(CHUNK, len(sched.active[slot].prefix)
                   - sched.active[slot].planned_pos + int(plan.num_valid[slot]))
               for slot in sched._admit_order if slot in sched.active
               and (plan.prefilling[slot] or sched.active[slot].planned_pos
                    < len(sched.active[slot].prefix))]
      short = [i for i, (g, w) in enumerate(zip(grants, wants)) if g < w]
      assert all(g == 0 for g in grants[short[0] + 1:]), (grants, wants)
      assert sum(w - g for g, w in zip(grants, wants)) == plan.flat_trimmed
  if budget == 64:
    assert fullest == 64 and trimmed == 0
  else:
    assert worst == WIDTH and trimmed > 0


def test_drafts_ride_the_rows_the_plan_leaves():
  """A speculating scheduler's draft caps fit the width with the plan's own
  positions, in admission order; what does not fit is counted."""
  from easyparallellibrary_tpu.serving.scheduler import FCFSScheduler
  slots, width = 64, 256
  sched = FCFSScheduler(num_slots=slots, prefill_chunk=CHUNK,
                        max_seq_len=128, spec_k=CHUNK - 1, width=width)
  for uid in range(slots):
    sched.submit(Request(uid=uid, prompt=np.arange(1, 4, dtype=np.int32),
                         max_new_tokens=40))
  sched.plan_step()
  sched.commit(np.zeros((slots,), np.int32))
  plan = sched.plan_step()               # every slot decodes and may draft
  assert plan.decode_tokens == slots and plan.prefill_tokens == 0
  caps = [int(plan.draft_cap[slot]) for slot in sched._admit_order]
  assert sum(caps) == width - slots
  assert caps == [CHUNK - 1] * 27 + [3] + [0] * (slots - 28)
  assert plan.flat_trimmed == slots * (CHUNK - 1) - (width - slots)


@pytest.mark.parametrize("name", ["gpt2", "glm-experts"])
def test_the_step_says_what_it_held_and_what_the_width_held_back(decoders,
                                                                 name):
  """``serving/flat_positions`` and ``serving/flat_trimmed`` every step,
  in the trace, the per-step record and ``ServingStats``;
  ``serving/flat_width`` once; an expert model's ``serving/
  routed_positions`` is what it was (the same sum); the trace validates."""
  epl.init()
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  records = []
  writer = type("W", (), {"write": lambda self, step, rec:
                          records.append(dict(rec))})()
  eng = _engine(decoders, name, metrics_writer=writer)
  lives = []
  real = eng._step_fn
  eng._step_fn = lambda *a: (lives.append(int(np.asarray(a[4]).sum())),
                             real(*a))[1]
  _drive(eng, _requests())
  events = tracer.events()
  counters = lambda n: [e["args"]["value"] for e in events
                        if e["ph"] == "C" and e["name"] == n]
  meta = [e["args"] for e in events
          if e["ph"] == "M" and e["name"] == "serving/flat_width"]
  assert meta == [{"width": WIDTH, "narrow": NARROW,
                   "positions": SLOTS * CHUNK}]
  assert counters("serving/flat_positions") == lives
  assert max(lives) == WIDTH
  trimmed = counters("serving/flat_trimmed")
  assert len(trimmed) == len(lives) and min(trimmed) == 0 < max(trimmed)
  # a step the width cut is a full one
  assert all(live == WIDTH for live, cut in zip(lives, trimmed) if cut)
  assert [r["flat_positions"] for r in records] == lives
  assert [r["flat_trimmed"] for r in records] == trimmed
  summary = eng.stats.summary()
  assert summary["flat_positions_per_step"] == pytest.approx(
      sum(lives) / len(lives))
  assert summary["flat_trimmed_step_share"] == pytest.approx(
      sum(cut > 0 for cut in trimmed) / len(trimmed))
  assert eng.stats.flat_trimmed == sum(trimmed)
  routed = counters("serving/routed_positions")
  assert routed == (lives if name == "glm-experts" else [])
  with tempfile.TemporaryDirectory() as tmp:
    trace_lib.validate_trace(tracer.export(os.path.join(tmp, "t.json")))


# ------------------------------------------------------------- the oracles --


def _shaped_requests():
  """88 requests of few shapes (an oracle compiles once a shape): prompts
  of 5, 17, 30 or 44 tokens, 3 or 6 new ones."""
  rng = np.random.RandomState(7)
  return [Request(uid=i, max_new_tokens=(3, 6)[i % 2],
                  prompt=rng.randint(0, VOCAB, ((5, 17, 30, 44)[i // 2 % 4],)
                                     ).astype(np.int32))
          for i in range(88)]


@pytest.mark.parametrize("name", DECODERS)
def test_served_tokens_are_the_oracles_from_one_slot_live_to_all(
    decoders, plain_logits, name):
  """One request alone, then a burst that fills every slot and outruns the
  width, then joins into slots that leave: greedy tokens equal
  ``generate()``'s for the GPT-2 block, and for the families without one
  are the plain reference's best at every position (teacher-forced, within
  the family's tolerance of a tie); ONE compiled program all along."""
  eng = _engine(decoders, name)
  requests = _shaped_requests()
  lives = []
  real = eng._step_fn
  eng._step_fn = lambda *a: (lives.append(int(np.asarray(a[4]).sum())),
                             real(*a))[1]
  eng.submit(requests[0])
  eng.step(); eng.step()
  later = requests[1:]
  for req in later[:SLOTS]:
    eng.submit(req)
  del later[:SLOTS]
  while eng.has_work or later:
    if later:
      eng.submit(later.pop(0))
    eng.step()
  assert lives[0] == 5 and max(lives) == WIDTH
  assert eng.stats.flat_trimmed_steps > 0
  assert real._cache_size() == 1
  got = {uid: np.asarray(fin.tokens) for uid, fin in eng.finished.items()}
  assert len(got) == len(requests)
  shapes = {}
  for req in requests:
    shapes.setdefault((len(req.prompt), req.max_new_tokens), []).append(req)
  for (n, new), group in shapes.items():
    served = np.stack([got[r.uid] for r in group])
    assert served.shape == (len(group), n + new)
    if name == "gpt2":
      from easyparallellibrary_tpu.models.gpt import generate
      model, params = decoders[name]
      want = generate(model, params, np.stack([r.prompt for r in group]),
                      new, use_cache=True)
      np.testing.assert_array_equal(served, np.asarray(want))
      continue
    logits = np.asarray(plain_logits[name](jnp.asarray(served)))
    at = logits[:, n - 1:-1]                       # [B, new, vocab]
    taken = np.take_along_axis(at, served[:, n:, None], axis=2)[..., 0]
    assert float((at.max(-1) - taken).max()) < LOGIT_TOL[name]


@pytest.mark.parametrize("name", DECODERS)
def test_flat_logits_are_the_plain_references(decoders, plain_logits, name):
  """Three chunks a slot through ``slot_step_logits`` on the narrow batch,
  the slots fed in overlapping waves (a step holds whole chunks, cut ones
  and idle slots, and never more than the width): every position's logits
  against the plain reference's for the whole sequence."""
  model, params = decoders[name]
  recurrent = kv_lib.has_recurrent_state(model.cfg)
  call = jax.jit(lambda kv, cur, tokens, nv, reset: slot_step_logits(
      model, params, kv, tokens, cur, num_valid=nv, width=WIDTH,
      **(dict(reset=reset) if recurrent else {})))
  S = 3 * CHUNK
  ids = np.random.RandomState(13).randint(0, VOCAB, (SLOTS, S)).astype(
      np.int32)
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, SLOTS, CHUNK)
  fed = np.zeros((SLOTS,), np.int64)
  got = np.zeros((SLOTS, S, VOCAB), np.float32)
  step = 0
  while (fed < S).any():
    # a wave of 34 slots, moving by 20 a step; every third slot of it
    # feeds five positions where it could feed eight
    wave = (np.arange(SLOTS) - 20 * step) % SLOTS < 34
    nv = np.where(wave, np.minimum(
        np.where(np.arange(SLOTS) % 3 == step % 3, 5, CHUNK), S - fed), 0)
    assert 0 < nv.sum() <= WIDTH
    tokens = np.zeros((SLOTS, CHUNK), np.int32)
    for b in np.nonzero(nv)[0]:
      tokens[b, :nv[b]] = ids[b, fed[b]:fed[b] + nv[b]]
    logits, kv = call(kv, cur, jnp.asarray(tokens),
                      jnp.asarray(nv, jnp.int32), jnp.asarray(fed == 0))
    logits = np.asarray(logits)
    for b in np.nonzero(nv)[0]:
      got[b, fed[b]:fed[b] + nv[b]] = logits[b, :nv[b]]
    cur = cur + jnp.asarray(nv, jnp.int32)
    fed += nv
    step += 1
  want = np.asarray(plain_logits[name](jnp.asarray(ids)))
  assert float(np.abs(got - want).max()) < LOGIT_TOL[name]


# ------------------------------------------------------- the other twins --


def _wide_vocab_gpt():
  """A GPT-2 block whose head is most of its work: over every position
  of 64 x 8 it alone would cost more than the whole flat step."""
  cfg = GPTConfig(vocab_size=4096, num_layers=2, num_heads=4, d_model=32,
                  d_ff=64, max_seq_len=128, dtype=jnp.float32)
  model = GPT(cfg)
  return model, model.init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 4), jnp.int32))["params"]


def test_the_head_runs_on_the_rows_that_are_read_by_the_cost_card():
  """The compiled twins' cost cards (observability/device.py) and their
  lowered programs: the plain and the guarded step pay for a head of
  ``num_slots`` rows, the speculating one for ``K + 1`` a slot, none for
  ``num_slots x chunk``."""
  from easyparallellibrary_tpu.observability import device as device_lib
  from easyparallellibrary_tpu.serving.speculative import NgramDrafter
  epl.init()
  model, params = _wide_vocab_gpt()
  D, V, K = 32, 4096, 3
  previous = device_lib.get_introspector()
  intro = device_lib.install(device_lib.DeviceIntrospector())
  heads = {}
  try:
    for label, kw in (("plain", {}), ("guarded", dict(resilience=True)),
                      ("spec", dict(drafter=NgramDrafter(k=K, ngram_max=3)))):
      eng = ContinuousBatchingEngine(
          model, params, num_slots=SLOTS, prefill_chunk=CHUNK,
          track_prefix=f"serving/{label}", **kw)
      assert eng.flat_width == WIDTH
      specs = []
      real, note = eng._step_fn, eng._note_step_specs
      eng._note_step_specs = lambda args, note=note, specs=specs: (
          specs.append(device_lib.specs_of(args)), note(args))[1]
      eng.submit(Request(uid=label, prompt=np.arange(1, 7, dtype=np.int32),
                         max_new_tokens=4))
      eng.run()
      assert real._cache_size() == 1
      heads[label] = [
          [int(d) for d in m.group(1).split("x")]
          for m in re.finditer(
              r"stablehlo\.dot_general .*: \(tensor<([\dx]+)xf32>, "
              rf"tensor<[\dx]+xf32>\) -> tensor<[\dx]*x{V}xf32>",
              real.lower(*specs[0]).as_text())]
    flops = {label: intro.cards[f"serving/{label}/fused_step"].flops
             for label in heads}
  finally:
    device_lib.install(previous) if previous is not None else device_lib.reset()
  assert heads == {"plain": [[SLOTS, D]], "guarded": [[SLOTS, D]],
                   "spec": [[SLOTS, K + 1, D]]}
  # a head over every position would alone cost more than the plain and
  # the guarded step do whole (the speculating step's card also holds its
  # verification's sort of K + 1 rows a slot, taken or not)
  every_position = 2.0 * SLOTS * CHUNK * D * V
  a_row = 2.0 * SLOTS * D * V
  assert a_row < flops["plain"] < every_position
  assert flops["guarded"] == pytest.approx(flops["plain"], rel=0.02)
  assert flops["spec"] > flops["plain"] + K * a_row


def _served(eng, requests):
  for req in requests:
    eng.submit(req)
  return {uid: np.asarray(t) for uid, t in eng.run().items()}


def test_the_speculating_and_the_guarded_twin_on_the_narrow_batch(decoders):
  """Both twins at 64 x 8 on 256 rows, a burst that outruns the width: the
  speculating engine's drafts ride the rows the plan leaves and its greedy
  streams are the plain engine's; the guarded engine convicts the slot
  whose logits a fault poisons, retries it, and serves the same streams."""
  from easyparallellibrary_tpu.serving.speculative import NgramDrafter
  def requests():
    # repetitive prompts, so the n-gram drafter has something to propose
    rng = np.random.RandomState(17)
    return [Request(uid=i, max_new_tokens=6, prompt=np.tile(
        rng.randint(0, VOCAB, (4,)), 6)[:int(n)].astype(np.int32))
            for i, n in enumerate(np.random.RandomState(19).randint(
                9, 24, SLOTS + 8))]
  want = _served(_engine(decoders, "gpt2"), requests())
  spec = _engine(decoders, "gpt2", drafter=NgramDrafter(k=3, ngram_max=3))
  assert spec.flat_width == WIDTH == spec.scheduler.width
  got = _served(spec, requests())
  assert spec.stats.flat_trimmed_steps > 0 and spec.stats.drafted_tokens > 0
  assert spec._step_fn._cache_size() == 1
  assert got.keys() == want.keys()
  for uid in want:
    np.testing.assert_array_equal(got[uid], want[uid], err_msg=str(uid))
  guarded = _engine(decoders, "gpt2", resilience=True)
  assert guarded.flat_width == WIDTH
  inj = chaos.NaNLogitsInjector(guarded, bad_calls=(1, 4))
  got = _served(guarded, requests())
  assert guarded._bad_policy.step_retries > 0
  assert inj._cache_size() == 1
  for uid in want:
    np.testing.assert_array_equal(got[uid], want[uid], err_msg=str(uid))
