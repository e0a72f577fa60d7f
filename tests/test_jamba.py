"""The hybrid decoder (models/jamba.py) against the benchmark's plain
reference (perfbench/reference/jamba.py), and recurrent state beside K/V
in the continuous-batching engine.

Toy widths with both layer kinds: hidden 64, 4 layers (attention at layer
1, Mamba elsewhere), d_inner 128, 16 states, dt_rank 4, 4 query heads on 1
K/V head.  float32 on both sides, matmuls at ``highest``.  Tolerances:
logits are O(1) (weights N(0, 0.2): at width 64 the published 0.02 gives a
model that copies its input, which would test nothing), and program and
reference differ by float32 rounding in another order of the same sums, so
``2e-4`` absolute on logits is ~100 x what is seen (2e-6) and far below a
dropped term or a stale state (>= 1e-2).  What moves no arithmetic is held
bit for bit: an idle slot's state, the kernel against the reference
lowering in interpret mode, the weights' placement.
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import GPT, GPTConfig  # noqa: E402
from easyparallellibrary_tpu.models.blocks import (  # noqa: E402
    advance_window, gqa_causal_attention)
from easyparallellibrary_tpu.models.layer_kinds import ATTENTION, MAMBA  # noqa: E402
from easyparallellibrary_tpu.models.slot_core import (  # noqa: E402
    slot_cache_attend, slot_step_logits)
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import (  # noqa: E402
    ROADMAP_RECURRENT_STATE, check_draft_compatible)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter  # noqa: E402
from perfbench.reference import jamba as ref  # noqa: E402
from perfbench.runners import epl_jamba  # noqa: E402

scan_lib = importlib.import_module(
    "easyparallellibrary_tpu.kernels.ssm_scan")

REF_CFG = ref.JambaConfig(
    num_hidden_layers=4, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=1, vocab_size=256,
    attn_layer_period=4, attn_layer_offset=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, n_positions=64,
    initializer_range=0.2)
F32 = {"dtype": "float32", "param_dtype": "float32"}
LOGIT_TOL = 2e-4
S = 40


@pytest.fixture(scope="module")
def both():
  """(program model, its params, reference params) from one seed."""
  epl.init()
  key = ref.seed_key(2 ** 31 + 5)
  model, shell_of = epl_jamba.build_model(REF_CFG, F32)
  params = epl_jamba.program_params(
      REF_CFG, key, shell_of(jnp.zeros((1, 8), jnp.int32)))
  return model, params, jax.jit(lambda k: ref.init_params(REF_CFG, k))(key)


@pytest.fixture(scope="module")
def ids():
  return jax.random.randint(jax.random.PRNGKey(0), (3, S), 0, 256)


@pytest.fixture(scope="module")
def want(both, ids):
  return ref.logits(REF_CFG, both[2], ids)


def _backend_takes(monkeypatch, impl):
  monkeypatch.setattr(scan_lib, "_backend_impl", lambda: impl)


# ------------------------------------------------------ model vs reference --


def test_layer_kinds_follow_the_published_rule():
  from easyparallellibrary_tpu.models.jamba import JambaConfig
  kinds = JambaConfig().layer_kinds()
  assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [7, 21]
  assert kinds.count(MAMBA) == 26
  assert kinds == dataclasses.replace(
      REF_CFG, num_hidden_layers=28, attn_layer_period=14,
      attn_layer_offset=7).layer_kinds()


def test_weights_sit_where_the_reference_has_them(both):
  """The glue makes a layer at a time what ``init_params`` stacks: the
  same values (the sums of squares agree to rounding), ``A_log`` and the
  taps transposed."""
  _, params, rp = both
  a = float(epl_jamba.sum_of_squares(params))
  b = float(epl_jamba.sum_of_squares(rp))
  assert abs(a - b) <= 1e-5 * b
  from flax import linen as nn
  p = nn.meta.unbox(params)
  np.testing.assert_array_equal(
      np.asarray(p["block_2"]["mamba"]["A_log"]),
      np.asarray(rp["mamba"]["A_log"][1]).T)
  np.testing.assert_array_equal(
      np.asarray(p["block_1"]["attn"]["k"]["kernel"]),
      np.asarray(rp["attention"]["k"][0].astype(jnp.float32)))


def test_full_forward_matches_the_reference(both, ids, want):
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": params}, ids)
  assert float(jnp.abs(want).max()) > 1.0          # not a model of zeros
  assert float((jnp.argmax(want, -1) == ids).mean()) < 0.5   # nor a copier
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def _through_the_cache(model, params, ids, chunk, impl="reference",
                       ragged=None):
  """Chunked prefill, then decode, through ``slot_step_logits``: the
  logits at every position, and the final cache."""
  B, n_tok = ids.shape
  kv, cursors = kv_lib.allocate_kv_cache(model.cfg, B, chunk)
  fed = np.zeros((B,), np.int64)
  out = [[] for _ in range(B)]
  step = 0
  call = jax.jit(lambda kv, tokens, cursors, num_valid, reset:
                 slot_step_logits(model, params, kv, tokens, cursors,
                                  num_valid=num_valid, reset=reset,
                                  ssm_scan_impl=impl))
  with jax.default_matmul_precision("highest"):
    while (fed < n_tok).any():
      tokens = np.zeros((B, chunk), np.int32)
      num_valid = np.zeros((B,), np.int32)
      for b in range(B):
        n = min(chunk, n_tok - fed[b])
        if ragged is not None:
          n = min(n, ragged[(step + b) % len(ragged)])
        tokens[b, :n] = np.asarray(ids[b, fed[b]:fed[b] + n])
        num_valid[b] = n
      logits, kv = call(kv, jnp.asarray(tokens), cursors,
                        jnp.asarray(num_valid), jnp.asarray(fed == 0))
      for b in range(B):
        out[b].append(logits[b, :num_valid[b]])
      cursors = cursors + num_valid
      fed += num_valid
      step += 1
  return jnp.stack([jnp.concatenate(o) for o in out]), kv


@pytest.mark.parametrize("chunk", [1, 4, 8, 16])
def test_prefill_then_decode_through_the_cache(both, ids, want, chunk):
  """Every position's logits through the slot cache, whatever the chunk
  width, against the reference's full forward (no cache, no chunks)."""
  model, params, _ = both
  got, _ = _through_the_cache(model, params, ids, chunk)
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_ragged_chunks_and_idle_slots(both, ids, want):
  """Slots advance by 0, 1, 3 or a whole chunk in the same call: each
  recurrence takes exactly its own ``num_valid`` tokens."""
  model, params, _ = both
  got, _ = _through_the_cache(model, params, ids, 8, ragged=(8, 0, 1, 3))
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_an_idle_slot_keeps_its_state_bit_for_bit(both, ids, impl):
  model, params, _ = both
  _, kv = _through_the_cache(model, params, ids[:, :16], 8, impl=impl)
  cursors = jnp.full((3,), 16, jnp.int32)
  tokens = jnp.asarray(np.asarray(ids[:, 16:24]), jnp.int32)
  _, after = slot_step_logits(
      model, params, kv, tokens, cursors,
      num_valid=jnp.asarray([8, 0, 3], jnp.int32),
      reset=jnp.zeros((3,), bool), ssm_scan_impl=impl)
  for name, block in kv.items():
    if "mamba" not in block:
      continue
    for leaf in ("conv_state", "ssm_state"):
      before, now = block["mamba"][leaf], after[name]["mamba"][leaf]
      np.testing.assert_array_equal(np.asarray(before[1]),
                                    np.asarray(now[1]))
      assert not np.array_equal(np.asarray(before[0]), np.asarray(now[0]))


def test_a_reused_slot_starts_from_zero_state(both, ids, want):
  """The state analogue of the K/V no-leak test: a slot that held another
  request gives, after ``reset``, the logits of a fresh cache.  Without
  the reset it does not (stale state is masked by nothing)."""
  model, params, _ = both
  other = jnp.flip(ids, axis=1)
  _, kv = _through_the_cache(model, params, other, 8)

  def replay(reset):
    cursors = jnp.full((3,), S, jnp.int32)
    with jax.default_matmul_precision("highest"):
      logits, _ = slot_step_logits(
          model, params, kv, ids[:, :8], jnp.where(reset, 0, cursors),
          num_valid=jnp.full((3,), 8, jnp.int32), reset=reset,
          ssm_scan_impl="reference")
    return logits

  fresh = replay(jnp.ones((3,), bool))
  assert float(jnp.abs(fresh - want[:, :8]).max()) < LOGIT_TOL
  stale = replay(jnp.asarray([True, False, True]))
  assert float(jnp.abs(stale[1] - want[1, :8]).max()) > 1e-2
  assert float(jnp.abs(stale[0] - want[0, :8]).max()) < LOGIT_TOL


def test_advance_window_is_a_select_not_arithmetic():
  r = np.random.RandomState(0)
  full = jnp.asarray(r.randn(5, 3 + 8, 16), jnp.float32)
  nv = jnp.asarray([0, 1, 3, 8, 5], jnp.int32)
  got = np.asarray(advance_window(full, nv, 3))
  for b, n in enumerate(np.asarray(nv)):
    np.testing.assert_array_equal(got[b], np.asarray(full)[b, n:n + 3])
  np.testing.assert_array_equal(np.asarray(advance_window(full, None, 3)),
                                np.asarray(full)[:, 8:])


def test_one_kv_head_attends_as_repeated_heads():
  """``slot_cache_attend`` with 4 query heads on 1 K/V head, against dense
  causal attention with that head repeated; and the full forward's
  grouped attention against the same."""
  from easyparallellibrary_tpu.models.gpt import _dense_causal_attention
  r = np.random.RandomState(1)
  B, T, H, hd, C = 2, 24, 4, 16, 8
  q = jnp.asarray(r.randn(B, T, H, hd), jnp.float32)
  k, v = (jnp.asarray(r.randn(B, T, 1, hd), jnp.float32) for _ in range(2))
  dense = _dense_causal_attention(q, jnp.repeat(k, H, 2),
                                  jnp.repeat(v, H, 2), jnp.float32)
  grouped = gqa_causal_attention(q, k, v, jnp.float32)
  assert float(jnp.abs(grouped - dense).max()) < 1e-5
  ck = jnp.zeros((B, T + C, 1, hd), jnp.float32)
  cv = jnp.zeros_like(ck)
  outs = []
  for start in range(0, T, C):
    out, ck, cv = slot_cache_attend(
        q[:, start:start + C], k[:, start:start + C], v[:, start:start + C],
        ck, cv, jnp.full((B,), start, jnp.int32), jnp.float32,
        write_impl="reference")
    outs.append(out)
  assert float(jnp.abs(jnp.concatenate(outs, 1) - dense).max()) < 1e-5


# ------------------------------------------------------------------ kernel --


def _scan_inputs(B, C, N, Di, dtype, seed):
  r = np.random.RandomState(seed)
  f32 = jnp.float32
  state = jnp.asarray(r.randn(B, N, Di), f32)
  u, z = (jnp.asarray(r.randn(B, C, Di), dtype) for _ in range(2))
  delta = jax.nn.softplus(jnp.asarray(r.randn(B, C, Di) - 3.0, f32))
  Bm, Cm = (jnp.asarray(r.randn(B, C, N), f32) for _ in range(2))
  A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=f32)[:, None], (N, Di))
  num_valid = jnp.asarray(([0, C] + list(r.randint(0, C + 1, B)))[:B],
                          jnp.int32)
  reset = jnp.asarray(([False, True] + list(r.rand(B) < 0.4))[:B])
  return (state, u, delta, Bm, Cm, z, A, jnp.ones((Di,), f32), num_valid,
          reset)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("chunk", [1, 4, 8, 16])
def test_kernel_in_interpret_mode_equals_the_reference_lowering(chunk,
                                                                dtype):
  """Same contract, same order of operations: interpreted, the kernel's
  state and outputs are the reference lowering's bit for bit (on a chip
  the sum over the states runs in another order: float32 rounding, which
  chip_smoke.py checks)."""
  args = _scan_inputs(6, chunk, 16, 256, dtype, seed=chunk)
  want_out, want_state = scan_lib.ssm_scan_reference(*args)
  out, state = scan_lib.ssm_scan(*args, impl="interpret")
  np.testing.assert_array_equal(np.asarray(state), np.asarray(want_state))
  np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                np.asarray(want_out.astype(jnp.float32)))
  # slot 0 is idle and not reset: untouched; slot 1 is reset
  np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(args[0][0]))
  assert not np.asarray(out[0]).any()


def test_positions_beyond_num_valid_are_the_identity():
  args = list(_scan_inputs(4, 8, 16, 128, jnp.float32, seed=3))
  args[8] = jnp.asarray([3, 3, 3, 3], jnp.int32)
  args[9] = jnp.zeros((4,), bool)
  _, short = scan_lib.ssm_scan_reference(*args)
  cut = [a[:, :3] if i in (1, 2, 3, 4, 5) else a for i, a in enumerate(args)]
  _, want = scan_lib.ssm_scan_reference(*cut)
  np.testing.assert_array_equal(np.asarray(short), np.asarray(want))


@pytest.mark.parametrize("shape,dtype,chunk,sharded,fits", [
    ((128, 16, 5120), jnp.bfloat16, 8, False, True),     # the cell's state
    ((128, 16, 5120), jnp.float32, 16, False, True),
    ((128, 16, 5120), jnp.bfloat16, 8, True, False),     # a mesh of chips
    ((128, 16, 5120), jnp.bfloat16, 64, False, False),   # chunk not unrolled
    ((8, 16, 100), jnp.float32, 8, False, False),        # no whole lane tile
    ((8, 12, 128), jnp.float32, 8, False, False),        # no whole sublanes
    ((8, 16, 128), jnp.float16, 8, False, False),
])
def test_the_rule_declines_what_the_kernel_cannot_tile(
    monkeypatch, shape, dtype, chunk, sharded, fits):
  _backend_takes(monkeypatch, "pallas")
  impl = scan_lib.resolve_ssm_scan_impl(shape, dtype, chunk, sharded=sharded)
  assert impl == ("pallas" if fits else "reference")


def test_the_rule_follows_the_backend(monkeypatch):
  shape = (128, 16, 5120)
  assert scan_lib.resolve_ssm_scan_impl(shape, jnp.bfloat16, 8) == \
      "reference"                                       # this is a CPU
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert scan_lib.resolve_ssm_scan_impl(shape, jnp.bfloat16, 8) == "pallas"
  with pytest.raises(ValueError, match="impl must be one of"):
    scan_lib.ssm_scan(*_scan_inputs(2, 4, 16, 128, jnp.float32, 0),
                      impl="mosaic")


# ------------------------------------------------------------------ engine --


_REF_LOGITS = jax.jit(lambda rp, ids: ref.logits(REF_CFG, rp, ids))


def _greedy_by_the_reference(rp, prompt, n, pad_to=32):
  """Token by token through the reference's full forward (causal, so the
  zero padding behind the last token changes nothing)."""
  toks = list(int(t) for t in prompt)
  for _ in range(n):
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(toks)] = toks
    lg = _REF_LOGITS(rp, jnp.asarray(ids))
    toks.append(int(jnp.argmax(lg[0, len(toks) - 1])))
  return np.asarray(toks, np.int32)


@pytest.mark.quick
@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_engine_streams_equal_greedy_decoding_by_the_reference(
    monkeypatch, both, impl):
  """Seven requests of mixed lengths through three slots (so slots are
  reused and prefill chunks ride beside decodes): every finished stream
  is what the reference decodes greedily from the same prompt, with no
  cache at all.  One compile, under either scan lowering."""
  _backend_takes(monkeypatch, impl)
  model, params, rp = both
  r = np.random.default_rng(0)
  reqs = [(f"r{i}", r.integers(0, 256, int(r.integers(3, 20))).astype(
      np.int32), int(r.integers(2, 10))) for i in range(7)]
  with jax.default_matmul_precision("highest"):
    eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                   prefill_chunk=4)
    assert kv_lib.resolved(eng.lowerings) == {
        "kv_write_impl": "reference", "slot_attn_impl": "reference",
        "ssm_scan_impl": impl}
    for uid, prompt, n in reqs:
      assert eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n))
    out = eng.run()
  assert eng._step_fn._cache_size() == 1
  for uid, prompt, n in reqs:
    want = _greedy_by_the_reference(rp, prompt, n)
    np.testing.assert_array_equal(out[uid], want)
    assert len(set(want[len(prompt):].tolist())) > 1 or n < 3, uid


@pytest.mark.parametrize("make", ["gpt", "jamba"])
def test_cache_bytes_is_the_sum_of_the_leaves(both, make):
  if make == "gpt":
    cfg = GPTConfig(vocab_size=64, num_layers=3, num_heads=4, d_model=32,
                    d_ff=64, max_seq_len=48, dtype=jnp.bfloat16)
    kinds = 3 * [ATTENTION]
  else:
    cfg = dataclasses.replace(both[0].cfg, dtype=jnp.bfloat16)
    kinds = [MAMBA, ATTENTION, MAMBA, MAMBA]
  assert list(kv_lib.layer_kinds(cfg)) == kinds
  kv, cursors = kv_lib.allocate_kv_cache(cfg, 5, 4)
  leaves = jax.tree_util.tree_leaves(kv)
  assert kv_lib.cache_bytes(cfg, 5, 4) == sum(
      leaf.size * leaf.dtype.itemsize for leaf in leaves)
  layout = kv_lib.cache_layout(cfg, 5, 4)
  assert layout["kv_leaves"] == 2 * kinds.count(ATTENTION)
  assert layout["state_leaves"] == 2 * kinds.count(MAMBA)
  assert layout["kv_bytes"] + layout["state_bytes"] == \
      kv_lib.cache_bytes(cfg, 5, 4)
  assert cursors.shape == (5,) and not any(np.asarray(l).any()
                                           for l in leaves)
  if make == "jamba":
    state = kv["block_0"]["mamba"]
    assert state["ssm_state"].shape == (5, 16, 128)
    assert state["ssm_state"].dtype == jnp.float32
    assert state["conv_state"].shape == (5, 3, 128)
    assert kv["block_1"]["attn"]["cached_key"].shape == (5, 64 + 4, 1, 16)
    assert kv_lib.has_recurrent_state(cfg)
  else:
    assert not kv_lib.has_recurrent_state(cfg)
    assert kv_lib.ssm_scan_impl(cfg, 5, 4) is None


@pytest.mark.parametrize("kwargs", [
    {"paged": True, "block_size": 16},
    {"paged": True, "block_size": 16, "prefix_cache": True},
    {"drafter": "ngram"},
    {"resilience": True},
], ids=["paged", "prefix_cache", "speculative", "guarded_step"])
def test_what_rolls_a_cursor_back_refuses_recurrent_state(both, kwargs):
  """Each composition that takes a request back to an earlier position
  by moving a cursor refuses this model at construction, all with the one
  message; the same engine without it builds."""
  model, params, _ = both
  if kwargs.get("drafter") == "ngram":
    kwargs = {"drafter": NgramDrafter(k=2)}
  with pytest.raises(ValueError) as e:
    ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                             **kwargs)
  assert ROADMAP_RECURRENT_STATE in str(e.value)
  assert "recurrent-state layers (JambaConfig)" in str(e.value)


def test_a_recurrent_draft_model_is_refused_with_the_same_message(both):
  gpt_cfg = GPTConfig(vocab_size=256, num_layers=1, num_heads=2, d_model=16,
                      d_ff=32, max_seq_len=64)
  with pytest.raises(ValueError) as e:
    check_draft_compatible(gpt_cfg, both[0].cfg)
  assert ROADMAP_RECURRENT_STATE in str(e.value)


def test_the_engine_says_what_it_holds_and_counts_resets(both):
  """Trace metadata ``serving/cache_layout`` and ``serving/ssm_scan_impl``
  beside ``serving/kv_write_impl``, the same two as engine attributes,
  and ``serving/state_resets`` beside ``serving/active_slots``: one reset
  a request."""
  model, params, _ = both
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   prefill_chunk=4)
    for i in range(3):
      eng.submit(Request(uid=i, prompt=np.arange(5, dtype=np.int32) + i,
                         max_new_tokens=3))
    eng.run()
    events = tracer.events()
  finally:
    trace_lib.install(None)
  meta = {ev["name"]: ev["args"] for ev in events if ev["ph"] == "M"
          and ev["name"].startswith("serving/")}
  assert meta["serving/ssm_scan_impl"] == {"impl": "reference"}
  assert meta["serving/kv_write_impl"] == {"impl": "reference"}
  assert meta["serving/cache_layout"] == eng.cache_layout
  assert eng.cache_layout == kv_lib.cache_layout(model.cfg, 2, 4)
  counters = lambda name: [ev["args"]["value"] for ev in events
                           if ev["ph"] == "C" and ev["name"] == name]
  resets = counters("serving/state_resets")
  assert len(resets) == len(counters("serving/active_slots")) > 0
  assert sum(resets) == 3


def test_a_gpt_engine_is_what_it_was():
  """No recurrent state: no scan, no reset counter, a layout that holds
  K/V alone (every contiguous engine says what it holds and in which
  order), and the fused step is handed no state arguments (its greedy
  streams against ``generate()`` are tests/test_serving.py's)."""
  epl.init()
  cfg = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                  d_ff=64, max_seq_len=48, dtype=jnp.float32)
  model = GPT(cfg)
  params = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   prefill_chunk=4)
    eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3))
    eng.run()
    names = {ev["name"] for ev in tracer.events()}
  finally:
    trace_lib.install(None)
  assert eng.lowerings["ssm_scan_impl"] is None
  assert eng.cache_layout == {
      "kv_bytes": 4 * 2 * 52 * 32 * 4, "kv_leaves": 4, "state_bytes": 0,
      "state_leaves": 0, "kv_order": "positions"}
  assert {"serving/kv_write_impl", "serving/cache_layout"} <= names
  assert not names & {"serving/state_resets", "serving/ssm_scan_impl"}
  kv, _ = kv_lib.allocate_kv_cache(cfg, 2, 4)
  assert set(kv["block_0"]) == {"attn"}
  assert kv["block_0"]["attn"]["cached_key"].shape == (2, 52, 4, 8)


def test_engine_on_a_mesh_of_chips_takes_the_reference_scan(monkeypatch,
                                                           both):
  """Recurrent state is replicated on a mesh and the SPMD partitioner
  cannot split a Mosaic call: the rule resolves the reference there."""
  _backend_takes(monkeypatch, "pallas")
  model, params, _ = both
  cfg = model.cfg
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  assert kv_lib.ssm_scan_impl(cfg, 2, 4, mesh) == "reference"
  assert kv_lib.ssm_scan_impl(cfg, 2, 4, None) == "pallas"
  shardings, _ = kv_lib.kv_cache_shardings(cfg, mesh)
  assert jax.tree_util.tree_structure(shardings) == \
      jax.tree_util.tree_structure(kv_lib.cache_leaves(cfg, 2, 4))
  assert shardings["block_0"]["mamba"]["ssm_state"].spec == \
      jax.sharding.PartitionSpec()
