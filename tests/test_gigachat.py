"""The GigaChat 3.5 decoder (models/gigachat.py) against the benchmark's
plain reference (perfbench/reference/gigachat3_5.py), the gated delta rule
in its two forms and two lowerings (kernels/gdn_scan.py), and a matrix of
recurrent state beside a latent leaf in the continuous-batching engine.

Toy widths with every kind of layer: hidden 64, 4 layers (a dense linear
layer, a full expert layer, two linear expert layers), 4 key heads on 8
value heads of 16 | 16, a convolution of 4 taps, a latent attention of 4
heads of 16 | 8 | 16 on ranks 32 | 32 with YaRN's table (factor 8 over an
"original" 16 positions, so that three of four pairs are slowed), 8 routed
experts top-2 of which this chip holds 3 (from 2).  float32 on both sides,
matmuls at ``highest``.  Tolerances, each with its reason:

* ``LOGIT_TOL`` 5e-4 on logits of size ~7 (weights N(0, 0.2): at width 64
  the published 0.02 gives a model that copies its input, which would test
  nothing): program and reference differ by float32 rounding in another
  order of the same sums (the chunk's form re-associates the recurrence;
  the absorbed latent attention re-associates two products); seen 3e-5,
  while a dropped term, a stale state or a wrong gate is >= 1e-2;
* ``FORM_TOL`` 2e-5 between the chunk's form and the one-position form on
  states of size ~4: the same sums in another order, seen 1e-6;
* what moves no arithmetic is held bit for bit: an idle slot's state and
  window under either lowering, the weights' placement.
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
from easyparallellibrary_tpu.models.blocks import YarnDims, rotary  # noqa: E402
from easyparallellibrary_tpu.models.gigachat import GigaChatConfig  # noqa: E402
from easyparallellibrary_tpu.models.layer_kinds import (  # noqa: E402
    GATED_DELTA, LATENT)
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import (  # noqa: E402
    ROADMAP_RECURRENT_STATE, check_draft_compatible)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter  # noqa: E402
from perfbench.reference import gigachat3_5 as ref  # noqa: E402
from perfbench.runners import epl_gigachat3_5 as glue  # noqa: E402

gdn = importlib.import_module("easyparallellibrary_tpu.kernels.gdn_scan")

REF_CFG = ref.GigaChat35Config(
    num_hidden_layers=4, full_attention_layers=(1,), hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, heads=4, q_rank=32,
    kv_rank=32, nope=16, rope=8, value=16, theta=1e5, yarn_factor=8.0,
    yarn_original=16, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=1.0, yarn_mscale_all_dim=1.0, mla_scaling_factor=True,
    linear_key_heads=4, linear_value_heads=8, linear_key_dim=16,
    linear_value_dim=16, conv_kernel=4, gate_scale=2.0, o_norm_eps=1e-6,
    norm_gating_weight=2.0, swiglu_limit=10.0, router_width=8,
    experts_first=2, n_routed_experts=3, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, vocab_size=256,
    n_positions=128, initializer_range=0.2)
F32 = {"dtype": "float32", "param_dtype": "float32"}
LOGIT_TOL = 5e-4
FORM_TOL = 2e-5
S = 40


@pytest.fixture(scope="module")
def both():
  """(program model, its params, reference params) from one seed."""
  epl.init()
  key = ref.seed_key(2 ** 31 + 5)
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


def _backend_takes(monkeypatch, impl):
  monkeypatch.setattr(gdn, "_backend_impl", lambda: impl)


# ------------------------------------------------------ model vs reference --


def test_layer_kinds_follow_the_published_list():
  kinds = GigaChatConfig().layer_kinds()
  assert [i for i, k in enumerate(kinds) if k == LATENT] == list(
      range(3, 40, 4))
  assert kinds.count(GATED_DELTA) == 30
  assert glue.model_config(REF_CFG, F32).layer_kinds() == (
      GATED_DELTA, LATENT, GATED_DELTA, GATED_DELTA)


def test_weights_sit_where_the_reference_has_them(both):
  """The glue makes a layer at a time what ``init_params`` lists: the
  same values (the sums of squares agree to rounding; the reference draws
  its held experts again for its part), the held experts' gate and up
  joined."""
  _, params, rp = both
  a = float(glue.sum_of_squares(params))
  b = float(glue.sum_of_squares(rp))
  assert abs(a - b) <= 1e-5 * b
  from flax import linen as nn
  p = nn.meta.unbox(params)
  np.testing.assert_array_equal(
      np.asarray(p["block_2"]["linear"]["A_log"]),
      np.asarray(rp["layers"][2]["mix"]["A_log"]))
  np.testing.assert_array_equal(
      np.asarray(p["block_0"]["linear"]["conv_w"]),
      np.asarray(rp["layers"][0]["mix"]["conv"].astype(jnp.float32)))
  expert = rp["layers"][1]["ff"]["experts"].expert(REF_CFG.experts_first + 1)
  np.testing.assert_array_equal(
      np.asarray(p["block_1"]["moe"]["experts_gate_up"][1, :, 32:]),
      np.asarray(expert["up"].astype(jnp.float32)))
  assert p["block_1"]["latent"]["gate"]["kernel"].shape == (64, 4 * 16)


def test_the_reference_counts_its_own_parameters(both):
  _, params, _ = both
  n = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
  assert n == REF_CFG.param_count()


def test_full_forward_matches_the_reference(both, ids, want):
  """Training mode: whole sequences from zero state, the chunked delta
  rule over the sequence, against the reference's recurrence position by
  position."""
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": params}, ids)
  assert float(jnp.abs(want).max()) > 1.0          # not a model of zeros
  assert float((jnp.argmax(want, -1) == ids).mean()) < 0.5   # nor a copier
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_yarn_slows_the_slow_pairs_and_keeps_the_fast():
  """The frequency table against the reference's, and against plain
  rotary where YaRN changes nothing (factor 1)."""
  yarn = glue.model_config(REF_CFG, F32).latent_dims().yarn
  np.testing.assert_allclose(yarn.frequencies(8, 1e5),
                             ref.yarn_frequencies(REF_CFG, 8), rtol=1e-6)
  plain = 1e5 ** (-2.0 * np.arange(4) / 8)
  np.testing.assert_allclose(yarn.frequencies(8, 1e5),
                             plain / [1, 8, 8, 8], rtol=1e-6)
  assert yarn.softmax_factor == pytest.approx((0.1 * np.log(8) + 1) ** 2)
  assert yarn.amplitude == 1.0
  x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 3, 8))
  pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
  np.testing.assert_allclose(
      rotary(x, pos, 1e5, YarnDims(1.0, 16)), rotary(x, pos, 1e5), atol=1e-6)


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
                                  gdn_scan_impl=impl,
                                  moe_gmm_impl="reference"))
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


@pytest.mark.parametrize("chunk", [1, 4, 8])
def test_prefill_then_decode_through_the_cache(both, ids, want, chunk):
  """Every position's logits through the slot cache, whatever the chunk
  width, against the reference's full forward (no cache, no chunks)."""
  model, params, _ = both
  got, _ = _through_the_cache(model, params, ids, chunk)
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_ragged_chunks_and_idle_slots(both, ids, want):
  """Slots advance by 0, 1, 3 or a whole chunk in the same call: each
  state takes exactly its own ``num_valid`` positions."""
  model, params, _ = both
  got, _ = _through_the_cache(model, params, ids, 8, ragged=(8, 0, 1, 3))
  assert float(jnp.abs(got - want).max()) < LOGIT_TOL


def test_an_idle_slot_keeps_its_state_bit_for_bit(both, ids):
  model, params, _ = both
  _, kv = _through_the_cache(model, params, ids[:, :16], 8)
  cursors = jnp.full((3,), 16, jnp.int32)
  tokens = jnp.asarray(np.asarray(ids[:, 16:24]), jnp.int32)
  _, after = slot_step_logits(
      model, params, kv, tokens, cursors,
      num_valid=jnp.asarray([8, 0, 3], jnp.int32),
      reset=jnp.zeros((3,), bool), gdn_scan_impl="reference",
      moe_gmm_impl="reference")
  linear = [name for name, block in kv.items() if "linear" in block]
  assert len(linear) == 3
  for name in linear:
    for leaf in ("conv_state", "delta_state"):
      before, now = kv[name]["linear"][leaf], after[name]["linear"][leaf]
      np.testing.assert_array_equal(np.asarray(before[1]),
                                    np.asarray(now[1]))
      assert not np.array_equal(np.asarray(before[0]), np.asarray(now[0]))


def test_a_reused_slot_starts_from_zero_state(both, ids, want):
  """A slot that held another request gives, after ``reset``, the logits
  of a fresh cache.  Without the reset it does not (stale state is masked
  by nothing)."""
  model, params, _ = both
  other = jnp.flip(ids, axis=1)
  _, kv = _through_the_cache(model, params, other, 8)

  def replay(reset):
    cursors = jnp.full((3,), S, jnp.int32)
    with jax.default_matmul_precision("highest"):
      logits, _ = slot_step_logits(
          model, params, kv, ids[:, :8], jnp.where(reset, 0, cursors),
          num_valid=jnp.full((3,), 8, jnp.int32), reset=reset,
          gdn_scan_impl="reference", moe_gmm_impl="reference")
    return logits

  fresh = replay(jnp.ones((3,), bool))
  assert float(jnp.abs(fresh - want[:, :8]).max()) < LOGIT_TOL
  stale = replay(jnp.asarray([True, False, True]))
  assert float(jnp.abs(stale[1] - want[1, :8]).max()) > 1e-2
  assert float(jnp.abs(stale[0] - want[0, :8]).max()) < LOGIT_TOL


# ------------------------------------------------- the delta rule's forms --


def _scan_operands(B=5, C=16, Hk=2, Hv=4, d=128, dtype=jnp.float32, seed=0):
  """``(state, window, x, taps, g, beta)`` of ``gdn_scan`` at heads of a
  lane tile (what the kernel takes), a convolution of 4 taps."""
  ks = jax.random.split(jax.random.PRNGKey(seed), 6)
  W = 2 * Hk * d + Hv * d
  state = jax.random.normal(ks[0], (B, Hv, d, d), jnp.float32)
  x = jax.random.normal(ks[1], (B, C, W), jnp.float32).astype(dtype)
  g = -0.5 * jax.nn.softplus(jax.random.normal(ks[2], (B, C, Hv)))
  beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, C, Hv)))
  window = jax.random.normal(ks[4], (B, 3, W), jnp.float32).astype(dtype)
  taps = 0.5 * jax.random.normal(ks[5], (4, W), jnp.float32)
  return state, window, x, taps, g, beta


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_the_chunks_form_is_the_one_position_form(impl, chunk):
  """A chunk fed whole (the kernel: the convolution over the chunk and the
  WY form; the reference: its scan) against the same positions fed one a
  call (the one-position form under either lowering, the window carried
  from call to call): outputs, final state and final window."""
  state, window, x, taps, g, beta = _scan_operands(B=3, C=chunk)
  whole_o, whole_s, whole_w = gdn.gdn_scan(state, window, x, taps, g, beta,
                                           impl=impl)
  s, w, outs = state, window, []
  for t in range(chunk):
    o, s, w = gdn.gdn_scan(s, w, x[:, t:t + 1], taps, g[:, t:t + 1],
                           beta[:, t:t + 1], impl=impl)
    outs.append(o)
  assert float(jnp.abs(jnp.concatenate(outs, 1) - whole_o).max()) < FORM_TOL
  assert float(jnp.abs(s - whole_s).max()) < FORM_TOL
  np.testing.assert_array_equal(np.asarray(w), np.asarray(whole_w))
  np.testing.assert_array_equal(np.asarray(w), np.asarray(x[:, -3:]))
  assert float(jnp.abs(whole_s - state).max()) > 0.1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_is_the_reference_scan(dtype):
  """Interpreted, against the ``lax.scan``: an idle slot, a decoding one, a
  whole chunk from ``reset``, a partly valid chunk, a decoding slot from
  ``reset``, in one call."""
  state, window, x, taps, g, beta = _scan_operands(dtype=dtype)
  nv = jnp.asarray([0, 1, 16, 5, 1], jnp.int32)
  reset = jnp.asarray([False, False, True, False, True])
  o_ref, s_ref, w_ref = gdn.gdn_scan(state, window, x, taps, g, beta, nv,
                                     reset, impl="reference")
  o_k, s_k, w_k = gdn.gdn_scan(state, window, x, taps, g, beta, nv, reset,
                               impl="interpret")
  tol = FORM_TOL if dtype == jnp.float32 else 1e-2    # a bfloat16 output
  assert float(jnp.abs(o_ref.astype(jnp.float32)
                       - o_k.astype(jnp.float32)).max()) < tol
  assert float(jnp.abs(s_ref - s_k).max()) < FORM_TOL
  np.testing.assert_array_equal(np.asarray(w_ref, np.float32),
                                np.asarray(w_k, np.float32))
  # positions beyond a slot's live ones give zeros
  live = np.arange(16)[None] < np.asarray(nv)[:, None]
  for o in (o_ref, o_k):
    assert not np.asarray(o, np.float32)[~live].any()
    assert np.asarray(o, np.float32)[live].any()


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_num_valid_zero_keeps_the_state_and_reset_starts_from_zero(impl):
  state, window, x, taps, g, beta = _scan_operands(B=4, C=8)
  # negative zeros: what a multiply by one and an add of zero would flip
  state = state.at[0, 0, 0].set(-0.0)
  nv = jnp.asarray([0, 8, 8, 0], jnp.int32)
  reset = jnp.asarray([False, False, True, True])
  _, new, new_w = gdn.gdn_scan(state, window, x, taps, g, beta, nv, reset,
                               impl=impl)
  np.testing.assert_array_equal(np.asarray(new[0]).view(np.uint32),
                                np.asarray(state[0]).view(np.uint32))
  np.testing.assert_array_equal(np.asarray(new_w[0]), np.asarray(window[0]))
  assert not np.asarray(new[3]).any()        # reset, and nothing fed
  assert not np.asarray(new_w[3]).any()
  _, fresh, _ = gdn.gdn_scan(jnp.zeros_like(state), jnp.zeros_like(window),
                             x, taps, g, beta, nv, impl=impl)
  np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(fresh[2]))
  assert float(jnp.abs(new[1] - fresh[1]).max()) > 0.1


@pytest.mark.parametrize("nv", [0, 1, 2, 3, 5, 8])
def test_the_window_advances_by_the_live_inputs(nv):
  """``advance_window`` against the rows of the window followed by the
  chunk, by hand: the last three of the first ``3 + nv``."""
  _, window, x, _, _, _ = _scan_operands(B=2, C=8)
  got = gdn.advance_window(window, x, jnp.asarray([nv, 8], jnp.int32),
                           jnp.zeros((2,), bool))
  full = np.concatenate([np.asarray(window), np.asarray(x)], 1)
  np.testing.assert_array_equal(np.asarray(got[0]), full[0, nv:nv + 3])
  np.testing.assert_array_equal(np.asarray(got[1]), full[1, 8:11])


def test_a_whole_sequence_in_chunks_is_the_scan():
  """``gdn_sequence`` (training mode: the convolution over the sequence,
  then chunks of 16 over 40 positions, the last padded) against the
  reference scan fed 8 positions a call behind a zero window."""
  state, window, x, taps, g, beta = _scan_operands(B=2, C=40, seed=1)
  zero = jnp.zeros_like(window)
  seq = gdn.gdn_sequence(
      gdn.convolved(jnp.concatenate([zero, x], 1), taps, 40), g, beta,
      state.shape, chunk=16)
  s, w, outs = jnp.zeros_like(state), zero, []
  for c in range(0, 40, 8):
    o, s, w = gdn.gdn_scan(s, w, x[:, c:c + 8], taps, g[:, c:c + 8],
                           beta[:, c:c + 8], impl="reference")
    outs.append(o)
  assert float(jnp.abs(seq - jnp.concatenate(outs, 1)).max()) < FORM_TOL


@pytest.mark.parametrize("chunk,sharded,want", [
    (32, False, "pallas"), (1, False, "pallas"), (8, False, "reference"),
    (64, False, "reference"), (32, True, "reference")],
                         ids=["cell", "one_position", "half_a_bf16_tile",
                              "too_long_to_unroll", "on_a_mesh"])
def test_the_rule_reads_shapes_and_the_backend(monkeypatch, chunk, sharded,
                                               want):
  _backend_takes(monkeypatch, "pallas")
  shape, width = (128, 64, 128, 128), 2 * 32 * 128 + 64 * 128
  assert gdn.resolve_gdn_scan_impl(shape, width, jnp.bfloat16, chunk,
                                   sharded=sharded) == want
  # heads narrower than a lane tile: the toy model's
  assert gdn.resolve_gdn_scan_impl((3, 8, 16, 16), 256, jnp.float32,
                                   8) == "reference"
  _backend_takes(monkeypatch, "reference")
  assert gdn.resolve_gdn_scan_impl(shape, width, jnp.bfloat16,
                                   32) == "reference"
  assert gdn.key_heads(shape, width) == 32


# ------------------------------------------------------------ the shares --


def test_the_shares_add_up_to_the_uncut_expert_layer(both):
  """Four shares of two experts each (the cell's chip is one of sixteen):
  their routed parts plus the shared expert counted once are the layer
  that holds all eight, in the reference; and the program's layer at this
  chip's share is the reference's at the same share."""
  _, _, rp = both
  ff = rp["layers"][1]["ff"]
  h = jax.random.normal(jax.random.PRNGKey(7), (24, 64), jnp.float32)
  whole = ref.moe(REF_CFG, h, ff, held=(0, 8))
  parts = sum(ref.moe(REF_CFG, h, ff, held=(2 * j, 2), shared=False)
              for j in range(4))
  shared = ref.mlp(REF_CFG, h, ff["shared"], "float32")
  assert float(jnp.abs(parts + shared - whole).max()) < 1e-5
  assert float(jnp.abs(parts).max()) > 0.1
  # The chip's own share is not the whole layer.
  mine = ref.moe(REF_CFG, h, ff)
  assert float(jnp.abs(mine - whole).max()) > 1e-2


@pytest.mark.parametrize("which", ["dense", "experts"])
def test_every_gated_mlp_clamps_both_pre_activations(both, which):
  """``swiglu_limit``: inputs large enough that gates pass 10 and up
  projections leave +-10 (at the model's own activations neither happens
  once in a test, and a missing clamp would go unseen): the program's
  dense MLP, and its expert layer with the shared expert, against the
  reference's; and the same layers without the limit differ."""
  from easyparallellibrary_tpu.models.blocks import GatedMLP
  from easyparallellibrary_tpu.models.moe import DroplessMoE
  from flax import linen as nn
  model, params, rp = both
  cfg = model.cfg
  p = nn.meta.unbox(params)
  h = 30.0 * jax.random.normal(jax.random.PRNGKey(11), (1, 24, 64))
  loose = dataclasses.replace(cfg, swiglu_limit=1e9)
  with jax.default_matmul_precision("highest"):
    if which == "dense":
      ff = rp["layers"][0]["ff"]
      want = ref.mlp(REF_CFG, h[0], ff, "float32")
      run = lambda c: GatedMLP(c, limit=c.swiglu_limit).apply(
          {"params": p["block_0"]["mlp"]}, h)[0]
      pre = jnp.matmul(h[0], ff["gate"].astype(jnp.float32))
    else:
      ff = rp["layers"][1]["ff"]
      want = ref.moe(REF_CFG, h[0], ff)
      run = lambda c: DroplessMoE(c, moe_gmm_impl="reference").apply(
          {"params": p["block_1"]["moe"]}, h, mutable=["stats"])[0][0]
      pre = jnp.matmul(h[0], ff["shared"]["gate"].astype(jnp.float32))
    assert float(pre.max()) > 20.0                  # the clamp has work
    got, unclamped = run(cfg), run(loose)
  scale = float(jnp.abs(want).max())
  assert float(jnp.abs(got - want).max()) < 1e-4 * scale
  assert float(jnp.abs(unclamped - want).max()) > 1e-2 * scale


# --------------------------------------------------------------- serving --

_REF_LOGITS = jax.jit(lambda rp, ids: ref.logits(REF_CFG, rp, ids))


def _reference_logits(rp, stream, pad_to=64):
  ids = np.zeros((1, pad_to), np.int32)
  ids[0, :len(stream)] = stream
  return _REF_LOGITS(rp, jnp.asarray(ids))[0, :len(stream)]


@pytest.mark.quick
@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_engine_streams_follow_the_references_logits(monkeypatch, both,
                                                     impl):
  """Seven requests of mixed lengths through three slots (so slots are
  re-used by a second and a third request and prefill chunks ride beside
  decodes): every served token is the reference's greedy choice given the
  stream before it, with no cache at all (its full forward over the
  finished stream), unless the reference's two best lie within
  ``LOGIT_TOL`` there.  One compile.  ``interpret`` names the rule's
  backend lowering; at these widths the rule declines the kernel (heads of
  16 are no lane tile), which the record says."""
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
        "gdn_scan_impl": "reference", "moe_gmm_impl": "reference"}
    for uid, prompt, n in reqs:
      assert eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n))
    out = eng.run()
  assert eng._step_fn._cache_size() == 1
  for uid, prompt, n in reqs:
    stream = np.asarray(out[uid])
    assert len(stream) == len(prompt) + n
    lg = _reference_logits(rp, stream)
    served = stream[len(prompt):]
    at = lg[len(prompt) - 1:len(stream) - 1]
    gap = jnp.max(at, -1) - jnp.take_along_axis(
        at, jnp.asarray(served)[:, None], -1)[:, 0]
    assert float(gap.max()) < LOGIT_TOL, uid


def test_cache_bytes_counts_recurrent_and_latent_leaves(both):
  cfg = dataclasses.replace(both[0].cfg, dtype=jnp.bfloat16)
  kv, cursors = kv_lib.allocate_kv_cache(cfg, 5, 4)
  leaves = jax.tree_util.tree_leaves(kv)
  assert kv_lib.cache_bytes(cfg, 5, 4) == sum(
      leaf.size * leaf.dtype.itemsize for leaf in leaves)
  layout = kv_lib.cache_layout(cfg, 5, 4)
  assert layout["state_leaves"] == 6 and layout["latent_leaves"] == 1
  assert layout["kv_leaves"] == 0 and layout["kv_order"] == "positions"
  assert layout["state_bytes"] == 3 * 5 * (8 * 16 * 16 * 4 + 3 * 256 * 2)
  assert layout["latent_bytes"] == 5 * (128 + 4) * (32 + 8) * 2
  state = kv["block_0"]["linear"]
  assert state["delta_state"].shape == (5, 8, 16, 16)
  assert state["delta_state"].dtype == jnp.float32
  assert state["conv_state"].shape == (5, 3, 256)
  assert kv["block_1"]["latent"]["cached_latent"].shape == (5, 132, 1, 40)
  assert kv_lib.recurrent_kinds(cfg) == (GATED_DELTA,)
  assert kv_lib.latent_kinds(cfg) == (LATENT,)
  assert kv_lib.ssm_scan_impl(cfg, 5, 4) is None
  assert cursors.shape == (5,)


@pytest.mark.parametrize("kwargs", [
    {"paged": True, "block_size": 16},
    {"paged": True, "block_size": 16, "prefix_cache": True},
    {"drafter": "ngram"},
    {"resilience": True},
], ids=["paged", "prefix_cache", "speculative", "guarded_step"])
def test_what_rolls_a_cursor_back_refuses_the_matrix_state(both, kwargs):
  """Each composition that takes a request back to an earlier position
  refuses this model at construction, with the recurrent kinds' one
  message, which names the new kind."""
  model, params, _ = both
  if kwargs.get("drafter") == "ngram":
    kwargs = {"drafter": NgramDrafter(k=2)}
  with pytest.raises(ValueError) as e:
    ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                             **kwargs)
  assert ROADMAP_RECURRENT_STATE in str(e.value)
  assert ("recurrent-state layers (GigaChatConfig) of kind gated_delta"
          in str(e.value))


def test_a_draft_model_with_a_matrix_state_is_refused(both):
  from easyparallellibrary_tpu.models import GPTConfig
  gpt_cfg = GPTConfig(vocab_size=256, num_layers=1, num_heads=2, d_model=16,
                      d_ff=32, max_seq_len=128)
  with pytest.raises(ValueError) as e:
    check_draft_compatible(gpt_cfg, both[0].cfg)
  assert ROADMAP_RECURRENT_STATE in str(e.value)


def test_the_engine_says_what_it_holds_and_counts_the_forms(both):
  """Trace metadata ``serving/gdn_scan_impl`` and ``serving/cache_layout``;
  ``serving/state_resets``, ``serving/state_slots`` and
  ``serving/state_chunk_positions`` a step: one reset a request, a slot
  that feeds one position runs the one-position form, the prompts'
  chunks the chunk's."""
  model, params, _ = both
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   prefill_chunk=4)
    for i in range(3):
      eng.submit(Request(uid=i, prompt=np.arange(6, dtype=np.int32) + i,
                         max_new_tokens=3))
    eng.run()
    events = tracer.events()
  finally:
    trace_lib.install(None)
  meta = {ev["name"]: ev["args"] for ev in events if ev["ph"] == "M"
          and ev["name"].startswith("serving/")}
  assert meta["serving/gdn_scan_impl"] == {"impl": "reference"}
  assert "serving/ssm_scan_impl" not in meta
  assert meta["serving/cache_layout"] == eng.cache_layout
  assert eng.cache_layout == kv_lib.cache_layout(model.cfg, 2, 4)
  counters = lambda name: [ev["args"]["value"] for ev in events
                           if ev["ph"] == "C" and ev["name"] == name]
  steps = len(counters("serving/active_slots"))
  for name in ("serving/state_resets", "serving/state_slots",
               "serving/state_chunk_positions"):
    assert len(counters(name)) == steps > 0, name
  assert sum(counters("serving/state_resets")) == 3
  # A prompt of 6 at chunk 4 is a chunk of 4 and one of 2; every later
  # step of a request feeds one position.
  assert sum(counters("serving/state_chunk_positions")) == 3 * 6
  assert sum(counters("serving/state_slots")) == 3 * (2 + 2)


# ----------------------------------- the latent layer on the tile grid (PR 51) --

# Eight heads: whole sublane tiles of float32, which the tile kernel wants.
WIDE_CFG = dataclasses.replace(REF_CFG, heads=8)


@pytest.fixture(scope="module")
def wide():
  epl.init()
  key = ref.seed_key(2 ** 31 + 7)
  model, shell_of = glue.build_model(WIDE_CFG, F32)
  return model, glue.program_params(
      WIDE_CFG, key, shell_of(jnp.zeros((1, 8), jnp.int32)))


def _attend_takes(monkeypatch, impl):
  """The attend's and the window write's backend lowering (the delta rule's
  kernel declines these heads either way)."""
  for name in ("slot_attention", "kv_write"):
    monkeypatch.setattr(importlib.import_module(
        f"easyparallellibrary_tpu.kernels.{name}"), "_backend_impl",
        lambda: impl)


def _mixed_requests():
  rng = np.random.default_rng(5)
  return [Request(uid=f"r{j}", prompt=rng.integers(0, 256, n).astype(np.int32),
                  max_new_tokens=m)
          for j, (n, m) in enumerate([(3, 6), (19, 9), (30, 5), (11, 12),
                                      (26, 7)])]


def test_narrow_and_wide_steps_commit_the_same_on_the_tile_grid(monkeypatch,
                                                                wide):
  """One engine whose flat batch is narrower than its ``slots x chunk``
  positions and has a second width (40 and 16 rows of 4 x 16, named here:
  the rule gives so few positions their full width).  Under the interpreted
  kernels the plain latent leaf takes the tile grid (``tile_attn_out``
  ``flat``: queries read from the flat batch, the result written to its
  rows, on steps that take either side of the layers' conditionals) and no
  ``serving/attn_rows_read`` is counted, the first grid's walk being gone;
  ``serving/attn_tile_positions`` is the plan's live tiles of 8 and its
  decoding slots; the engine commits what it commits under the reference
  lowerings.  One compile each."""
  from easyparallellibrary_tpu.profiler.serving import ServingStats
  from easyparallellibrary_tpu.serving import engine as engine_lib
  model, params = wide
  monkeypatch.setattr(engine_lib, "flat_width", lambda slots, chunk: 40)
  monkeypatch.setattr(engine_lib, "narrow_width", lambda width, slots: 16)
  outs, counted = {}, {}
  for impl, form in (("reference", None), ("interpret", "flat")):
    _attend_takes(monkeypatch, impl)
    stats = ServingStats()
    tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
    try:
      eng = ContinuousBatchingEngine(model, params, num_slots=4,
                                     prefill_chunk=16, stats=stats)
      plans = []
      plan_step = eng.scheduler.plan_step
      def planned(*a, plan_step=plan_step, plans=plans, **kw):
        plan = plan_step(*a, **kw)
        if plan is not None:
          plans.append(np.array(plan.num_valid))
        return plan
      eng.scheduler.plan_step = planned
      for r in _mixed_requests():
        assert eng.submit(r)
      with jax.default_matmul_precision("highest"):
        outs[impl] = eng.run()
      events = tracer.events()
    finally:
      trace_lib.install(None)
    assert (eng.flat_width, eng.flat_narrow) == (40, 16)
    assert eng.lowerings["slot_attn_impl"] == impl
    assert eng.lowerings["tile_attn_out"] == form
    assert 0 < stats.flat_narrow_steps < stats.steps
    assert eng._step_fn._cache_size() == 1
    counted[impl] = {
        name: [ev["args"]["value"] for ev in events
               if ev["ph"] == "C" and ev["name"] == f"serving/{name}"]
        for name in ("attn_tile_positions", "attn_rows_read",
                     "flat_positions")}
    counted[impl]["plans"] = plans
  for uid, toks in outs["reference"].items():
    np.testing.assert_array_equal(np.asarray(outs["interpret"][uid]),
                                  np.asarray(toks))
  assert not counted["reference"]["attn_tile_positions"]
  got = counted["interpret"]
  assert not got["attn_rows_read"]
  assert len(got["attn_tile_positions"]) == len(got["flat_positions"]) > 0
  # Held to the plans: a slot that feeds one position counts 1, one that
  # feeds more its live tiles of 8 positions.
  want = sorted(int(np.sum(np.where(nv == 1, 1, -(-nv // 8) * 8)))
                for nv in got["plans"] if nv.sum())
  assert sorted(got["attn_tile_positions"]) == want
  assert all(t >= f for t, f in zip(got["attn_tile_positions"],
                                    got["flat_positions"]))
  assert sum(got["attn_tile_positions"]) > sum(got["flat_positions"])


def test_the_step_holds_two_launches_on_the_flat_batch(monkeypatch, wide):
  """The fused step's program under the interpreted kernels at a narrower
  width: two ``slot_attn`` launches (many, one) whose queries are the flat
  batch ``[T, H, r + dr]`` and whose output its rows ``[T, H, r]``; at
  full width (and for GLM-4.7-Flash's 20 heads at any) the one launch of
  the first grid on ``[slots, Hkv, rows, ..]`` operands."""
  from easyparallellibrary_tpu.models.slot_core import slot_step_logits
  model, params = wide
  sa = importlib.import_module("easyparallellibrary_tpu.kernels.slot_attention")
  B, C, T = 4, 16, 40
  kv, cursors = kv_lib.allocate_kv_cache(model.cfg, B, C)
  tokens = jnp.zeros((B, C), jnp.int32)
  nv = jnp.asarray([16, 1, 0, 3], jnp.int32)

  def launches(width):
    closed = jax.make_jaxpr(lambda p, kv: slot_step_logits(
        model, p, kv, tokens, cursors, num_valid=nv,
        reset=jnp.zeros((B,), jnp.bool_), width=width,
        kv_write_impl="interpret", slot_attn_impl="interpret",
        gdn_scan_impl="reference", moe_gmm_impl="reference"))(params, kv)
    found = []
    def walk(jaxpr):
      for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call" and e.params["name"] == (
            sa.SLOT_ATTN):
          found.append([tuple(v.aval.shape) for v in e.invars])
        for sub in jax.core.jaxprs_in_params(e.params):
          walk(sub)
    walk(closed.jaxpr)
    return found

  H, W, r = 8, 40, 32
  flat = launches(T)
  assert len(flat) == 2
  for shapes in flat:
    assert (T, H, W) in shapes and (T, H, r) in shapes
    assert not any(len(s) > 2 and (s[:2] == (B, C) or s[0] == B * C)
                   for s in shapes)
  (first,) = launches(None)
  assert (B, 1, sa._query_rows(C, H, jnp.float32), W) in first
