"""The decoder whose window layers keep their K/V PAIR as a ring beside full
layers without positions, and whose experts are routed from the layer's
input (models/smallthinker.py, models/moe.py's routing rule, router input
and gate) against the benchmark's plain reference
(perfbench/reference/smallthinker.py); the seventh cache kind, the ring
write of a pair in rows and ``slot_attn_kvwin`` in the engine.

A toy of the published SHAPE: hidden 64 as 14 query heads on 2 K/V heads of
8 (a head size that is NOT ``d_model / heads``: 64 / 14 does not divide;
groups of 7), a window of 8, two periods of [full without positions,
window with rotary x 3], 8 ReLU experts of 32 top-3 routed by softmax from
the layer's input, vocabulary 97; ``ring_tile`` 4, so that at chunk 4 a
ring is 12 rows and a context of 48 positions (6 windows) goes round it
four times.  float32 on both sides, matmuls at ``highest``.  The kernel
cases use lane-tile widths (heads of 128, rings of 128 and 256 rows).

Tolerances: logits are O(1-10) (weights N(0, 0.2), as tests/test_glm_moe.py
argues), program and reference differ by float32 rounding in another order
of the same sums (3.6e-4 seen on logits of up to 5.8), so ``2e-3`` absolute
on logits is ~5 x what is seen and far below what a wrong term gives:
attending past the window, rotating the full layers, or routing from the
post-attention stream each move logits by 1 and more (asserted below).
The seed is one with no near-tie, within float32 rounding, at the 3rd
expert: a flip there is a different and equally valid choice that moves
logits far more than rounding does; the cell's check on the chip lives
with it (PERF.md).
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import moe as moe_lib  # noqa: E402
from easyparallellibrary_tpu.models import smallthinker as st_lib  # noqa: E402
from easyparallellibrary_tpu.models.layer_kinds import ATTENTION, WINDOW_KV  # noqa: E402
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, engine as engine_lib,
    kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import (  # noqa: E402
    ROADMAP_WINDOW_KV, check_draft_compatible)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter  # noqa: E402
from perfbench.reference import smallthinker as ref  # noqa: E402
from perfbench.runners import epl_smallthinker as glue  # noqa: E402

kvw, sa, gmm = (
    importlib.import_module(f"easyparallellibrary_tpu.kernels.{m}")
    for m in ("kv_write", "slot_attention", "moe_gmm"))
KERNELS = [kvw, sa, gmm]

LAYOUT = (0, 1, 1, 1) * 2
REF_CFG = ref.SmallThinkerConfig(
    hidden_size=64, num_attention_heads=14, num_key_value_heads=2,
    head_dim=8, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, sliding_window_size=8,
    sliding_window_layout=LAYOUT, rope_layout=LAYOUT, vocab_size=97,
    n_positions=64, initializer_range=0.2)
# The kernels' tiles: heads of one lane tile (a pair kept in rows), a
# window whose ring of 128 rows a context of 248 goes round.
WIDE_CFG = dataclasses.replace(REF_CFG, head_dim=128, sliding_window_size=100,
                               sliding_window_layout=(0, 1, 1),
                               rope_layout=(0, 1, 1), n_positions=248)
F32 = {"dtype": "float32", "param_dtype": "float32", "ring_tile": 4}
F32_WIDE = dict(F32, ring_tile=128)
LOGIT_TOL = 2e-3
S = 48


def _build(ref_cfg, opts, seed=2 ** 31 + 7):
  epl.init()
  key = ref.seed_key(seed)
  model, shell_of = glue.build_model(ref_cfg, opts)
  params = glue.program_params(
      ref_cfg, key, shell_of(jnp.zeros((1, 8), jnp.int32)))
  return model, params, jax.jit(lambda k: ref.init_params(ref_cfg, k))(key)


@pytest.fixture(scope="module")
def both():
  """(program model, its params, reference params) from one seed."""
  return _build(REF_CFG, F32)


@pytest.fixture(scope="module")
def wide():
  return _build(WIDE_CFG, F32_WIDE)


@pytest.fixture(scope="module")
def ids():
  return jax.random.randint(jax.random.PRNGKey(0), (2, S), 0, 97)


@pytest.fixture(scope="module")
def want(both, ids):
  with jax.default_matmul_precision("highest"):
    return ref.logits(REF_CFG, both[2], ids)


def _backend_takes(monkeypatch, impl):
  for mod in KERNELS:
    monkeypatch.setattr(mod, "_backend_impl", lambda: impl)


def _chunked(model, params, ids, chunk, **impls):
  """``ids`` [B, S] through the slot cache: request ``b`` in slot ``2 b``
  (the odd slots idle), prefilled ``chunk`` positions a step for the first
  half and decoded one a step after it, the second request a step behind
  the first.  Returns the logits [B, S, vocab] of the positions fed."""
  B, S = ids.shape
  slots = 2 * B
  kv, _ = kv_lib.allocate_kv_cache(model.cfg, slots, chunk)
  step = jax.jit(lambda kv, tok, cur, nv: slot_step_logits(
      model, params, kv, tok, cur, num_valid=nv, **impls))
  got = np.zeros((B, S, model.cfg.vocab_size), np.float32)
  cur = np.zeros(slots, np.int32)
  pos = [0] * B
  t = 0
  while min(pos) < S:
    tok = np.zeros((slots, chunk), np.int32)
    nv = np.zeros(slots, np.int32)
    for b in range(B):
      n = min(chunk if pos[b] < S // 2 else 1, S - pos[b])
      if n <= 0 or t < b:
        continue
      tok[2 * b, :n] = np.asarray(ids[b, pos[b]:pos[b] + n])
      nv[2 * b] = n
    with jax.default_matmul_precision("highest"):
      lg, kv = step(kv, jnp.asarray(tok), jnp.asarray(cur), jnp.asarray(nv))
    for b in range(B):
      n = int(nv[2 * b])
      got[b, pos[b]:pos[b] + n] = np.asarray(lg[2 * b, :n])
      pos[b] += n
    cur += nv
    t += 1
  return got


# ----------------------------------------------------- model and reference --


def test_weights_sit_where_the_reference_has_them(both):
  model, params, rp = both
  params = nn.unbox(params)
  assert float(glue.sum_of_squares(params)) == pytest.approx(
      float(glue.sum_of_squares(rp)), rel=1e-5)
  n = sum(x.size for x in jax.tree_util.tree_leaves(params))
  assert n == REF_CFG.param_count()
  block = params["block_1"]
  assert set(block) == {"norm_in", "norm_ff", "attn", "moe"}
  # No bias in the tree: the routing rule is the model's own.
  assert set(block["moe"]) == {"router_kernel", "experts_gate_up",
                               "experts_down"}
  assert block["attn"]["q"]["kernel"].shape == (64, 14 * 8)
  assert block["attn"]["k"]["kernel"].shape == (64, 2 * 8)
  expert = rp["layers"][1]["ff"]["experts"].expert(5)
  np.testing.assert_array_equal(
      np.asarray(block["moe"]["experts_gate_up"][5]),
      np.concatenate([expert["gate"], expert["up"]], -1).astype(np.float32))


def test_parameters_of_the_published_cut_add_up():
  """3,966,937,600 parameters, 7.93 GB at 2 B each; a slot of the cell is
  119.1 MB, 269.0 without the rings (ISSUE 42's reckoning, from the
  program's own leaves)."""
  import json
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  with open(os.path.join(root, "perfbench", "configs",
                         "smallthinker-21b-a3b.json")) as f:
    doc = json.load(f)
  cfg = ref.SmallThinkerConfig.from_file(doc)
  assert cfg.param_count() == doc["parameters"]["total"] == 3966937600
  assert cfg.layer_params() == doc["parameters"]["layer"] == 398627840
  program = glue.model_config(cfg, {})
  assert kv_lib.cache_bytes(program, 1, 32) == 119144448
  assert program.ring_length(32) == 4224
  lay = kv_lib.cache_layout(program, 48, 32)
  assert (lay["kv_leaves"], lay["window_leaves"], lay["kv_order"]) == (
      4, 12, "rows")
  assert lay["window_bytes"] == 48 * 12 * 4224 * 512 * 2
  assert kv_lib.kv_heads(program) == (4, 128)   # not 2560 / 28


def test_full_forward_matches_the_reference(both, ids, want):
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": params}, ids)
  np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("chunk", [1, 4, 8])
def test_prefill_in_chunks_then_decode_matches_the_reference(
    both, ids, want, chunk):
  """Through the slot cache, rings of window - 1 + chunk rows (up to 4)
  that a context of 48 goes round several times, a second request a step
  out of phase and idle slots between."""
  model, params, _ = both
  assert model.cfg.ring_length(chunk) <= S // 3
  got = _chunked(model, params, ids, chunk)
  np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_the_tolerance_has_teeth(both, ids, want):
  """The window, the rotary layout and the router's input each move the
  logits by hundreds of tolerances when they are changed in the
  REFERENCE (its planted faults) or in the PROGRAM."""
  model, params, rp = both
  with jax.default_matmul_precision("highest"):
    for fault in ("no_window", "rope_everywhere"):
      off = ref.logits(REF_CFG, rp, ids, fault)
      assert float(jnp.max(jnp.abs(off - want))) > 100 * LOGIT_TOL, fault
    cfg = model.cfg
    for wrong in (dataclasses.replace(cfg, sliding_window=9),
                  dataclasses.replace(cfg, rope_layout=(1,) * 8),
                  dataclasses.replace(cfg, rope_layout=(0,) * 8),
                  dataclasses.replace(cfg, window_layout=(0,) * 8)):
      got = st_lib.SmallThinker(wrong).apply({"params": params}, ids)
      assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGIT_TOL, wrong


def test_the_router_reads_the_layers_input(both, ids, want, monkeypatch):
  """Fed the post-attention stream in place of the layer's input (the two
  swapped), the program leaves the reference by hundreds of tolerances: in
  the full forward and through the slot cache."""
  model, params, _ = both
  plain = moe_lib.DroplessMoE.__call__

  def swapped(self, x, live=None, router_in=None):
    assert router_in is not None
    return plain(self, x, live, router_in=None)   # routes from ``x``

  monkeypatch.setattr(moe_lib.DroplessMoE, "__call__", swapped)
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": params}, ids)
  assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGIT_TOL
  got = _chunked(model, params, ids, 4)
  assert float(np.max(np.abs(got - np.asarray(want)))) > 100 * LOGIT_TOL


def test_the_routing_rule_is_the_references():
  """Top-k of the logits, softmax over the chosen alone, float32 whatever
  the input's dtype; equal to the softmax over all renormalised over the
  chosen."""
  r = np.random.RandomState(3)
  x = jnp.asarray(r.randn(40, 64), jnp.float32)
  w = jnp.asarray(r.randn(64, 8) * 0.2, jnp.float32)
  with jax.default_matmul_precision("highest"):
    chosen, weights = moe_lib.softmax_topk_route(x, w, 3)
    want_c, want_w = ref.route(REF_CFG, x[None], w, "float32")
  np.testing.assert_array_equal(chosen, want_c[0])
  np.testing.assert_allclose(weights, want_w[0], atol=1e-6)
  full = jax.nn.softmax(x @ w, -1)
  picked = jnp.take_along_axis(full, chosen, -1)
  np.testing.assert_allclose(
      weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
  assert weights.dtype == jnp.float32
  _, low = moe_lib.softmax_topk_route(x.astype(jnp.bfloat16), w, 3)
  assert low.dtype == jnp.float32


def test_the_gate_is_relu_and_the_default_silu():
  r = np.random.RandomState(4)
  x = jnp.asarray(r.randn(6, 16), jnp.float32)
  chosen = jnp.asarray(r.randint(0, 4, (6, 2)), jnp.int32)
  weights = jnp.asarray(r.rand(6, 2), jnp.float32)
  gate_up = jnp.asarray(r.randn(4, 16, 24) * 0.3, jnp.float32)
  down = jnp.asarray(r.randn(4, 12, 16) * 0.3, jnp.float32)
  for gate, act in ((None, jax.nn.silu), (jax.nn.relu, jax.nn.relu)):
    kw = {} if gate is None else {"gate": gate}
    y, _ = moe_lib.dropless_experts(x, chosen, weights, None, gate_up, down,
                                    impl="reference", **kw)
    want = sum(weights[:, i:i + 1] * jnp.einsum(
        "nf,nfd->nd",
        act(jnp.einsum("nd,ndf->nf", x, gate_up[chosen[:, i], :, :12]))
        * jnp.einsum("nd,ndf->nf", x, gate_up[chosen[:, i], :, 12:]),
        down[chosen[:, i]]) for i in range(2))
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_glm_lfm2_and_dots3_keep_their_trees_and_their_routing():
  """What the expert layer now takes as arguments left the other expert
  decoders as they were: a selection bias in the tree, the ``noaux_tc``
  rule, SiLU, the router fed what the experts are fed."""
  from easyparallellibrary_tpu.models.dots3_note import (
      Dots3Note, Dots3NoteConfig)
  from easyparallellibrary_tpu.models.glm_moe import GlmMoe, GlmMoeConfig
  from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
  toy = dict(vocab_size=64, d_model=32, moe_d_ff=16, n_routed_experts=4,
             num_experts_per_tok=2, dtype=jnp.float32,
             param_dtype=jnp.float32)
  latent = dict(num_heads=2, q_lora_rank=16, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)
  models = (
      GlmMoe(GlmMoeConfig(num_layers=2, d_ff=48, **latent, **toy)),
      Lfm2Moe(Lfm2MoeConfig(
          layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
          d_ff=48, num_heads=2, num_kv_heads=1, **toy)),
      Dots3Note(Dots3NoteConfig(
          layer_types=("full_attention", "sliding_attention"), d_ff=48,
          index_n_heads=2, index_head_dim=16, index_topk=4, sliding_window=5,
          swa_num_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=16,
          swa_qk_nope_head_dim=8, swa_qk_rope_head_dim=8, swa_v_head_dim=8,
          ring_tile=8, max_seq_len=64, **latent, **toy)))
  ids = jnp.zeros((1, 8), jnp.int32)
  for model in models:
    assert not hasattr(model.cfg, "expert_route")
    assert not hasattr(model.cfg, "expert_gate")
    shell = nn.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    moe = next(v["moe"] for v in shell.values()
               if isinstance(v, dict) and "moe" in v)
    assert "e_score_correction_bias" in moe
  # The rule they route by, the gate and the input, on one layer: what
  # DroplessMoE gives is the noaux_tc sum over SiLU experts of ``x``.
  cfg = models[1].cfg
  layer = moe_lib.DroplessMoE(cfg, moe_gmm_impl="reference")
  x = jax.random.normal(jax.random.PRNGKey(1), (5, 1, 32), jnp.float32)
  p = nn.unbox(layer.init(jax.random.PRNGKey(2), x)["params"])
  p["e_score_correction_bias"] = jnp.asarray([0.3, -0.2, 0.1, 0.0])
  got = layer.apply({"params": p}, x)
  chosen, weights = moe_lib.noaux_tc_route(
      x[:, 0], p["router_kernel"], p["e_score_correction_bias"], 2,
      cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.route_norm_eps)
  want, _ = moe_lib.dropless_experts(
      x[:, 0], chosen, weights, None, p["experts_gate_up"],
      p["experts_down"], impl="reference")
  np.testing.assert_array_equal(got[:, 0], want)


# ------------------------------------------------- the ring write, a pair --


def _ring_case(dtype, C=16, R=128, W=256, seed=0):
  """Six slots: a window at the ring's start, across a stripe's edge,
  across the ring's END, a slot shorter than the window, an idle slot and
  a slot reused many turns in."""
  r = np.random.RandomState(seed)
  leaves = [jnp.asarray(r.randn(6, R, W), dtype) for _ in range(2)]
  rows = [jnp.asarray(r.randn(6, C, W), dtype) for _ in range(2)]
  cursors = jnp.asarray([0, 128 - C // 2 - 1, R - C // 2, 3, 40,
                         9 * R + R - 3], jnp.int32)
  num_valid = jnp.asarray([C, C, C, 1, 0, C // 2], jnp.int32)
  return leaves, rows, cursors, num_valid


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("R", [128, 256], ids=["one-tile", "two-tiles"])
def test_ring_write_of_a_pair_in_rows_is_bit_identical(dtype, R):
  leaves, rows, cursors, nv = _ring_case(dtype, R=R)
  want = kvw.kv_write_reference(*leaves, *rows, cursors, ring=True)
  got = kvw.kv_write(*leaves, *rows, cursors, nv, impl="interpret",
                     ring=True)
  fed = np.asarray(nv) > 0
  bits = lambda x: np.asarray(x).view(
      {2: np.uint16, 4: np.uint32}[x.dtype.itemsize])
  for g, w, old in zip(got, want, leaves):
    np.testing.assert_array_equal(bits(g)[fed], bits(w)[fed])
    # An idle slot's ring is left as it was (the rows form visits the fed
    # slots alone); nothing reads what the reference wrote there.
    np.testing.assert_array_equal(bits(g)[~fed], bits(old)[~fed])
  # The chunk that crosses the ring's end lies in its last rows and its
  # first.
  C = rows[0].shape[1]
  np.testing.assert_array_equal(got[0][2, R - C // 2:], rows[0][2, :C // 2])
  np.testing.assert_array_equal(got[0][2, :C // 2], rows[0][2, C // 2:])


def test_ring_write_rule_takes_a_pair_in_rows_and_declines_what_it_cannot(
    monkeypatch):
  bf16 = jnp.bfloat16
  # The cell's: 48 slots, rings of 4,224 rows of 4 x 128, chunk 32; and its
  # full layers' leaf.
  assert kvw.kv_write_fits((48, 4224, 512), bf16, 32, ring=True)
  assert kvw.kv_write_fits((48, 16416, 512), bf16, 32)
  assert not kvw.kv_write_fits((48, 4200, 512), bf16, 32, ring=True)  # tiles
  assert not kvw.kv_write_fits((48, 4224, 500), bf16, 32, ring=True)  # lanes
  assert not kvw.kv_write_fits((48, 128, 512), bf16, 48, ring=True)  # stripes
  monkeypatch.setattr(kvw, "_backend_impl", lambda: "pallas")
  assert kvw.resolve_kv_write_impl((48, 4224, 512), bf16, 32,
                                   ring=True) == "pallas"
  assert kvw.resolve_kv_write_impl((48, 4224, 512), bf16, 32, sharded=True,
                                   ring=True) == "reference"


# ------------------------------------------------------- slot_attn_kvwin --


def _attend_case(dtype, C, nv, cursors, H=14, Hkv=2, hd=128, R=256,
                 window=200, seed=0):
  """Rings in which every row no query may see holds NaN."""
  B = len(cursors)
  k = jax.random.split(jax.random.PRNGKey(seed), 3)
  q = jax.random.normal(k[0], (B, C, H, hd), jnp.float32).astype(dtype)
  rings = [jax.random.normal(kk, (B, R, Hkv * hd), jnp.float32).astype(dtype)
           for kk in k[1:]]
  cur, n = jnp.asarray(cursors, jnp.int32), jnp.asarray(nv, jnp.int32)
  held = sa.ring_positions(cur + n, R)
  unseen = ((held < jnp.maximum(cur - window + 1, 0)[:, None])
            | (n[:, None] == 0))
  planted = [jnp.where(unseen[..., None], jnp.nan, x) for x in rings]
  return q, rings, planted, cur, n


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("C", [16, 4], ids=["two-launches", "one-launch"])
def test_kv_window_attend_kernel_equals_the_reference(dtype, tol, C):
  """Groups of 7 on heads of 128: a chunk at the ring's start, one that
  crosses its end, a decode deep in, a slot shorter than the window, an
  idle slot, a partial chunk in a reused slot.  float32 differs by the
  order of the same sums; bfloat16 by the probabilities' rounding before
  the value product (values are O(1))."""
  nv = [C, C, 1, 3, 0, C // 2]
  q, rings, planted, cur, n = _attend_case(
      dtype, C, nv, [0, 250, 1000, 5, 777, 9 * 256 + 30])
  want = sa.slot_attention_kv_window(q, *rings, cur, n, impl="reference",
                                     window=200)
  got = sa.slot_attention_kv_window(q, *planted, cur, n, impl="interpret",
                                    window=200)
  assert not bool(jnp.any(jnp.isnan(got)))
  live = (np.arange(C)[None] < np.asarray(nv)[:, None])[..., None, None]
  np.testing.assert_allclose(
      np.where(live, got.astype(jnp.float32), 0),
      np.where(live, want.astype(jnp.float32), 0), atol=tol, rtol=0)
  # What the kernel does not compute comes out zeros.
  assert float(jnp.max(jnp.abs(jnp.where(live, 0, got)))) == 0.0


def test_kv_window_attend_is_attention_over_each_querys_window():
  """Against plain attention over the slot's HISTORY: row ``p mod R`` holds
  position ``p``, a query at ``t`` sees ``t - window < p <= t``, query head
  ``h`` its K/V head ``h // 7``."""
  C, R, W, H, Hkv, hd = 8, 128, 100, 14, 2, 128
  r = np.random.RandomState(1)
  cursors, nv = np.asarray([0, 95, 300, 1234]), np.asarray([8, 8, 1, 5])
  hist = r.randn(2, 4, 1300, Hkv * hd).astype(np.float32)
  rings = np.zeros((2, 4, R, Hkv * hd), np.float32)
  for b in range(4):
    for p in range(max(0, cursors[b] + nv[b] - R), cursors[b] + nv[b]):
      rings[:, b, p % R] = hist[:, b, p]
  q = r.randn(4, C, H, hd).astype(np.float32)
  with jax.default_matmul_precision("highest"):
    got = np.asarray(sa.slot_attention_kv_window(
        jnp.asarray(q), jnp.asarray(rings[0]), jnp.asarray(rings[1]),
        jnp.asarray(cursors, jnp.int32), jnp.asarray(nv, jnp.int32),
        impl="interpret", window=W))
  for b in range(4):
    for i in range(nv[b]):
      t = cursors[b] + i
      lo = max(0, t - W + 1)
      for h in range(H):
        g = h // 7
        keys = hist[0, b, lo:t + 1, g * hd:(g + 1) * hd]
        vals = hist[1, b, lo:t + 1, g * hd:(g + 1) * hd]
        s = keys @ q[b, i, h] / np.sqrt(hd)
        p = np.exp(s - s.max())
        np.testing.assert_allclose(got[b, i, h], (p / p.sum()) @ vals,
                                   atol=2e-5)


@pytest.mark.parametrize("nv", [[16, 3, 0, 5], [1, 1, 0, 1], [0, 0, 0, 0],
                                [0, 0, 0, 16], [0, 0, 0, 1], [1, 16, 0, 0]],
                         ids=["mixed", "decodes-only", "idle",
                              "no-decode-live-last", "no-prefill-decode-last",
                              "idle-last"])
def test_a_launch_with_nothing_to_do_overwrites_nothing(nv):
  """The decoding slots' launch writes a block of several positions and
  goes first; where either launch has nothing to do, the one block it
  still visits ends as the other launch, or an idle slot, would have it."""
  q, rings, planted, cur, n = _attend_case(
      jnp.float32, 16, nv, [3, 0, 600, 1000])
  want = sa.slot_attention_kv_window(q, *rings, cur, n, impl="reference",
                                     window=200)
  got = sa.slot_attention_kv_window(q, *planted, cur, n, impl="interpret",
                                    window=200)
  live = (np.arange(16)[None] < np.asarray(nv)[:, None])[..., None, None]
  np.testing.assert_allclose(np.where(live, got, 0), np.where(live, want, 0),
                             atol=5e-6, rtol=0)
  assert float(jnp.max(jnp.abs(jnp.where(live, 0, got)))) == 0.0


def test_the_cells_leaves_fit_the_kernels(monkeypatch):
  """At the cell's geometry every rule takes its kernel on a TPU: the full
  layers' pair through ``kv_write`` and ``slot_attn`` (28 on 4 heads of
  128), the rings through the ring write and ``slot_attn_kvwin``, the
  experts through ``moe_gmm``."""
  bf16 = jnp.bfloat16
  assert sa.slot_attn_fits((48, 16416, 512), bf16, 32, 28, 128)
  assert sa.tile_attn_fits((48, 4224, 512), bf16, 32, 28, 128, ring=True)
  assert sa.tile_attn_fits((48, 4224, 512), jnp.float32, 32, 28, 128,
                           ring=True)
  # The pair form is built behind a window only, in rows of whole heads.
  assert not sa.tile_attn_fits((48, 4224, 512), bf16, 32, 28, 128)
  assert not sa.tile_attn_fits((48, 4224, 512), bf16, 32, 28, 64, ring=True)
  assert not sa.tile_attn_fits((48, 4224, 512), bf16, 32, 30, 128, ring=True)
  assert not sa.tile_attn_fits((48, 4200, 512), bf16, 32, 28, 128, ring=True)
  assert sa.pair_tile_positions(32, 28) == 32     # the ring read once
  assert sa.pair_tile_positions(64, 28) == 16
  _backend_takes(monkeypatch, "pallas")
  cfg = glue.model_config(dataclasses.replace(
      REF_CFG, hidden_size=2560, num_attention_heads=28,
      num_key_value_heads=4, head_dim=128, moe_ffn_hidden_size=768,
      moe_num_primary_experts=64, moe_num_active_primary_experts=6,
      sliding_window_size=4096, vocab_size=151936, n_positions=16384), {})
  assert [rule(cfg, 48, 32) for rule in (
      kv_lib.kv_write_impl, kv_lib.slot_attn_impl, kv_lib.kv_win_write_impl,
      kv_lib.kv_win_attn_impl, kv_lib.moe_gmm_impl)] == ["pallas"] * 5
  # A model without window layers over pairs asks neither rule.
  from easyparallellibrary_tpu.models.gpt import GPTConfig
  gpt = GPTConfig(vocab_size=64, num_layers=1, num_heads=2, d_model=128,
                  d_ff=128, max_seq_len=128)
  assert kv_lib.kv_win_write_impl(gpt, 4, 8) is None
  assert kv_lib.kv_win_attn_impl(gpt, 4, 8) is None


# ------------------------------------------------------------------ engine --


def _requests():
  rng = np.random.default_rng(5)
  return [Request(uid=f"r{j}", prompt=rng.integers(0, 97, n).astype(np.int32),
                  max_new_tokens=m)
          for j, (n, m) in enumerate([(3, 6), (19, 9), (41, 5), (11, 12),
                                      (26, 17)])]


def _serve(model, params, slots=3, chunk=4, **kw):
  eng = ContinuousBatchingEngine(model, params, num_slots=slots,
                                 prefill_chunk=chunk, **kw)
  for r in _requests():
    assert eng.submit(r)
  with jax.default_matmul_precision("highest"):
    out = eng.run()
  return eng, out


def _teacher_forced(ref_cfg, rp, out):
  """Every generated token of every request against the reference's
  argmax at its position (float32 logits, no near-tie on this seed)."""
  for r in _requests():
    toks = np.asarray(out[r.uid])
    with jax.default_matmul_precision("highest"):
      lg = ref.logits(ref_cfg, rp, jnp.asarray(toks)[None])[0]
    n = len(r.prompt)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(lg, -1))[n - 1:-1], toks[n:], err_msg=r.uid)


def test_engine_on_mixed_prompts_equals_per_request_reference_decoding(both):
  """Five requests through three slots: reused slots, rings gone round
  (contexts of up to 46 behind rings of 12 rows)."""
  model, params, rp = both
  eng, out = _serve(model, params)
  assert kv_lib.resolved(eng.lowerings) == dict.fromkeys(
      ("kv_write_impl", "slot_attn_impl", "kv_win_write_impl",
       "kv_win_attn_impl", "moe_gmm_impl"), "reference")
  assert eng.step_overlap == "on"
  _teacher_forced(REF_CFG, rp, out)


def test_engine_commits_the_same_under_the_interpreted_kernels(monkeypatch,
                                                               wide):
  model, params, rp = wide
  # Chunk 16: a tiled chunk, whose decoding slots take a launch apart.
  _, plain = _serve(model, params, chunk=16)
  _backend_takes(monkeypatch, "interpret")
  eng, out = _serve(model, params, chunk=16)
  # (The toy experts are narrower than the grouped matmul's tiles.)
  assert kv_lib.resolved(eng.lowerings) == dict(dict.fromkeys(
      ("kv_write_impl", "slot_attn_impl", "kv_win_write_impl",
       "kv_win_attn_impl"), "interpret"), moe_gmm_impl="reference")
  assert eng.cache_layout["kv_order"] == "rows"
  for uid, toks in plain.items():
    np.testing.assert_array_equal(np.asarray(out[uid]), np.asarray(toks))
  _teacher_forced(WIDE_CFG, rp, out)


def test_interpreted_kernels_equal_the_reference_lowerings(wide):
  """One fused call twice over (the second reads what the first wrote):
  prefill chunks, decodes and idle slots side by side, every kernel of the
  window and of the full layers against its reference lowering."""
  model, params, _ = wide
  N, C = 6, 16
  r = np.random.RandomState(4)
  tokens = jnp.asarray(r.randint(0, 97, (N, C)), jnp.int32)
  num_valid = jnp.asarray([C, 1, 0, C // 2, 1, C], jnp.int32)
  got = {}
  for impl in ("interpret", "reference"):
    kv, cursors = kv_lib.allocate_kv_cache(model.cfg, N, C)
    cursors = jnp.asarray([120, 7, 0, 200, 126, 0], jnp.int32)
    step = jax.jit(lambda kv, cur: slot_step_logits(
        model, params, kv, tokens, cur, num_valid=num_valid,
        kv_write_impl=impl, slot_attn_impl=impl, kv_win_write_impl=impl,
        kv_win_attn_impl=impl, moe_gmm_impl="reference"))
    with jax.default_matmul_precision("highest"):
      for _ in range(2):
        lg, kv = step(kv, cursors)
        cursors = cursors + num_valid
    got[impl] = np.asarray(lg)[np.arange(C)[None]
                               < np.asarray(num_valid)[:, None]]
  np.testing.assert_allclose(got["interpret"], got["reference"],
                             atol=LOGIT_TOL, rtol=0)


def test_the_rings_do_not_grow_with_the_served_context(both):
  model = both[0]
  short = kv_lib.cache_layout(model.cfg, 3, 4)
  longer = kv_lib.cache_layout(
      dataclasses.replace(model.cfg, max_seq_len=4 * model.cfg.max_seq_len),
      3, 4)
  assert short["window_bytes"] == longer["window_bytes"] == (
      3 * 6 * 2 * 12 * 16 * 4)
  assert longer["kv_bytes"] > 3 * short["kv_bytes"]
  assert (short["kv_leaves"], short["window_leaves"]) == (4, 12)
  assert kv_lib.layer_kinds(model.cfg) == (
      ATTENTION, WINDOW_KV, WINDOW_KV, WINDOW_KV) * 2
  assert kv_lib.has_kv_window(model.cfg)
  assert not kv_lib.has_latent_cache(model.cfg)
  assert not kv_lib.has_recurrent_state(model.cfg)
  leaves = kv_lib.cache_leaves(model.cfg, 3, 4)
  assert leaves["block_0"]["attn"]["cached_key"].shape == (3, 68, 2, 8)
  assert leaves["block_1"]["attn"]["cached_value"].shape == (3, 12, 2, 8)


@pytest.mark.parametrize("window", [1, 8, 4096])
def test_slot_rows_are_the_sums_they_say(window):
  rng = np.random.default_rng(6)
  resident = rng.integers(0, 9000, 16).astype(np.int32)
  nv = rng.integers(0, 33, 16).astype(np.int32)
  context = sum(r + n for r, n in zip(resident, nv) if n)
  behind = sum(min(r + n, window - 1 + n) for r, n in zip(resident, nv) if n)
  assert engine_lib._slot_rows(resident, nv, window) == (context, behind)


def test_the_engine_says_what_it_resolved_and_counts_the_rows(both):
  """Trace metadata for the window layers' write and attend beside the
  full layers', the cache's layout, and the two row counters every step
  (tracer, per-step record, the stats' summary) against the same sums
  taken from the finished requests."""
  model, params, _ = both
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    stats = ServingStats()
    eng, out = _serve(model, params, stats=stats)
    events = tracer.events()
  finally:
    trace_lib.install(None)
  meta = {e["name"]: e["args"] for e in events if e["ph"] == "M"
          and e["name"].startswith("serving/")}
  for name in ("kv_write_impl", "slot_attn_impl", "kv_win_write_impl",
               "kv_win_attn_impl", "moe_gmm_impl"):
    assert meta[f"serving/{name}"] == {"impl": "reference"}, name
  assert meta["serving/cache_layout"]["window_leaves"] == 12
  assert eng._capture_context()["serving"]["kv_win_attn_impl"] == "reference"
  series = lambda name: [e["args"]["value"] for e in events
                         if e["ph"] == "C" and e["name"] == name]
  steps = len(series("serving/active_slots"))
  assert len(series("serving/context_rows")) == steps
  assert len(series("serving/kv_window_rows")) == steps
  # A request of L tokens feeds positions 0 .. L - 2 in slot-steps of at
  # most a chunk; a slot-step at [s, e) has e rows under its bound and
  # min(e, 8 - 1 + e - s) behind its window.
  assert all(w <= c for w, c in zip(series("serving/kv_window_rows"),
                                    series("serving/context_rows")))
  fed = sum(len(out[r.uid]) - 1 for r in _requests())
  assert sum(series("serving/routed_positions")) == fed
  # Every position is under its own slot-step's bound, and a decode step
  # at position t counts t + 1 rows of context, min(t + 1, 8) of window.
  decode_context = sum(
      t + 1 for r in _requests()
      for t in range(len(r.prompt), len(out[r.uid]) - 1))
  assert sum(series("serving/context_rows")) > decode_context
  assert sum(series("serving/kv_window_rows")) < sum(
      series("serving/context_rows"))
  summary = stats.summary()
  assert summary["context_rows_per_step"] * summary["steps"] == (
      pytest.approx(sum(series("serving/context_rows"))))
  assert summary["kv_window_rows_per_step"] * summary["steps"] == (
      pytest.approx(sum(series("serving/kv_window_rows"))))


def test_the_step_record_carries_the_row_counters(both):
  from easyparallellibrary_tpu.observability.registry import MetricRegistry
  model, params, _ = both
  registry = MetricRegistry()
  eng = ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                                 registry=registry)
  assert eng.submit(Request(uid="a", prompt=np.arange(13, dtype=np.int32),
                            max_new_tokens=3))
  with jax.default_matmul_precision("highest"):
    eng.run()
  assert {"serving/context_rows", "serving/kv_window_rows"} <= set(
      registry.latest())


@pytest.mark.parametrize("feature", [
    dict(paged=True), dict(prefix_cache=True, paged=True),
    dict(drafter=NgramDrafter(k=2)), dict(resilience=True)])
def test_rollback_features_refuse_the_kv_ring_with_one_message(both,
                                                               feature):
  model, params, _ = both
  with pytest.raises(ValueError) as e:
    ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                             **feature)
  msg = str(e.value)
  assert ROADMAP_WINDOW_KV in msg and "ROADMAP item R6" in msg
  assert "window_kv" in msg and "SmallThinkerConfig" in msg


def test_a_draft_model_with_a_kv_ring_is_refused(both):
  model = both[0]
  with pytest.raises(ValueError) as e:
    check_draft_compatible(model.cfg, model.cfg)
  assert ROADMAP_WINDOW_KV in str(e.value)
