"""The decoder whose full layers select the rows they attend and whose
other layers attend behind a window (models/dots3_note.py, the shared
``LatentAttention`` of models/glm_moe.py, models/moe.py's share of the
experts) against the benchmark's plain reference
(perfbench/reference/dots3_note.py); its two new cache kinds, the ring
write, the two new attends and the index kernel in the engine.

Toy widths with every mechanism: hidden 64; a full layer of 4 heads (16 |
8 | 16 on ranks 32 / 32) with an indexer of 2 heads of 16 that keeps 4
rows; a window layer of 2 heads (24 | 8 | 16 on ranks 32 / 48) behind a
window of 5; 8 routed experts top-2 of which this chip holds 3 (2, 3, 4)
beside a shared one; the published period [full (dense), full, sliding,
sliding, sliding]; vocabulary 256.  float32 on both sides, matmuls at
``highest``.  The kernel cases use lane-tile widths (8 heads, an index key
of 128, rings of 128 rows).

Tolerances: logits are O(1-10) (weights N(0, 0.2), as tests/test_glm_moe.py
argues), program and reference differ by float32 rounding in another order
of the same sums, so ``3e-4`` absolute on logits is ~30 x what is seen and
far below what a wrong term gives: an index score computed in bfloat16
flips selections (5e-2 and more on this seed, asserted below), a dropped
gate halves a layer's attention output.  The seed is one with no near-tie,
within float32 rounding, at the 4th index score or the 2nd expert: a flip
there is a different and equally valid choice that moves logits far more
than rounding does; the cell's check on the chip lives with it (PERF.md).
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
from easyparallellibrary_tpu.models.blocks import ring_length  # noqa: E402
from easyparallellibrary_tpu.models.layer_kinds import (  # noqa: E402
    SPARSE_LATENT, WINDOW_LATENT)
from easyparallellibrary_tpu.models.slot_core import slot_step_logits  # noqa: E402
from easyparallellibrary_tpu.observability import trace as trace_lib  # noqa: E402
from easyparallellibrary_tpu.profiler.serving import ServingStats  # noqa: E402
from easyparallellibrary_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine, Request, engine as engine_lib,
    kv_cache as kv_lib)
from easyparallellibrary_tpu.serving._capabilities import (  # noqa: E402
    ROADMAP_LATENT_CACHE, ROADMAP_SPARSE_LATENT, ROADMAP_WINDOW_LATENT,
    check_draft_compatible)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter  # noqa: E402
from perfbench.reference import dots3_note as ref  # noqa: E402
from perfbench.runners import epl_dots3_note as glue  # noqa: E402

kvw, sa, di, gmm = (
    importlib.import_module(f"easyparallellibrary_tpu.kernels.{m}")
    for m in ("kv_write", "slot_attention", "dsa_index", "moe_gmm"))
KERNELS = [kvw, sa, di, gmm]

PERIOD = (ref.FULL, ref.FULL, ref.SLIDING, ref.SLIDING, ref.SLIDING)
REF_CFG = ref.Dots3NoteConfig(
    layer_types=PERIOD, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32,
    full=ref.LatentSizes(heads=4, q_rank=32, kv_rank=32, nope=16, rope=8,
                         value=16, theta=8e7),
    swa=ref.LatentSizes(heads=2, q_rank=32, kv_rank=48, nope=24, rope=8,
                        value=16, theta=5e4),
    index_n_heads=2, index_head_dim=16, index_topk=4, sliding_window_size=5,
    router_width=8, experts_first=2, n_routed_experts=3, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, vocab_size=256,
    n_positions=128, initializer_range=0.2, bias_std=0.05)
# The kernels' tiles: 8 heads of either kind, an index key of one lane
# tile, a latent row of whole sublane tiles.
WIDE_CFG = dataclasses.replace(
    REF_CFG,
    full=dataclasses.replace(REF_CFG.full, heads=8),
    swa=dataclasses.replace(REF_CFG.swa, heads=8),
    index_head_dim=128, n_positions=248)
F32 = {"dtype": "float32", "param_dtype": "float32", "ring_tile": 8}
F32_WIDE = dict(F32, ring_tile=128)
LOGIT_TOL = 3e-4
S = 40


def _build(ref_cfg, opts, seed=2 ** 31 + 5):
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
  return jax.random.randint(jax.random.PRNGKey(0), (3, S), 0, 256)


@pytest.fixture(scope="module")
def want(both, ids):
  return ref.logits(REF_CFG, both[2], ids)


def _backend_takes(monkeypatch, impl):
  for mod in KERNELS:
    monkeypatch.setattr(mod, "_backend_impl", lambda: impl)


def _chunked(model, params, ids, chunk, **impls):
  """``ids`` [B, S] through slot mode ``chunk`` positions at a time;
  returns the logits of every position [B, S, V]."""
  B, S_ = ids.shape
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, chunk)
  outs = []
  with jax.default_matmul_precision("highest"):
    for s in range(0, S_, chunk):
      nv = jnp.full((B,), min(chunk, S_ - s), jnp.int32)
      block = jnp.zeros((B, chunk), jnp.int32).at[:, :int(nv[0])].set(
          ids[:, s:s + chunk])
      lg, kv = slot_step_logits(model, params, kv, block, cur,
                                num_valid=nv, **impls)
      cur = cur + nv
      outs.append(lg[:, :int(nv[0])])
  return jnp.concatenate(outs, 1)


# ------------------------------------------------------ model vs reference --


def test_weights_sit_where_the_reference_has_them(both):
  model, params, rp = both
  n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
  assert n == REF_CFG.param_count()
  np.testing.assert_allclose(float(glue.sum_of_squares(params)),
                             float(glue.sum_of_squares(rp)), rtol=1e-5)
  full, window = (params[f"block_{i}"]["latent"] for i in (1, 2))
  assert {"gate", "index_q", "index_k", "index_k_norm", "index_w"} <= set(full)
  assert "gate" in window and not any(k.startswith("index") for k in window)
  # The router keeps its width, the stacks hold this chip's three experts.
  moe = nn.unbox(params["block_1"]["moe"])
  assert moe["router_kernel"].shape == (64, 8)
  assert moe["experts_gate_up"].shape == (3, 64, 64)
  np.testing.assert_array_equal(
      np.asarray(moe["experts_down"]),
      np.asarray(rp["layers"][1]["ff"]["experts"]["down"]))


def test_parameters_of_the_published_cut_add_up():
  """ISSUE 39's count, from the reference's own arithmetic at the
  published widths (the cell's test reckons it from the program's tree)."""
  import json
  doc = json.load(open(os.path.join(
      os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
      "perfbench", "configs", "dots3-note-prev.json")))
  cfg = ref.Dots3NoteConfig.from_file(doc)
  assert cfg.mixer_params(ref.FULL) == {
      "mixer": 134_022_656, "gate": 655_360, "indexer": 9_371_904}
  assert cfg.mixer_params(ref.SLIDING) == {
      "mixer": 90_507_264, "gate": 327_680}
  assert cfg.param_count() == 4_087_154_176


def test_full_forward_matches_the_reference(both, ids, want):
  model, params, _ = both
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": params}, ids)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("chunk", [1, 4, 8])
def test_prefill_in_chunks_then_decode_matches_the_reference(
    both, ids, want, chunk):
  """Through the two new cache kinds: 40 positions wrap a ring of 8 or 16
  rows several times and pass position 4, where the selection starts to
  discard, in the first chunks."""
  model, params, _ = both
  assert model.cfg.ring_length(chunk) == ring_length(5, chunk, 8) < S
  got = _chunked(model, params, ids, chunk)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=LOGIT_TOL, rtol=0)


def test_the_cells_window_ring_and_selection_sizes_match_the_reference():
  """Toy widths at the cell's STRUCTURAL sizes (window 513, a ring of 640
  rows, a selection of 2048, chunk 32): 2,200 positions wrap the ring
  three times and pass position 2048, where the selection starts to
  discard, by 150."""
  S_long = 2200
  cfg = dataclasses.replace(REF_CFG, index_topk=2048,
                            sliding_window_size=513, n_positions=S_long + 32)
  model, params, rp = _build(cfg, dict(F32, ring_tile=128))
  assert model.cfg.ring_length(32) == 640
  long_ids = jax.random.randint(jax.random.PRNGKey(9), (1, S_long), 0, 256)
  got = _chunked(model, params, long_ids, 32)
  want = ref.logits(cfg, rp, long_ids)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=LOGIT_TOL, rtol=0)


def test_the_tolerance_has_teeth(both, ids, want):
  """What the comparison must fail: index scores computed in bfloat16 (the
  reference's own control) and a gate that is dropped."""
  model, params, rp = both
  # a bfloat16 score moves the logits where it flips a near-tie at the
  # selection's edge: some of 16 sequences have one
  more = jax.random.randint(jax.random.PRNGKey(3), (16, S), 0, 256)
  low = ref.logits(REF_CFG, rp, more, "bf16index")
  assert float(jnp.max(jnp.abs(low - ref.logits(REF_CFG, rp, more)))) > (
      100 * LOGIT_TOL)
  ungated = jax.tree_util.tree_map_with_path(
      lambda path, x: jnp.zeros_like(x)
      if any(getattr(k, "key", None) == "gate" for k in path)
      and any(getattr(k, "key", None) == "latent" for k in path) else x,
      params)
  with jax.default_matmul_precision("highest"):
    got = model.apply({"params": ungated}, ids)
  assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGIT_TOL


def test_selection_and_window_change_the_answer(both, ids, want):
  """Neither mask is vacuous at these sizes: keeping every row, or a
  window that holds the sequence, gives other logits."""
  _, _, rp = both
  for change in ({"index_topk": S}, {"sliding_window_size": S}):
    other = ref.logits(dataclasses.replace(REF_CFG, **change), rp, ids)
    assert float(jnp.max(jnp.abs(other - want))) > 100 * LOGIT_TOL


def test_interpreted_kernels_equal_the_reference_lowerings(wide):
  """The fused step under the four kernels' interpreted forms (the ring
  write, ``dsa_index``, ``slot_attn_sel``, ``slot_attn_win``) against
  their reference lowerings and against the plain reference, across a
  ring wrap (128 rows, 144 positions)."""
  model, params, rp = wide
  long_ids = jax.random.randint(jax.random.PRNGKey(1), (2, 144), 0, 256)
  want = ref.logits(WIDE_CFG, rp, long_ids)
  C = 8
  assert kv_lib.cache_leaves(model.cfg, 2, C)["block_2"]["latent"][
      "cached_latent"].shape == (2, 128, 1, 56)
  ref_l = _chunked(model, params, long_ids, C, kv_write_impl="reference",
                   slot_attn_impl="reference", dsa_index_impl="reference",
                   moe_gmm_impl="reference")
  got = _chunked(model, params, long_ids, C, kv_write_impl="interpret",
                 slot_attn_impl="interpret", dsa_index_impl="interpret",
                 moe_gmm_impl="interpret")
  np.testing.assert_allclose(np.asarray(ref_l), np.asarray(want),
                             atol=LOGIT_TOL, rtol=0)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=LOGIT_TOL, rtol=0)


def _step_shapes(closed):
  """Every array shape of a traced program, the bodies of its conditionals,
  loops, jitted calls and kernels' wrappers included."""
  shapes = set()

  def walk(jaxpr):
    for eqn in jaxpr.eqns:
      shapes.update(tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                    if hasattr(v.aval, "shape"))
      if eqn.primitive.name == "pallas_call":
        continue                                   # a kernel's own blocks
      for sub in jax.core.jaxprs_in_params(eqn.params):
        walk(sub)
  walk(closed.jaxpr)
  return shapes


def _slot_ordered(B, C, model):
  """The ``[slots, chunk]``-ordered arrays of queries and of attended
  latent rows a step of ``model`` could hold, a layer type each."""
  shapes = set()
  for kind in (ref.FULL, ref.SLIDING):
    dims = model.cfg.latent_dims(kind)
    H, r, W = dims.num_heads, dims.kv_lora_rank, dims.latent_dim
    shapes |= {(B, C, H, W), (B, C, H, r), (B, C * H, r), (B * C, H, r)}
  return shapes


def test_kernels_read_the_flat_batch_where_it_lies(wide):
  """Under a flat batch narrower than ``slots x chunk`` the selected and
  the windowed kernels take their query rows from the flat batch itself
  (slot ``b``'s from row ``starts[b]`` on, at no tile's edge) and write
  their result to the same rows: two steps of a whole chunk, a partial one,
  an idle slot and a decode, interpreted kernels against the reference
  lowerings at the same width and at full width.  The traced step holds no
  ``[slots, chunk]``-ordered array of queries or of attended rows, which the
  reference lowering at the same width does."""
  model, params, _ = wide
  B, C = 4, 16
  tokens = jax.random.randint(jax.random.PRNGKey(3), (2, B, C), 0, 256)
  num_valid = jnp.asarray([[C, 3, 0, 1], [1, C, 5, 0]], jnp.int32)
  kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, C)
  trace = lambda impl: _step_shapes(jax.make_jaxpr(
      lambda kv: slot_step_logits(
          model, params, kv, tokens[0], cur, num_valid=num_valid[0], width=40,
          kv_write_impl=impl, slot_attn_impl=impl, dsa_index_impl=impl,
          moe_gmm_impl="reference"))(kv))
  assert not trace("interpret") & _slot_ordered(B, C, model)
  assert len(trace("reference") & _slot_ordered(B, C, model)) >= 4
  got = {}
  with jax.default_matmul_precision("highest"):
    for name, impl, width in (("flat", "interpret", 40),
                              ("same width", "reference", 40),
                              ("full width", "reference", None)):
      kv, cur = kv_lib.allocate_kv_cache(model.cfg, B, C)
      outs = []
      for step in range(2):
        lg, kv = slot_step_logits(
            model, params, kv, tokens[step], cur, num_valid=num_valid[step],
            width=width, kv_write_impl=impl, slot_attn_impl=impl,
            dsa_index_impl=impl, moe_gmm_impl="reference")
        cur = cur + num_valid[step]
        live = np.arange(C)[None] < np.asarray(num_valid[step])[:, None]
        outs.append(np.asarray(lg)[live])
      got[name] = np.concatenate(outs)
  for name in ("same width", "full width"):
    np.testing.assert_allclose(got["flat"], got[name], atol=LOGIT_TOL,
                               rtol=0, err_msg=name)


# ----------------------------------------------------------------- kernels --

DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32", "bf16"])


def _tol(dtype):
  return 3e-2 if dtype == jnp.bfloat16 else 1e-5


@DTYPES
@pytest.mark.parametrize("R", [128, 256], ids=["one-tile", "two-tiles"])
@pytest.mark.parametrize("cursors", [[0, 100, 250, 1020],
                                     [252, 255, 256 + 249, 5 * 256 + 253],
                                     [120, 121, 127, 128]])
def test_ring_write_lands_in_two_stripes_bit_for_bit(dtype, cursors, R):
  """The ring form of ``kv_write``: a chunk that crosses the ring's end
  continues at its head (in the leaf's first tile, or, in a ring of one
  tile, in the same one); interpreted kernel, reference and the
  definition agree bit for bit and no other row moves."""
  rng = np.random.default_rng(0)
  B, C, W = 4, 8, 48
  leaf = jnp.asarray(rng.normal(size=(B, R, 1, W)), dtype)
  new = jnp.asarray(rng.normal(size=(B, C, 1, W)), dtype)
  cur = jnp.asarray(cursors, jnp.int32)
  want = np.array(leaf.astype(jnp.float32))
  for b in range(B):
    for i in range(C):
      want[b, (cursors[b] + i) % R] = np.asarray(new[b, i], np.float32)
  for impl in ("reference", "interpret"):
    got, none = kvw.kv_write(leaf, None, new, None, cur, impl=impl, ring=True)
    assert none is None
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)


def test_ring_write_rule_declines_what_it_cannot_tile(monkeypatch):
  monkeypatch.setattr(kvw, "_backend_impl", lambda: "pallas")
  take = lambda shape, **kw: kvw.resolve_kv_write_impl(
      shape, jnp.bfloat16, 32, **kw)
  assert take((32, 640, 1, 1088), ring=True) == "pallas"
  assert take((32, 600, 1, 1088), ring=True) == "reference"   # not tiles
  assert take((32, 640, 1088), ring=True) == "reference"      # kept in rows
  assert take((32, 640, 1, 1088), ring=True, sharded=True) == "reference"


CHUNKS = pytest.mark.parametrize("C", [8, 16], ids=["one-launch",
                                                    "decodes-apart"])


def _slots(C):
  """Cursors and live positions of four slots: a whole chunk from a
  leaf's start, a partial one across a block's edge, an idle slot and a
  decode (which a tiled chunk serves in a launch of its own)."""
  return (jnp.asarray([0, 125, 240, 200], jnp.int32),
          jnp.asarray([C, 3, 0, 1], jnp.int32))


@DTYPES
@CHUNKS
def test_dsa_index_kernel_equals_the_reference(dtype, C):
  """Index scores of live queries under their bounds, NaN planted at and
  beyond every bound; a dead position's row holds anything."""
  rng = np.random.default_rng(1)
  B, Hi, d, Lc = 4, 4, 128, 264
  q = jnp.asarray(rng.normal(size=(B, C, Hi, d)), dtype)
  w = jnp.asarray(rng.normal(size=(B, C, Hi)), jnp.float32)
  keys = rng.normal(size=(B, Lc, d)).astype(np.float32)
  cur, nv = _slots(C)
  want = di.dsa_index(q, w, jnp.asarray(keys, dtype), cur, nv,
                      impl="reference")
  for b in range(B):
    keys[b, int(cur[b] + nv[b]):] = np.nan
  got = di.dsa_index_pallas(q, w, jnp.asarray(keys, dtype), cur, nv,
                            interpret=True, block=128)
  for b in range(B):
    n = int(nv[b])
    np.testing.assert_allclose(np.asarray(got[b, :n]),
                               np.asarray(want[b, :n]), rtol=1e-4, atol=1e-3)
    t = int(cur[b]) + np.arange(n)
    assert np.all(np.asarray(got[b, :n])[
        np.arange(Lc)[None] > t[:, None]] == di.MASKED)


def test_kth_largest_is_exact():
  rng = np.random.default_rng(2)
  x = rng.normal(size=(37, 300)).astype(np.float32)
  x[:, 250:] = di.MASKED
  x[3, :5] = [0.0, -0.0, 1e-38, -1e-38, 7.0]
  k = rng.integers(1, 250, 37).astype(np.int32)
  got = np.asarray(di.kth_largest(jnp.asarray(x), jnp.asarray(k)))
  want = np.sort(x, 1)[:, ::-1][np.arange(37), k - 1]
  np.testing.assert_array_equal(got, want)


@DTYPES
@CHUNKS
def test_selected_attend_kernel_equals_the_reference(dtype, C):
  """``slot_attn_sel``: the rows a query's threshold keeps, nothing at or
  beyond a bound (NaN planted there), zeros for dead positions."""
  rng = np.random.default_rng(3)
  B, H, W, r, Lc = 4, 8, 48, 32, 264
  q = jnp.asarray(rng.normal(size=(B, C, H, W)), dtype)
  leaf = rng.normal(size=(B, Lc, 1, W)).astype(np.float32)
  cur, nv = _slots(C)
  t = np.asarray(cur)[:, None] + np.arange(C)[None]
  scores = jnp.where(jnp.arange(Lc)[None, None] <= t[..., None],
                     jnp.asarray(rng.normal(size=(B, C, Lc)), jnp.float32),
                     di.MASKED)
  thr = di.kth_largest(scores.reshape(B * C, Lc),
                       jnp.clip(jnp.asarray(t).reshape(-1) + 1, 1, 16)
                       ).reshape(B, C)
  want = sa.slot_attention_selected(q, jnp.asarray(leaf, dtype), scores, thr,
                                    cur, nv, impl="reference", v_width=r,
                                    scale=0.2)
  for b in range(B):
    leaf[b, int(cur[b] + nv[b]):] = np.nan
  got = sa.slot_attention_selected_pallas(
      q, jnp.asarray(leaf, dtype), scores, thr, cur, nv, interpret=True,
      block=128, v_width=r, scale=0.2)
  for b in range(B):
    n = int(nv[b])
    np.testing.assert_allclose(
        np.asarray(got[b, :n], np.float32), np.asarray(want[b, :n], np.float32),
        rtol=_tol(dtype), atol=_tol(dtype))
    assert np.all(np.asarray(got[b, n:], np.float32) == 0)


@DTYPES
@CHUNKS
@pytest.mark.parametrize("block", [None, 128], ids=["one-block", "two-blocks"])
def test_window_attend_kernel_equals_attention_over_the_window(dtype, block,
                                                                C):
  """``slot_attn_win`` over a ring of 256 rows (window 133), before and
  after it wraps: against plain attention over the positions ``t - 133 < s
  <= t`` of the slot's history; never-written rows and the dead rows this
  step wrote hold NaN."""
  rng = np.random.default_rng(4)
  B, H, W, r, R, window = 5, 8, 48, 32, 256, 133
  q = jnp.asarray(rng.normal(size=(B, C, H, W)), dtype)
  cur = np.array([0, 100, 250, 1000, 700])
  nv = np.array([C, 3, C, 0, 1])
  hist = rng.normal(size=(B, 1100, W)).astype(np.float32)
  ring = np.full((B, R, 1, W), np.nan, np.float32)
  for b in range(B):
    for p in range(cur[b] + nv[b]):
      ring[b, p % R, 0] = hist[b, p]
    for p in range(cur[b] + nv[b], cur[b] + C):
      ring[b, p % R, 0] = np.nan
  ring = jnp.asarray(ring, dtype)
  got = sa.slot_attention_window_pallas(
      q, ring, jnp.asarray(cur), jnp.asarray(nv), interpret=True, block=block,
      window=window, v_width=r, scale=0.2)
  ref_l = sa.slot_attention_window(
      q, jnp.nan_to_num(ring), jnp.asarray(cur), jnp.asarray(nv),
      impl="reference", window=window, v_width=r, scale=0.2)
  for b in range(B):
    for i in range(nv[b]):
      t = cur[b] + i
      k = jnp.asarray(hist[b, max(0, t - window + 1):t + 1], dtype).astype(
          jnp.float32)
      p = jax.nn.softmax(jnp.einsum(
          "hd,kd->hk", q[b, i].astype(jnp.float32), k) * 0.2, -1)
      for out in (got, ref_l):
        np.testing.assert_allclose(np.asarray(out[b, i], np.float32),
                                   np.asarray(p @ k[:, :r]),
                                   rtol=_tol(dtype), atol=_tol(dtype))
    assert np.all(np.asarray(got[b, nv[b]:], np.float32) == 0)


@pytest.mark.parametrize("form", ["sel", "win"])
@pytest.mark.parametrize("nv", [[16, 3, 0, 5], [1, 1, 0, 1], [0, 0, 0, 0],
                                [0, 0, 0, 16]],
                         ids=["no-decode", "only-decodes", "idle",
                              "last-slot-alone"])
def test_a_launch_with_nothing_to_do_overwrites_nothing(form, nv):
  """A tiled chunk is served by two launches into one buffer (the slots
  that feed several positions, the decoding slots); a launch whose kind of
  slot is absent still visits one tile, which must leave what the other
  launch wrote there as it is.  In ``[slots, chunk]`` order and from the
  flat batch, whose result lies where its queries do and is zeros in the
  rows no position lives in."""
  case = _attend_case(form, nv, [0, 125, 40, 200])
  B, C, want, nv = case["B"], case["C"], case["want"], np.asarray(nv)
  got = case["pallas"](case["q"])
  for b in range(B):
    n = int(nv[b])
    np.testing.assert_allclose(np.asarray(got[b, :n]), np.asarray(want[b, :n]),
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got[b, n:]) == 0)
  # (a flat batch holds a tile of positions at least: 8 rows)
  flat, starts, live = _packed(case["q"], nv, rows=sum(nv) + 9)
  got = np.asarray(case["pallas"](flat, starts=starts, chunk=C))
  assert got.shape == (sum(nv) + 9,) + want.shape[2:]
  np.testing.assert_allclose(got[:sum(nv)], np.asarray(want)[live],
                             rtol=1e-5, atol=1e-5)
  assert np.all(got[sum(nv):] == 0)


def _attend_case(form, nv, cursors, C=16):
  """Operands of one attend of either one-leaf form over ``len(nv)`` slots
  (float32, 8 heads of 48 on values of 32, leaves of 256 rows), the
  reference's result in ``[slots, chunk]`` order, the jitted kernel
  (interpreted) and the operands :func:`sa._tile_launch` takes after
  ``(q, starts, leaf, cur, bound, into, C, feeds)``."""
  rng = np.random.default_rng(8)
  B, H, W, r, L = len(nv), 8, 48, 32, 256
  q = jnp.asarray(rng.normal(size=(B, C, H, W)), jnp.float32)
  leaf = jnp.asarray(rng.normal(size=(B, L, 1, W)), jnp.float32)
  cur, nv = jnp.asarray(cursors, jnp.int32), jnp.asarray(nv, jnp.int32)
  if form == "win":
    kw = dict(window=133, v_width=r, scale=0.2)
    want = sa.slot_attention_window(q, leaf, cur, nv, impl="reference", **kw)
    pallas = lambda q, **flat: sa.slot_attention_window_pallas(
        q, leaf, cur, nv, interpret=True, **flat, **kw)
    scores = thr = None
  else:
    t = np.asarray(cur)[:, None] + np.arange(C)[None]
    scores = jnp.where(jnp.arange(L)[None, None] <= t[..., None],
                       jnp.asarray(rng.normal(size=(B, C, L)), jnp.float32),
                       di.MASKED)
    thr = di.kth_largest(scores.reshape(B * C, L),
                         jnp.clip(jnp.asarray(t).reshape(-1) + 1, 1, 16)
                         ).reshape(B, C)
    kw = dict(v_width=r, scale=0.2)
    want = sa.slot_attention_selected(q, leaf, scores, thr, cur, nv,
                                      impl="reference", **kw)
    pallas = lambda q, **flat: sa.slot_attention_selected_pallas(
        q, leaf, scores, thr, cur, nv, interpret=True, **flat, **kw)
  launch = lambda q, starts, into, C_, feeds: sa._tile_launch(
      q, starts, leaf, cur, jnp.where(nv > 0, cur + nv, 0), into, C_, feeds,
      *((None, None) if scores is None else
        (scores[:, :C_], thr[:, :C_])),
      window=kw.get("window"), interpret=True, block=None, v_width=r,
      scale=0.2)
  return dict(B=B, C=C, q=q, want=want, pallas=pallas, launch=launch)


def _packed(q, nv, rows):
  """``q`` ``[B, C, ..]`` as a flat batch of ``rows`` rows, each slot's
  first ``nv[b]`` positions in slot order and zeros after them; the flat
  row of each slot's first position; the ``[B, C]`` mask that picks the
  live positions in that order."""
  nv = np.asarray(nv)
  live = np.arange(q.shape[1])[None] < nv[:, None]
  flat = np.zeros((rows,) + q.shape[2:], np.float32)
  flat[:nv.sum()] = np.asarray(q)[live]
  return (jnp.asarray(flat), jnp.asarray(np.cumsum(nv) - nv, jnp.int32), live)


SENTINEL = -7.0


@pytest.mark.parametrize("form", ["sel", "win"])
def test_a_tile_writes_its_live_positions_and_no_other_row(form):
  """The flat output handed to a launch full of a sentinel: slots that feed
  1, 7, 8, 9, a whole chunk of 16 and 0 positions beside each other and a
  last slot of 5 whose one tile starts within a tile of the batch's last
  row (it is read from ``T - 8`` on and worked ``shift`` rows down).  The
  launch over the slots that feed several positions writes their live rows
  and leaves the decoding slot's row, the rows after a partial last tile
  (the NEXT slot's) and the batch's padding rows as they were; the decoding
  slots' launch then fills that one row."""
  nv = np.asarray([1, 7, 8, 9, 16, 0, 5])
  case = _attend_case(form, nv, [200, 0, 125, 40, 3, 77, 230])
  T = nv.sum() + 2
  flat, starts, live = _packed(case["q"], nv, rows=T)
  assert int(starts[-1]) > T - 8 and int(starts[-1]) + 5 < T
  want = np.asarray(case["want"])[live]
  many, one = sa.split_decodes(jnp.asarray(nv), case["C"])
  into = jnp.full((T, 8, 32), SENTINEL, jnp.float32)
  got = np.asarray(case["launch"](flat, starts, into, case["C"], many))
  assert np.all(got[0] == SENTINEL)            # the decoding slot's row
  assert np.all(got[nv.sum():] == SENTINEL)    # the padding rows
  np.testing.assert_allclose(got[1:nv.sum()], want[1:], rtol=1e-5, atol=1e-5)
  got = np.asarray(case["launch"](flat, starts, jnp.asarray(got), 1, one))
  np.testing.assert_allclose(got[:nv.sum()], want, rtol=1e-5, atol=1e-5)
  assert np.all(got[nv.sum():] == SENTINEL)


@pytest.mark.parametrize("form", ["sel", "win"])
@pytest.mark.parametrize("nv", [[1, 9], [9, 1], [1, 9, 1], [16, 1, 0, 3]],
                         ids=["decode-first", "decode-last", "between",
                              "idle-between"])
def test_the_two_launches_land_beside_each_other(form, nv):
  """The decoding slots' launch and the other's write disjoint rows of the
  flat output (or, where one has nothing to do and visits a tile all the
  same, what the other writes there), whichever slot comes first in the
  batch and whichever launch runs first: nothing a launch wrote is
  written over, every other row keeps the sentinel."""
  nv = np.asarray(nv)
  case = _attend_case(form, nv, [200, 0, 125, 40][:len(nv)])
  T = nv.sum() + 1
  flat, starts, live = _packed(case["q"], nv, rows=T)
  many, one = sa.split_decodes(jnp.asarray(nv), case["C"])
  launch = jax.jit(case["launch"], static_argnums=3)
  launches = [(case["C"], many), (1, one)]
  for order in (launches, launches[::-1]):
    out = jnp.full((T, 8, 32), SENTINEL, jnp.float32)
    for C_, feeds in order:
      out = launch(flat, starts, out, C_, feeds)
    np.testing.assert_allclose(np.asarray(out)[:nv.sum()],
                               np.asarray(case["want"])[live],
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(out)[nv.sum():] == SENTINEL)


def test_the_pair_form_takes_and_gives_what_it_did():
  """``slot_attn_kvwin`` keeps its ``[slots, chunk, heads x hd]`` operands
  and output (an offset in rows into a rank-2 flat batch is one Mosaic
  cannot take): two launches, the decoding slots' first, each handed the
  output buffer, the stacked rows' positions, ``q`` and the two rings, and
  blocks of the output a tile."""
  B, C, H, Hkv, hd, R = 4, 16, 8, 2, 128, 256
  q = jnp.zeros((B, C, H, hd), jnp.float32)
  ring = jnp.zeros((B, R, Hkv * hd), jnp.float32)
  closed = jax.make_jaxpr(lambda *a: sa.slot_attention_kv_window_pallas(
      *a, interpret=True, window=133, scale=0.1))(
          q, ring, ring, jnp.zeros((B,), jnp.int32),
          jnp.full((B,), C, jnp.int32))
  (inner,) = [e for e in closed.jaxpr.eqns if e.primitive.name in (
      "pjit", "jit")]
  calls = [e for e in inner.params["jaxpr"].jaxpr.eqns
           if e.primitive.name == "pallas_call"]
  rows = lambda tp: Hkv * sa._pair_rows(tp, H // Hkv, jnp.float32)
  assert [sa.pair_tile_positions(c, H) for c in (1, C)] == [1, C]
  for call, tp in zip(calls, (1, C), strict=True):
    assert call.params["name"] == sa.SLOT_ATTN_KVWIN
    assert [tuple(v.aval.shape) for v in call.invars[-5:]] == [
        (B, C, H * hd), (rows(tp), 1), (B, C, H * hd), (B, R, Hkv * hd),
        (B, R, Hkv * hd)]
    assert [tuple(v.aval.shape) for v in call.outvars] == [(B, C, H * hd)]
    out_block = call.params["grid_mapping"].block_mappings[-1].block_shape
    assert tuple(int(getattr(d, "block_size", d)) for d in out_block) == (
        1, 8 if tp == 1 else C, H * hd)


def test_the_cells_leaves_fit_the_kernels(monkeypatch):
  """The dispatch rules at the cell's shapes (32 slots x chunk 32, context
  12,800, published widths), on a backend that takes the kernels."""
  _backend_takes(monkeypatch, "pallas")
  from easyparallellibrary_tpu.models.dots3_note import Dots3NoteConfig
  cfg = Dots3NoteConfig(vocab_size=19008, experts_held=(0, 32),
                        max_seq_len=12800)
  for rule in (kv_lib.kv_write_impl, kv_lib.slot_attn_impl,
               kv_lib.dsa_index_impl, kv_lib.moe_gmm_impl):
    assert rule(cfg, 32, 32) == "pallas", rule.__name__
  assert sa.tile_positions(32, 128) == 8
  leaves = kv_lib.cache_leaves(cfg, 32, 32)
  assert leaves["block_0"]["latent"]["cached_latent"].shape == (
      32, 12832, 1, 576)
  assert leaves["block_1"]["latent"]["cached_index"].shape == (32, 12832, 128)
  assert leaves["block_4"]["latent"]["cached_latent"].shape == (
      32, 640, 1, 1088)


# ------------------------------------------------------- the experts' share --


def test_the_shares_add_up_to_the_uncut_layer(both):
  """Every share's routed part plus the shared expert ONCE is what the
  uncut reference gives for the whole layer (model-configs guide, section
  4): three chips hold experts 0-2, 3-5 and 6-7 of the router's 8."""
  uncut = dataclasses.replace(REF_CFG, experts_first=0, n_routed_experts=8)
  ff = ref.init_moe_ff(uncut, jax.random.PRNGKey(7))
  h = jax.random.normal(jax.random.PRNGKey(8), (24, 64), jnp.float32)
  whole = ref.moe(uncut, h, ff, "float32")
  shared = ref.mlp(h, ff["shared"], "float32")
  total = shared
  base = glue.model_config(REF_CFG, F32)
  for first, count in ((0, 3), (3, 3), (6, 2)):
    cfg = dataclasses.replace(base, experts_held=(first, count))
    ex = jax.tree_util.tree_map(lambda a: a[first:first + count],
                                ff["experts"])
    p = {"router_kernel": ff["router"].astype(jnp.float32),
         "e_score_correction_bias": ff["bias"],
         "experts_gate_up": jnp.concatenate(
             [ex["gate"], ex["up"]], -1).astype(jnp.float32),
         "experts_down": ex["down"].astype(jnp.float32),
         "shared": {n: {"kernel": ff["shared"][n].astype(jnp.float32)}
                    for n in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
      y, sown = moe_lib.DroplessMoE(cfg).apply(
          {"params": p}, h[:, None], mutable=["stats"])
    total = total + (y[:, 0] - shared)
    # Two choices a position over 8 experts: the held ones' share of them.
    held = float(sown["stats"]["held_assignments"][0])
    assert 0 < held < 48
  np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                             atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("first,count", [(0, 3), (2, 3), (5, 3)])
def test_an_absent_experts_assignment_is_dead(first, count):
  """``sort_by_expert`` told which experts are held: an assignment below
  or above the range sorts behind the last group and counts in none."""
  chosen = jnp.asarray([[0, 7], [2, 3], [4, 5], [6, 1], [3, 3]], jnp.int32)
  live = jnp.asarray([True, True, True, True, False])
  order, sizes = moe_lib.sort_by_expert(chosen, live, count, first)
  flat = np.asarray(chosen).reshape(-1)
  alive = np.repeat(np.asarray(live), 2)
  want = [int(np.sum(alive & (flat == first + e))) for e in range(count)]
  assert np.asarray(sizes).tolist() == want
  head = np.asarray(order)[:sum(want)]
  assert sorted(flat[head].tolist()) == sorted(
      e for e, a in zip(flat, alive) if a and first <= e < first + count)


def test_glm_and_lfm2_keep_their_trees_and_their_stats():
  """The shared pieces left the other expert decoders as they were: no
  gate, no indexer, every expert held and none told apart, two floats a
  step."""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoe, GlmMoeConfig
  from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
  toy = dict(vocab_size=64, d_model=32, moe_d_ff=16, n_routed_experts=4,
             num_experts_per_tok=2, dtype=jnp.float32,
             param_dtype=jnp.float32)
  glm = GlmMoe(GlmMoeConfig(
      num_layers=2, d_ff=48, num_heads=2, q_lora_rank=16, kv_lora_rank=16,
      qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, **toy))
  lfm = Lfm2Moe(Lfm2MoeConfig(
      layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
      d_ff=48, num_heads=2, num_kv_heads=1, **toy))
  ids = jnp.zeros((1, 8), jnp.int32)
  for model in (glm, lfm):
    variables = model.init(jax.random.PRNGKey(0), ids)
    shell = nn.unbox(variables["params"])
    names = {k.key for path, _ in jax.tree_util.tree_leaves_with_path(shell)
             for k in path if hasattr(k, "key")}
    assert not {"gate_kernel", "index_q", "index_k", "index_w",
                "index_k_norm"} & names
    moe = next(v["moe"] for v in shell.values()
               if isinstance(v, dict) and "moe" in v)
    assert moe["experts_gate_up"].shape[0] == 4
    _, sown = model.apply({"params": shell}, ids, mutable=["stats"])
    assert engine_lib._expert_stats(sown["stats"]).shape == (2,)
  assert set(glm.init(jax.random.PRNGKey(0), ids)["params"]["block_0"][
      "latent"]) == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o"}


# ------------------------------------------------------------------ engine --


def _requests():
  rng = np.random.default_rng(5)
  return [Request(uid=f"r{j}", prompt=rng.integers(0, 256, n).astype(np.int32),
                  max_new_tokens=m)
          for j, (n, m) in enumerate([(3, 6), (19, 9), (30, 5), (11, 12),
                                      (26, 7)])]


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
    lg = ref.logits(ref_cfg, rp, jnp.asarray(toks)[None])[0]
    n = len(r.prompt)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(lg, -1))[n - 1:-1], toks[n:], err_msg=r.uid)


def test_engine_on_mixed_prompts_equals_per_request_reference_decoding(both):
  model, params, rp = both
  eng, out = _serve(model, params)
  assert kv_lib.resolved(eng.lowerings) == dict.fromkeys(
      ("kv_write_impl", "slot_attn_impl", "dsa_index_impl", "moe_gmm_impl"),
      "reference")
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
      ("kv_write_impl", "slot_attn_impl", "dsa_index_impl"), "interpret"),
      moe_gmm_impl="reference")
  for uid, toks in plain.items():
    np.testing.assert_array_equal(np.asarray(out[uid]), np.asarray(toks))
  _teacher_forced(WIDE_CFG, rp, out)


def test_narrow_and_wide_steps_commit_the_reference_lowerings_tokens(
    monkeypatch, wide):
  """One engine whose flat batch is narrower than its ``slots x chunk``
  positions and has a second width (40 and 16 rows of 4 x 16, named here:
  the rule gives so few positions their full width): the attends read and
  write the flat batch where it lies (``tile_attn_out`` ``flat``) on steps
  that take the narrow side of the layers' conditionals and on steps that
  take the wide one, and the engine commits what it commits under the
  reference lowerings, which move rows to ``[slots, chunk]`` and back."""
  model, params, rp = wide
  monkeypatch.setattr(engine_lib, "flat_width", lambda slots, chunk: 40)
  monkeypatch.setattr(engine_lib, "narrow_width", lambda width, slots: 16)
  outs = {}
  for impl, form in (("reference", "slots"), ("interpret", "flat")):
    _backend_takes(monkeypatch, impl)
    stats = ServingStats()
    tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
    try:
      eng, outs[impl] = _serve(model, params, slots=4, chunk=16, stats=stats)
      counters = {name: [ev["args"]["value"] for ev in tracer.events()
                         if ev["ph"] == "C" and ev["name"] == name]
                  for name in ("serving/attn_tile_positions",
                               "serving/flat_positions")}
    finally:
      trace_lib.install(None)
    assert (eng.flat_width, eng.flat_narrow) == (40, 16)
    assert eng.lowerings["slot_attn_impl"] == impl
    assert eng.lowerings["tile_attn_out"] == form
    assert 0 < stats.flat_narrow_steps < stats.steps
    assert eng._step_fn._cache_size() == 1
    # Where the tile kernels run, the chunk positions they work on a step
    # (live tiles of 8, decoding slots 1) beside the live ones.
    tiles, live = counters.values()
    assert len(tiles) == (len(live) if impl == "interpret" else 0)
    assert all(t >= n for t, n in zip(tiles, live))
    assert sum(tiles) > sum(live) or impl == "reference"
  for uid, toks in outs["reference"].items():
    np.testing.assert_array_equal(np.asarray(outs["interpret"][uid]),
                                  np.asarray(toks))
  _teacher_forced(WIDE_CFG, rp, outs["interpret"])


def test_the_window_leaves_do_not_grow_with_the_served_context(both):
  model = both[0]
  short = kv_lib.cache_layout(model.cfg, 3, 4)
  longer = kv_lib.cache_layout(
      dataclasses.replace(model.cfg, max_seq_len=4 * model.cfg.max_seq_len),
      3, 4)
  assert short["window_bytes"] == longer["window_bytes"] == (
      3 * 3 * 8 * 56 * 4)
  assert longer["latent_bytes"] > 3 * short["latent_bytes"]
  assert longer["index_bytes"] > 3 * short["index_bytes"]
  assert (short["latent_leaves"], short["index_leaves"],
          short["window_leaves"], short["kv_leaves"]) == (2, 2, 3, 0)
  assert short["kv_order"] == "positions"
  assert kv_lib.layer_kinds(model.cfg) == (
      SPARSE_LATENT, SPARSE_LATENT, WINDOW_LATENT, WINDOW_LATENT,
      WINDOW_LATENT)
  assert kv_lib.latent_kinds(model.cfg) == (SPARSE_LATENT, WINDOW_LATENT)
  assert kv_lib.has_latent_cache(model.cfg)
  assert not kv_lib.has_recurrent_state(model.cfg)


@pytest.mark.parametrize("most", [1, 4, 513, 2048])
def test_rows_up_to_is_the_sum_it_says(most):
  rng = np.random.default_rng(6)
  resident = rng.integers(0, 3000, 16).astype(np.int32)
  nv = rng.integers(0, 33, 16).astype(np.int32)
  want = sum(min(r + i + 1, most) for r, n in zip(resident, nv)
             for i in range(n))
  assert engine_lib._rows_up_to(resident, nv, most) == want


def test_the_engine_says_what_it_holds_and_counts_what_it_read(both):
  """Metadata, the per-step counters and the stats' summary: what a step
  scored, selected, kept behind its windows and routed to held experts,
  against the same sums taken from the finished requests."""
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
  assert meta["serving/dsa_index_impl"] == {"impl": "reference"}
  assert meta["serving/experts_held"] == {"first": 2, "count": 3,
                                          "published": 8}
  layout = meta["serving/cache_layout"]
  assert layout["window_leaves"] == 3 and layout["index_leaves"] == 2
  total = lambda name: sum(e["args"]["value"] for e in events
                           if e["ph"] == "C" and e["name"] == name)
  # A request of L tokens feeds positions 0 .. L - 2, each once.
  fed = [len(out[r.uid]) - 1 for r in _requests()]
  assert total("serving/selected_rows") == sum(
      min(t + 1, 4) for n in fed for t in range(n))
  assert total("serving/window_rows") == sum(
      min(t + 1, 5) for n in fed for t in range(n))
  # Every row under a slot-step's bound, once a query of the step.
  assert total("serving/index_rows") >= sum(
      t + 1 for n in fed for t in range(n))
  routed = total("serving/routed_positions")
  assert routed == sum(fed)
  held = total("serving/held_assignments")
  assert 0 < held < 4 * 2 * routed and held == int(held)
  summary = stats.summary()
  steps = summary["steps"]
  assert summary["selected_rows_per_step"] * steps == pytest.approx(
      total("serving/selected_rows"))
  assert summary["held_assignments_per_step"] * steps == pytest.approx(held)
  assert summary["index_rows_per_step"] > summary["selected_rows_per_step"]


@pytest.mark.parametrize("feature", [
    dict(paged=True), dict(prefix_cache=True, paged=True),
    dict(drafter=NgramDrafter(k=2)), dict(resilience=True)])
def test_rollback_features_refuse_the_new_kinds_with_one_message_each(
    both, feature):
  model, params, _ = both
  with pytest.raises(ValueError) as e:
    ContinuousBatchingEngine(model, params, num_slots=2, prefill_chunk=4,
                             **feature)
  msg = str(e.value)
  assert ROADMAP_SPARSE_LATENT in msg and ROADMAP_WINDOW_LATENT in msg
  assert ROADMAP_LATENT_CACHE not in msg
  for item in ("R5", "R6", "R10"):
    assert f"ROADMAP item {item}" in msg


def test_a_model_of_one_new_kind_is_refused_for_that_kind_alone(both):
  model = both[0]
  windows = dataclasses.replace(model.cfg, layer_types=(ref.SLIDING,) * 2,
                                first_k_dense=0)
  with pytest.raises(ValueError) as e:
    check_draft_compatible(model.cfg, windows)
  assert ROADMAP_WINDOW_LATENT in str(e.value)
  assert ROADMAP_SPARSE_LATENT not in str(e.value)
