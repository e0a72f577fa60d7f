"""The fused serving step's attend over the live rows
(kernels/slot_attention.py).

One algorithm — every slot's chunk against that slot's own cache, causal
at its cursor — with two lowerings.  The contract under test: the Pallas
kernel (interpreted here, as ``tests/test_kv_write.py`` runs the write)
equals the einsum reference to rounding for every valid query, gives
zeros for what it does not compute (idle slots, the invalid tail of a
chunk), lets nothing at or beyond a slot's bound reach its output, the
dispatch rule declines what the kernel cannot tile, and an engine built
on it commits the same greedy tokens as one built on the reference and
compiles once.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate, slot_cache_attend
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.profiler.serving import ServingStats
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving.speculative import (
    DraftModelDrafter, NgramDrafter)

sa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.slot_attention")
kvw = importlib.import_module("easyparallellibrary_tpu.kernels.kv_write")

LC = 1040                      # the cells' leaf: 1024 + a chunk of 16
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


def _backend_takes(monkeypatch, impl):
  """What a test steers: the lowering the backend would take, for the
  attend and for the write beside it."""
  monkeypatch.setattr(sa, "_backend_impl", lambda: impl)
  monkeypatch.setattr(kvw, "_backend_impl", lambda: impl)


def _operands(B, C, H, Hkv, hd, Lc, dtype, seed=0):
  r = np.random.RandomState(seed)
  mk = lambda *shape: r.standard_normal(shape).astype(np.float32)
  return (jnp.asarray(mk(B, C, H, hd), dtype), mk(B, Lc, Hkv, hd),
          mk(B, Lc, Hkv, hd))


def _cursors(C, block):
  """The first row, inside a block, the last row of a block, a block's
  first row, a window straddling two blocks, the last legal window."""
  return [0, 77, block - C, block, block - C // 2 - 1, LC - C]


def _check(q, ck, cv, cur, nv, dtype, block=None, poison=False):
  """Kernel against reference on the rows ``nv`` says are real; zeros
  elsewhere.  ``poison`` plants NaN in every row at or beyond a slot's
  bound (and in the whole of an idle slot) before the kernel reads."""
  B, C = q.shape[:2]
  cur, nv = np.asarray(cur, np.int32), np.asarray(nv, np.int32)
  want = sa.slot_attention_reference(
      q, jnp.asarray(ck, dtype), jnp.asarray(cv, dtype), jnp.asarray(cur))
  if poison:
    ck, cv = ck.copy(), cv.copy()
    for b in range(B):
      bound = cur[b] + nv[b] if nv[b] else 0
      ck[b, bound:] = np.nan
      cv[b, bound:] = np.nan
  got = sa.slot_attention_pallas(
      q, jnp.asarray(ck, dtype), jnp.asarray(cv, dtype), jnp.asarray(cur),
      jnp.asarray(nv), interpret=True, block=block)
  got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
  assert np.isfinite(got).all()
  real = (np.arange(C)[None] < nv[:, None])[:, :, None, None]
  np.testing.assert_allclose(np.where(real, got, 0), np.where(real, want, 0),
                             atol=TOL[dtype], rtol=TOL[dtype])
  assert (np.where(real, 0, got) == 0).all()
  return got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [(4, 4, 64), (4, 4, 128), (20, 1, 128),
                                   (8, 2, 64)],
                         ids=["mha_hd64", "mha_hd128", "one_kv_head",
                              "grouped_hd64"])
@pytest.mark.parametrize("C", [1, 8, 16])
def test_kernel_equals_the_reference_to_rounding(C, heads, dtype):
  H, Hkv, hd = heads
  block = 256
  cur = _cursors(C, block)
  q, ck, cv = _operands(len(cur), C, H, Hkv, hd, LC, dtype, seed=C)
  assert sa.slot_attn_fits((len(cur), LC, Hkv, hd), dtype, C, H)
  _check(q, ck, cv, cur, [C] * len(cur), dtype, block=block)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_rules_own_block_and_no_bound_given(dtype):
  """The block the rule picks for the leaf (512 KiB of K: 1024 of 1040
  rows in bfloat16, so the edge block holds 16), and ``num_valid=None``
  as ``generate()``'s decode calls it: every position real."""
  C, H, hd = 16, 4, 64
  cur = [0, 500, 1008, 1023, LC - C]
  q, ck, cv = _operands(len(cur), C, H, H, hd, LC, dtype, seed=3)
  assert sa.block_positions((len(cur), LC, H, hd), dtype, C, H) == \
      {jnp.float32: 512, jnp.bfloat16: 1024}[dtype]
  got = _check(q, ck, cv, cur, [C] * len(cur), dtype)
  unbounded = sa.slot_attention_pallas(
      q, jnp.asarray(ck, dtype), jnp.asarray(cv, dtype),
      jnp.asarray(cur, jnp.int32), interpret=True)
  np.testing.assert_array_equal(np.asarray(unbounded, np.float32), got)


@pytest.mark.parametrize("heads", [(4, 4, 64), (20, 1, 128)],
                         ids=["mha", "one_kv_head"])
def test_idle_slots_and_partial_chunks_come_out_zeros(heads):
  """``num_valid`` 0 (an idle slot, whatever its stale cursor says) and
  the tail of a partial chunk: zeros, and the live rows beside them
  unmoved — also when every slot before the first live one idles."""
  H, Hkv, hd = heads
  C = 8
  cur = [300, 0, 513, 255, 1000, 64, 900]
  nv = [0, 0, 3, 8, 1, 0, 5]
  q, ck, cv = _operands(len(cur), C, H, Hkv, hd, LC, jnp.float32, seed=5)
  got = _check(q, ck, cv, cur, nv, jnp.float32, block=256)
  assert (got[[0, 1, 5]] == 0).all() and (got[2, 3:] == 0).all()
  assert np.abs(got[3]).min() > 0
  # every slot idle: the grid's one row lands on an idle slot
  assert (_check(q, ck, cv, cur, [0] * len(cur), jnp.float32) == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", [256, None], ids=["block_256", "ruled"])
def test_nan_beyond_the_bound_does_not_reach_the_output(block, dtype):
  """Rows at or beyond ``cursor + num_valid`` hold NaN — in the bound's
  own block, in later blocks, in the leaf's edge block, in idle slots:
  the output is the clean cache's."""
  C, H, hd = 16, 4, 64
  cur = [0, 100, 250, 256, 511, 700, 1024, 40]
  nv = [16, 1, 16, 7, 2, 0, 16, 0]
  q, ck, cv = _operands(len(cur), C, H, H, hd, LC, dtype, seed=7)
  _check(q, ck, cv, cur, nv, dtype, block=block, poison=True)


# ---------------------------------------------------------------- the rule


@pytest.mark.parametrize("shape,dtype,chunk,heads,sharded", [
    ((4, 36, 2, 16), jnp.float32, 4, 2, False),      # a toy leaf
    ((4, 120, 2, 16), jnp.float32, 4, 2, False),     # under one tile
    ((4, 1200, 2, 16), jnp.float32, 160, 2, False),  # chunk over a tile
    ((4, 272, 2, 12), jnp.float32, 16, 2, False),    # hd not whole sublanes
    ((4, 272, 2, 16), jnp.float16, 16, 2, False),    # a dtype not proven
    ((4, 272, 3, 16), jnp.float32, 16, 4, False),    # heads not in groups
    ((4, 272, 64, 256), jnp.float32, 128, 64, False),  # over the VMEM
    ((4, 272, 2, 16), jnp.float32, 16, 2, True),     # leaf spread over chips
], ids=["toy_leaf", "short_leaf", "wide_chunk", "odd_hd", "f16",
        "odd_groups", "vmem", "sharded"])
def test_what_the_kernel_declines_takes_the_reference(
    monkeypatch, shape, dtype, chunk, heads, sharded):
  for impl in ("interpret", "pallas"):
    _backend_takes(monkeypatch, impl)
    assert sa.resolve_slot_attn_impl(shape, dtype, chunk, heads,
                                     sharded) == "reference"


@pytest.mark.parametrize("shape,chunk,heads,block", [
    ((96, 1040, 16, 64), 16, 16, 256),     # the GPT-2 medium cells
    ((128, 8200, 1, 128), 8, 20, 2048),    # the hybrid cell
    ((8, 1024, 16, 64), 1, 16, 256),       # generate()'s decode
], ids=["gpt2m_cells", "hybrid_cell", "decode_1"])
def test_rule_follows_the_backend_and_sizes_the_block(
    monkeypatch, shape, chunk, heads, block):
  dt = jnp.bfloat16
  assert sa.resolve_slot_attn_impl(shape, dt, chunk, heads) == \
      "reference"                      # this backend is the CPU
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert sa.resolve_slot_attn_impl(shape, dt, chunk, heads) == "pallas"
  assert sa.resolve_slot_attn_impl(shape, dt, chunk, heads,
                                   sharded=True) == "reference"
  assert sa.block_positions(shape, dt, chunk, heads) == block


def test_a_typo_is_refused_and_a_declined_shape_runs_the_reference(
    monkeypatch):
  q, ck, cv = _operands(3, 4, 2, 2, 16, 36, jnp.float32)
  cur = jnp.asarray([0, 7, 32], jnp.int32)
  with pytest.raises(ValueError, match="impl must be one of"):
    sa.slot_attention(q, jnp.asarray(ck), jnp.asarray(cv), cur,
                      impl="mosaic")
  # A backend that takes the kernel, a leaf shorter than a tile, impl
  # unresolved as ``generate()`` calls it: the reference, bit for bit.
  _backend_takes(monkeypatch, "interpret")
  got = sa.slot_attention(q, jnp.asarray(ck), jnp.asarray(cv), cur)
  want = sa.slot_attention_reference(q, jnp.asarray(ck), jnp.asarray(cv),
                                     cur)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_slot_cache_attend_writes_then_reads_under_either_lowering():
  """The whole of ``slot_cache_attend``: the chunk's own K/V are in the
  cache before the attend reads it, under both pairs of lowerings, and
  the leaves come back bit-identical."""
  C, H, hd = 8, 2, 16
  q, ck, cv = _operands(4, C, H, H, hd, 264, jnp.float32, seed=11)
  k = _operands(4, C, H, H, hd, 264, jnp.float32, seed=12)[0]
  v = _operands(4, C, H, H, hd, 264, jnp.float32, seed=13)[0]
  cur = jnp.asarray([0, 120, 128, 250], jnp.int32)
  nv = jnp.asarray([8, 8, 3, 8], jnp.int32)
  run = lambda impl: slot_cache_attend(
      q, k, v, jnp.asarray(ck), jnp.asarray(cv), cur,
      jnp.float32, write_impl=impl, attn_impl=impl, num_valid=nv)
  out_k, ck_k, cv_k = run("interpret")
  out_r, ck_r, cv_r = run("reference")
  np.testing.assert_array_equal(np.asarray(ck_k), np.asarray(ck_r))
  np.testing.assert_array_equal(np.asarray(cv_k), np.asarray(cv_r))
  real = (np.arange(C)[None] < np.asarray(nv)[:, None])[:, :, None, None]
  np.testing.assert_allclose(np.where(real, out_k, 0),
                             np.where(real, out_r, 0), atol=2e-6)


# ------------------------------------------------------------------ engine

SERVE = GPTConfig(vocab_size=64, num_layers=2, num_heads=2, d_model=32,
                  d_ff=64, max_seq_len=256, dtype=jnp.float32)
PROMPTS = (118, 3, 121, 40, 126)


def _model():
  model = GPT(SERVE)
  params = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]
  r = np.random.RandomState(0)
  prompts = [r.randint(0, 64, (n,)).astype(np.int32) for n in PROMPTS]
  return model, params, prompts


def _serve(monkeypatch, impl, drafter=None):
  """Five greedy requests over three slots: prefill chunks and decode
  tokens share steps, decode cursors walk through a tile boundary one
  row at a time, a slot idles while the others finish."""
  _backend_takes(monkeypatch, impl)
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    model, params, prompts = _model()
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
def test_engine_commits_the_same_greedy_tokens_under_either_attend(
    monkeypatch, drafter):
  epl.init()
  eng_k, out_k, events = _serve(monkeypatch, "interpret", DRAFTERS[drafter])
  eng_r, out_r, _ = _serve(monkeypatch, "reference", DRAFTERS[drafter])
  # Which attend each run timed is on record, not inferred.
  assert eng_k.slot_attn_impl == "interpret"
  assert eng_r.slot_attn_impl == "reference"
  facts = [e["args"] for e in events
           if e["ph"] == "M" and e["name"] == "serving/slot_attn_impl"]
  assert facts == [{"impl": "interpret"}]
  assert eng_k._capture_context()["serving"]["slot_attn_impl"] == \
      "interpret"
  assert sorted(out_k) == sorted(out_r) == list(range(len(PROMPTS)))
  for uid in out_k:
    np.testing.assert_array_equal(out_k[uid], out_r[uid])
  # Each step compiled once under its lowering.
  assert eng_k._step_fn._cache_size() == eng_r._step_fn._cache_size() == 1


def test_greedy_idle_greedy_compiles_once_and_equals_generate(monkeypatch):
  """Requests, a drained engine whose every slot idles, requests again:
  one compile, and the kernel-built engine still equals the one-request
  oracle (whose decode takes the kernel too, ``C = 1``, no bound)."""
  epl.init()
  _backend_takes(monkeypatch, "interpret")
  model, params, prompts = _model()
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


def test_engine_on_a_mesh_of_chips_takes_the_reference(monkeypatch):
  _backend_takes(monkeypatch, "interpret")
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  assert kv_lib.slot_attn_impl(SERVE, 3, 8, mesh) == "reference"
  assert kv_lib.slot_attn_impl(SERVE, 3, 8, None) == "interpret"


# ------------------------------------------------------- the one-leaf form
# A layer whose cache is ONE tensor (models/glm_moe.py: absorbed multi-head
# latent attention): ``cached_v=None``, the values are the keys' leading
# ``v_width`` columns, and the scores' scale is the caller's.


def _check_one_leaf(H, hd, vw, C, dtype, block=None, poison=True, Lc=LC):
  cur = np.asarray([0, 77, 256 - C, 256, 256 - C // 2 - 1, Lc - C], np.int32)
  nv = np.asarray([C, C, 1, max(C // 2, 1), 0, C], np.int32)
  B = len(cur)
  r = np.random.RandomState(hd + C)
  q = jnp.asarray(r.standard_normal((B, C, H, hd)) / np.sqrt(hd), dtype)
  leaf = r.standard_normal((B, Lc, 1, hd)).astype(np.float32)
  scale = 0.37
  want = sa.slot_attention_reference(
      q, jnp.asarray(leaf, dtype), None, jnp.asarray(cur), v_width=vw,
      scale=scale)
  dirty = leaf.copy()
  if poison:
    for b in range(B):
      dirty[b, cur[b] + nv[b] if nv[b] else 0:] = np.nan
  got = sa.slot_attention_pallas(
      q, jnp.asarray(dirty, dtype), None, jnp.asarray(cur), jnp.asarray(nv),
      interpret=True, block=block, v_width=vw, scale=scale)
  assert got.shape == (B, C, H, vw) == want.shape
  got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
  assert np.isfinite(got).all()
  real = (np.arange(C)[None] < nv[:, None])[:, :, None, None]
  np.testing.assert_allclose(np.where(real, got, 0), np.where(real, want, 0),
                             atol=TOL[dtype], rtol=TOL[dtype])
  assert (np.where(real, 0, got) == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("H,hd,vw,C", [
    (20, 576, 512, 8), (4, 48, 32, 8), (4, 64, 16, 1), (2, 64, 64, 16)],
    ids=["latent_576_values_512", "values_32_of_48", "decode",
         "values_all_of_the_keys"])
def test_one_leaf_equals_the_reference_and_reads_no_nan(H, hd, vw, C, dtype):
  """``v_width < hd`` (and ``== hd``), hd 576 with 20 heads as query rows
  of one head, a one-token decode; NaN in every row at or beyond a bound
  (it would reach the output through K or through the values taken from
  it) stays unread; idle slots and dead positions are zeros."""
  _check_one_leaf(H, hd, vw, C, dtype, block=256,
                  Lc=520 if hd == 576 else LC)


def test_one_leaf_with_the_rules_own_block():
  """The block the rule picks for the latent leaf: 256 rows of 576
  bfloat16 values (288 KiB, within the 512 KiB a K block may take)."""
  assert sa.block_positions((96, 4104, 1, 576), jnp.bfloat16, 8, 20) == 256
  _check_one_leaf(4, 64, 32, 8, jnp.bfloat16, block=None)


def test_one_leaf_call_is_refused_half_said():
  q, ck, _ = _operands(2, 8, 4, 1, 64, LC, jnp.float32)
  cur = jnp.zeros((2,), jnp.int32)
  with pytest.raises(ValueError, match="one-leaf attend"):
    sa.slot_attention(q, jnp.asarray(ck), None, cur, impl="reference")
  with pytest.raises(ValueError, match="one-leaf attend"):
    sa.slot_attention(q, jnp.asarray(ck), jnp.asarray(ck), cur,
                      impl="reference", v_width=32)


@pytest.mark.parametrize("backend,sharded,want", [
    ("pallas", False, "pallas"), ("pallas", True, "reference"),
    ("reference", False, "reference")],
    ids=["tpu", "tpu_on_a_mesh", "cpu"])
def test_rule_takes_the_latent_leaf_on_a_tpu(monkeypatch, backend, sharded,
                                             want):
  _backend_takes(monkeypatch, backend)
  for dtype in (jnp.bfloat16, jnp.float32):
    assert sa.resolve_slot_attn_impl((96, 4104, 1, 576), dtype, 8, 20,
                                     sharded=sharded) == want
