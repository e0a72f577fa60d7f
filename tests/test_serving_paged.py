"""Paged KV cache + token-flat fused step (ISSUE 7).

The exactness contract under test: the paged engine is a pure
REBATCHING of the same math — greedy token ids are bit-identical per
request to BOTH ``generate(use_cache=True)`` and the contiguous slot
engine (itself quick-pinned to generate), no matter when a request was
admitted, which blocks its K/V landed in, who owned those blocks
before, or whether the block pool ran dry and preempted it mid-flight.
Compile count stays 1 as requests join/leave and block tables reshuffle.
Heavyweight shape sweeps are ``slow``-marked so tier-1 keeps its window.
The CPU engine runs the bit-exact jnp reference path, which these tests
exercise throughout; the Pallas kernel is checked here interpreted,
lowered for the TPU in tests/test_tpu_lowering.py, and compiled against
the reference on the chip by chip_smoke.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.kernels.paged_attention import (
    paged_attention_pallas, paged_attention_reference)
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate
from easyparallellibrary_tpu.serving import (
    BlockAllocator, ContinuousBatchingEngine, DraftModelDrafter, Request,
    allocate_paged_kv_cache, blocks_per_slot, default_num_blocks,
    paged_cache_bytes)
from easyparallellibrary_tpu.testing import chaos

TINY = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)


def _model_and_params(cfg=TINY, seed=0):
  model = GPT(cfg)
  params = model.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, 4), jnp.int32))["params"]
  return model, params


def _prompts(lengths, vocab=64, seed=0):
  r = np.random.RandomState(seed)
  return [r.randint(0, vocab, (n,)).astype(np.int32) for n in lengths]


def _oracle(model, params, prompt, max_new):
  return np.asarray(
      generate(model, params, jnp.asarray(prompt)[None], max_new))[0]


# --------------------------------------------------------------- exactness


@pytest.mark.quick
def test_paged_greedy_exact_staggered_compile_once():
  """Token-flat paged decode is bit-exact vs generate(use_cache=True)
  per request — admissions staggered mid-flight, slots AND blocks reused
  across retirements — with fused-step compile count == 1 throughout
  (joins, leaves and block-table reshuffles are data)."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3, 9, 1, 6, 2))
  max_new = (6, 7, 8, 4, 5, 9)
  eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                 prefill_chunk=4, paged=True,
                                 block_size=4)
  for i in range(3):
    eng.submit(Request(uid=i, prompt=prompts[i],
                       max_new_tokens=max_new[i]))
  out = {}
  for _ in range(2):  # second wave joins a mid-flight batch
    for fin in eng.step():
      out[fin.uid] = fin.tokens
  for i in range(3, len(prompts)):
    eng.submit(Request(uid=i, prompt=prompts[i],
                       max_new_tokens=max_new[i]))
  out.update(eng.run())
  assert eng._step_fn._cache_size() == 1
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(
        out[i], _oracle(model, params, p, max_new[i]), err_msg=f"req {i}")
  # Retirement returned every block (no leaks, no dangling refcounts).
  assert eng.scheduler.kv_blocks_used == 0


@pytest.mark.quick
def test_paged_tp2_staggered_exact_vs_nonpaged_engine():
  """The paged engine on a TP=2 virtual mesh (heads sharded over
  `model`, pools allocated sharded) reproduces the NON-paged engine's
  greedy ids exactly under staggered admission — the contiguous engine
  is itself quick-pinned to generate, so the chain pins paged → slot →
  oracle."""
  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state)
  import optax
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  cfg = GPTConfig(**{**TINY.__dict__, "tensor_parallel": True})
  model = GPT(cfg)
  prompts = _prompts((4, 7, 2, 5), seed=1)

  def init_fn(rng):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(rng, jnp.asarray(prompts[0])[None])["params"],
        tx=optax.sgd(0.1))

  state, _ = create_sharded_train_state(init_fn, mesh,
                                        jax.random.PRNGKey(5))

  def drive(paged: bool, drafter=None):
    eng = ContinuousBatchingEngine(model, state.params, mesh=mesh,
                                   num_slots=2, prefill_chunk=4,
                                   paged=paged, block_size=4,
                                   drafter=drafter)
    for i, p in enumerate(prompts[:2]):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=5))
    out = {}
    for fin in eng.step():       # later submits join mid-flight
      out[fin.uid] = fin.tokens
    for i in range(2, len(prompts)):
      eng.submit(Request(uid=i, prompt=prompts[i], max_new_tokens=5))
    out.update(eng.run())
    assert eng._step_fn._cache_size() == 1
    return out

  from easyparallellibrary_tpu.serving import NgramDrafter
  paged_out, slot_out = drive(True), drive(False)
  # The speculative twin has its own mesh sharding signature (more
  # replicated inputs) — pin the meshed paged+spec combination too.
  spec_out = drive(True, drafter=NgramDrafter(k=2))
  for i in range(len(prompts)):
    np.testing.assert_array_equal(paged_out[i], slot_out[i],
                                  err_msg=f"req {i}")
    np.testing.assert_array_equal(spec_out[i], slot_out[i],
                                  err_msg=f"spec req {i}")


@pytest.mark.quick
def test_block_reuse_after_retirement_no_stale_kv():
  """A retired request's freed blocks are re-issued (lowest-free-first)
  to the next occupant with no stale-KV leakage: a SHORT request served
  after a LONG one reuses the same physical blocks yet matches its
  from-scratch oracle bit-exactly."""
  epl.init()
  model, params = _model_and_params(seed=2)
  long_p, short_p = _prompts((12, 3), seed=3)
  eng = ContinuousBatchingEngine(model, params, num_slots=1,
                                 prefill_chunk=4, paged=True,
                                 block_size=4)
  eng.submit(Request(uid="long", prompt=long_p, max_new_tokens=10))
  eng.step()
  long_blocks = set(eng.scheduler.slot_blocks(0))
  out = eng.run()
  eng.submit(Request(uid="short", prompt=short_p, max_new_tokens=6))
  eng.step()
  short_blocks = set(eng.scheduler.slot_blocks(0))
  out.update(eng.run())
  # The short request's blocks physically overlap the long one's —
  # the no-leakage property is doing real work here.
  assert short_blocks and short_blocks <= long_blocks
  np.testing.assert_array_equal(out["long"],
                                _oracle(model, params, long_p, 10))
  np.testing.assert_array_equal(out["short"],
                                _oracle(model, params, short_p, 6))


@pytest.mark.quick
def test_block_pool_exhaustion_preempts_and_replays_exact():
  """Pool exhaustion pages out the youngest lowest-priority slot via the
  requeue prefix-replay path (reason "preempted") instead of raising;
  both the survivor and the preempted request finish bit-exact, the one
  compiled step is reused, and every block returns to the pool."""
  epl.init()
  model, params = _model_and_params()
  p1, p2 = _prompts((10, 10), seed=7)
  # 9 usable blocks x 4 = 36 rows < 2 requests x 24 rows: must preempt.
  eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                 prefill_chunk=4, paged=True,
                                 block_size=4, num_blocks=10)
  eng.submit(Request(uid="a", prompt=p1, max_new_tokens=14))
  eng.submit(Request(uid="b", prompt=p2, max_new_tokens=14))
  out = eng.run(max_steps=300)
  assert eng.scheduler.preemptions >= 1
  assert eng._step_fn._cache_size() == 1
  for uid, p in (("a", p1), ("b", p2)):
    assert eng.finished[uid].finish_reason == "length"
    np.testing.assert_array_equal(out[uid], _oracle(model, params, p, 14),
                                  err_msg=uid)
  assert eng.scheduler.kv_blocks_used == 0
  assert eng.scheduler.kv_blocks_free == 9


@pytest.mark.slow
def test_paged_speculative_bit_exact_both_drafters():
  """Greedy speculative paged decode keeps the oracle bitstream: drafts
  ride leftover flat-budget positions, verification gathers target rows
  by flat index, and rejection is pure host bookkeeping (no cursors to
  roll back).  Same-params draft model guarantees multi-token accepted
  bursts; the n-gram drafter exercises partial/empty proposals."""
  from easyparallellibrary_tpu.serving import NgramDrafter
  epl.init()
  model, params = _model_and_params(seed=4)
  prompts = _prompts((5, 3, 9), seed=5)
  max_new = (8, 7, 10)
  for drafter in (DraftModelDrafter(model, params, k=3),
                  NgramDrafter(k=3)):
    eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                   prefill_chunk=4, paged=True,
                                   block_size=4, drafter=drafter)
    for i, p in enumerate(prompts):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new[i]))
    out = eng.run()
    assert eng._step_fn._cache_size() == 1
    assert eng._drafter_failures == 0
    for i, p in enumerate(prompts):
      np.testing.assert_array_equal(
          out[i], _oracle(model, params, p, max_new[i]),
          err_msg=f"{type(drafter).__name__} req {i}")


def test_paged_draft_model_longer_max_seq_len_binds_and_stays_exact():
  """A draft model padded LONGER than the target (which
  check_draft_compatible explicitly permits) must bind: the mirror pool
  is addressed through the ENGINE's block tables, so its capacity check
  uses the target's geometry, not the draft's wider one — and greedy
  stays bit-exact regardless of drafter shape."""
  epl.init()
  model, params = _model_and_params(seed=9)
  draft_cfg = GPTConfig(**{**TINY.__dict__, "max_seq_len": 64,
                           "num_layers": 1})
  draft_model = GPT(draft_cfg)
  draft_params = draft_model.init(jax.random.PRNGKey(1),
                                  jnp.zeros((1, 4), jnp.int32))["params"]
  (p,) = _prompts((6,), seed=10)
  eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4, paged=True,
      block_size=4,
      drafter=DraftModelDrafter(draft_model, draft_params, k=2))
  eng.submit(Request(uid="x", prompt=p, max_new_tokens=6))
  out = eng.run()
  assert eng._drafter_failures == 0
  np.testing.assert_array_equal(out["x"], _oracle(model, params, p, 6))


def test_paged_guarded_fault_free_equivalence_and_gauges():
  """Resilience on, no faults: the paged guarded step is bit-identical
  to the unguarded baseline with zero extra compiles, and the block-pool
  gauges flow through ServingStats."""
  epl.init()
  model, params = _model_and_params(seed=6)
  prompts = _prompts((6, 2), seed=8)

  def drive(resilience):
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   prefill_chunk=4, paged=True,
                                   block_size=4, resilience=resilience)
    for i, p in enumerate(prompts):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=7))
    out = eng.run()
    assert eng._step_fn._cache_size() == 1
    return eng, out

  eng_r, out_r = drive(True)
  _, out_b = drive(False)
  for i in range(len(prompts)):
    np.testing.assert_array_equal(out_r[i], out_b[i])
  s = eng_r.stats.summary()
  assert s["kv_blocks_free"] > 0 and s["preemptions"] == 0.0
  assert 0.0 <= s["kv_fragmentation"] <= 1.0


def test_paged_nan_step_retried_in_place_bit_exact():
  """A transient NaN device step on the paged engine: the verdict gates
  the commit, the retry re-feeds identical flat work (positions are
  host-planned — no cursor fetch), the poisoned rows (and the null
  block) are zeroed, and the final stream is bit-identical."""
  epl.init()
  model, params = _model_and_params()
  prompts = _prompts((5, 3))
  eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                 prefill_chunk=4, paged=True,
                                 block_size=4, resilience=True)
  inj = chaos.NaNLogitsInjector(eng, bad_calls=(2,))
  for i, p in enumerate(prompts):
    eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
  out = eng.run()
  assert inj.poisoned == [2]
  assert inj._cache_size() == 1
  assert eng.stats.bad_steps == 1 and eng.stats.step_retries >= 1
  for i, p in enumerate(prompts):
    assert eng.finished[i].finish_reason == "length"
    np.testing.assert_array_equal(out[i], _oracle(model, params, p, 6),
                                  err_msg=f"req {i}")


# ------------------------------------------------------------------- units


def test_block_allocator_freelist_and_refcounts():
  alloc = BlockAllocator(num_blocks=5, block_size=4)
  assert alloc.num_free == 4          # block 0 reserved (null block)
  a, b = alloc.alloc(), alloc.alloc()
  assert (a, b) == (1, 2)             # lowest-free-first, deterministic
  alloc.incref(a)
  alloc.decref(a)
  assert alloc.refcount(a) == 1       # still held: refcount, not free
  alloc.decref(a)
  assert alloc.refcount(a) == 0 and alloc.num_free == 3
  assert alloc.alloc() == 1           # freed block re-issued lowest-first
  with pytest.raises(ValueError, match="double free|not allocated"):
    alloc.decref(4)
  alloc.decref(b)
  # Fragmentation: 2 allocated blocks (8 rows), 5 resident tokens.
  alloc2 = BlockAllocator(num_blocks=5, block_size=4)
  alloc2.alloc(), alloc2.alloc()
  assert alloc2.fragmentation(5) == pytest.approx(1 - 5 / 8)


def test_paged_geometry_validation():
  model, params = _model_and_params()
  # block_size must divide max_seq_len (reduction-length parity with the
  # oracle — the greedy bit-exactness precondition).
  with pytest.raises(ValueError, match="divide max_seq_len"):
    blocks_per_slot(TINY, 5)
  assert blocks_per_slot(TINY, 4) == 8
  assert default_num_blocks(TINY, 3, 4) == 25
  assert paged_cache_bytes(TINY, 25, 4) == 2 * 2 * 25 * 4 * 32 * 4
  with pytest.raises(ValueError, match="one full-length request"):
    allocate_paged_kv_cache(TINY, 4, 8)
  epl.init()
  # token_budget below the effective batch cap could starve decodes.
  with pytest.raises(ValueError, match="token_budget"):
    ContinuousBatchingEngine(model, params, num_slots=4, prefill_chunk=4,
                             paged=True, block_size=4, token_budget=3)


def test_paged_timeline_blocks_in_report():
  """The per-request timeline shows block occupancy: the phase spans
  carry kv_blocks and report.py rolls up each request's peak."""
  from easyparallellibrary_tpu.observability import trace as trace_lib
  from easyparallellibrary_tpu.observability.report import (
      format_report, request_timelines)
  epl.init()
  tracer = trace_lib.Tracer(enabled=True, ring_capacity=8192)
  trace_lib.install(tracer)
  try:
    model, params = _model_and_params()
    (p,) = _prompts((9,))
    eng = ContinuousBatchingEngine(model, params, num_slots=1,
                                   prefill_chunk=4, paged=True,
                                   block_size=4)
    eng.submit(Request(uid="r", prompt=p, max_new_tokens=6))
    eng.run()
    events = tracer.events()
    rows = request_timelines(events)
    (row,) = [r for r in rows if r["uid"] == "r"]
    # 9 prompt + 6 new tokens => ceil(14/4) = 4 peak blocks.
    assert row["kv_blocks_peak"] == 4
    report = format_report(events)
    assert "blk" in report
  finally:
    trace_lib.install(None)


# ------------------------------------------------------- kernel parity


def _parity_case(seed=0, T=6, H=4, hd=16, NB=9, bs=8, MB=4,
                 dtype=jnp.float32):
  r = np.random.RandomState(seed)
  q = jnp.asarray(r.randn(T, H, hd), dtype)
  kp = jnp.asarray(r.randn(NB, bs, H, hd), dtype)
  vp = jnp.asarray(r.randn(NB, bs, H, hd), dtype)
  tables = jnp.asarray(r.randint(0, NB, (T, MB)), jnp.int32)
  positions = jnp.asarray(r.randint(0, MB * bs, (T,)), jnp.int32)
  return q, kp, vp, tables, positions


def test_paged_kernel_parity_interpret_mode():
  """The Pallas kernel in interpreter mode matches the jnp reference on
  CPU — the kernel's logic is exercised everywhere, not only on TPU."""
  args = _parity_case()
  ref = paged_attention_reference(*args)
  ker = paged_attention_pallas(*args, interpret=True)
  np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                             rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------- slow sweeps


@pytest.mark.slow
@pytest.mark.parametrize("block_size,chunk,token_budget",
                         [(2, 3, 7), (8, 4, 16), (16, 5, 9),
                          (32, 4, 23), (4, 1, 5)])
def test_paged_shape_sweep_exact(block_size, chunk, token_budget):
  """Heavyweight sweep: odd chunk widths, one-row blocks-per-slot,
  single-token budgets — every geometry keeps the oracle bitstream."""
  epl.init()
  model, params = _model_and_params(seed=block_size)
  prompts = _prompts((7, 2, 11, 4), seed=chunk)
  max_new = (5, 9, 6, 8)
  eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                 prefill_chunk=chunk, paged=True,
                                 block_size=block_size,
                                 token_budget=token_budget)
  for i, p in enumerate(prompts):
    eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new[i]))
  out = eng.run(max_steps=500)
  assert eng._step_fn._cache_size() == 1
  for i, p in enumerate(prompts):
    np.testing.assert_array_equal(
        out[i], _oracle(model, params, p, max_new[i]), err_msg=f"req {i}")


@pytest.mark.slow
def test_paged_persistent_nan_quarantine_replays_prefix_exact():
  """Two consecutive poisoned steps quarantine the slot: the request
  requeues with its committed prefix, its freed blocks are zeroed before
  reuse, and the chunked-prefill replay reproduces the oracle stream."""
  epl.init()
  model, params = _model_and_params()
  (p,) = _prompts((5,))
  eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                 prefill_chunk=4, paged=True,
                                 block_size=4, resilience=True)
  inj = chaos.NaNLogitsInjector(eng, bad_calls=(2, 3))
  eng.submit(Request(uid="q", prompt=p, max_new_tokens=6))
  out = eng.run()
  assert inj.poisoned == [2, 3]
  assert inj._cache_size() == 1
  assert eng.stats.requeues == 1
  assert eng.finished["q"].finish_reason == "length"
  np.testing.assert_array_equal(out["q"], _oracle(model, params, p, 6))
