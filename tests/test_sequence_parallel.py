"""Sequence/context parallelism tests: ring attention + Ulysses vs full
attention (new subsystem — no reference analog; SURVEY §5.7)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.sequence import ring_attention, ulysses_attention


def _full_attention(q, k, v, causal=True):
  B, S, H, D = q.shape
  scale = 1.0 / np.sqrt(D)
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
  if causal:
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    scores = jnp.where(mask[None, None], scores, -1e30)
  probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
  return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _qkv(B=2, S=32, H=4, D=8, seed=0):
  r = np.random.RandomState(seed)
  mk = lambda: jnp.asarray(r.randn(B, S, H, D), jnp.float32)
  return mk(), mk(), mk()


def _seq_mesh(n=4):
  env = epl.init(epl.Config({"sequence.parallelism": "ring",
                             "sequence.axis_size": n}))
  return epl.current_plan().build_mesh()


@pytest.mark.quick
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(causal):
  mesh = _seq_mesh(4)
  q, k, v = _qkv()
  out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=causal))(
      q, k, v)
  ref = _full_attention(q, k, v, causal=causal)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_ring_grads_match_full():
  mesh = _seq_mesh(4)
  q, k, v = _qkv(seed=3)

  def loss_ring(q, k, v):
    return jnp.mean(ring_attention(q, k, v, causal=True) ** 2)

  def loss_full(q, k, v):
    return jnp.mean(_full_attention(q, k, v, causal=True) ** 2)

  g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
  g2 = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
  for a, b in zip(g1, g2):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_ring_explicit_blocks_off_mesh():
  epl.init()  # no seq axis; force 4 blocks — pure blockwise attention
  q, k, v = _qkv(seed=5)
  out = ring_attention(q, k, v, causal=True, num_blocks=4)
  ref = _full_attention(q, k, v, causal=True)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def test_ring_indivisible_raises():
  epl.init()
  q, k, v = _qkv(S=30)
  with pytest.raises(ValueError):
    ring_attention(q, k, v, num_blocks=4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(causal):
  mesh = _seq_mesh(4)
  q, k, v = _qkv()
  out = jax.jit(lambda a, b, c: ulysses_attention(a, b, c, causal=causal))(
      q, k, v)
  ref = _full_attention(q, k, v, causal=causal)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def test_ulysses_head_divisibility():
  mesh = _seq_mesh(4)
  q, k, v = _qkv(H=6)  # 6 heads, seq axis 4 -> invalid
  with pytest.raises(ValueError):
    ulysses_attention(q, k, v)


@pytest.mark.slow
def test_gpt_with_ring_attention_matches_xla():
  from easyparallellibrary_tpu.models import GPT, GPTConfig
  env = epl.init(epl.Config({"sequence.parallelism": "ring",
                             "sequence.axis_size": 2}))
  mesh = epl.current_plan().build_mesh()
  base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
              d_ff=64, max_seq_len=16, dtype=jnp.float32, seq_parallel=True)
  ring_model = GPT(GPTConfig(**base, attn_impl="ring"))
  xla_model = GPT(GPTConfig(**base, attn_impl="xla"))
  ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 16)),
                    jnp.int32)
  params = ring_model.init(jax.random.PRNGKey(0), ids)["params"]
  out_ring = jax.jit(lambda p: ring_model.apply({"params": p}, ids))(params)
  out_xla = jax.jit(lambda p: xla_model.apply({"params": p}, ids))(params)
  np.testing.assert_allclose(out_ring, out_xla, rtol=2e-4, atol=2e-5)


def test_seq_sharded_batch_runs_on_seq_mesh():
  """End-to-end: activations actually sharded over the seq axis."""
  mesh = _seq_mesh(4)
  from jax.sharding import NamedSharding, PartitionSpec as P
  q, k, v = _qkv(B=2, S=32)
  qs = jax.device_put(q, NamedSharding(mesh, P("data", "seq", None, None)))
  ks = jax.device_put(k, NamedSharding(mesh, P("data", "seq", None, None)))
  vs = jax.device_put(v, NamedSharding(mesh, P("data", "seq", None, None)))
  out = jax.jit(lambda a, b, c: ring_attention(a, b, c))(qs, ks, vs)
  ref = _full_attention(q, k, v)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_seq_and_tensor_parallel_compose():
  """GPT on a seq2 x model2 x data2 mesh with ring attention + TP."""
  from easyparallellibrary_tpu.models import GPT, GPTConfig
  from easyparallellibrary_tpu.models.gpt import gpt_loss
  import optax
  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state, make_train_step, parallelize)

  env = epl.init(epl.Config({"sequence.parallelism": "ring",
                             "sequence.axis_size": 2}))
  with epl.split(2):
    pass
  mesh = epl.current_plan().build_mesh()
  sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
  assert (sizes["seq"], sizes["model"], sizes["data"]) == (2, 2, 2)

  cfg = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                  d_ff=64, max_seq_len=16, dtype=jnp.float32,
                  tensor_parallel=True, seq_parallel=True, attn_impl="ring")
  model = GPT(cfg)
  ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 17)),
                    jnp.int32)
  tx = optax.adam(1e-2)

  def init_fn(rng):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(rng, ids[:, :-1])["params"], tx=tx)

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0))
  step = parallelize(
      make_train_step(lambda p, b, r: gpt_loss(model, p, b, r)),
      mesh, shardings)
  losses = []
  for _ in range(5):
    state, m = step(state, {"ids": ids}, jax.random.PRNGKey(1))
    losses.append(float(m["loss"]))
  assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_ring_block_size_config_finer_blocks():
  env = epl.init(epl.Config({"sequence.parallelism": "ring",
                             "sequence.axis_size": 2,
                             "sequence.block_size": 4}))
  epl.current_plan().build_mesh()
  q, k, v = _qkv(S=32, seed=7)   # 32/4 = 8 blocks (multiple of axis 2)
  out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=True))(
      q, k, v)
  ref = _full_attention(q, k, v, causal=True)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_ring_default_uses_flash_shard_map(monkeypatch):
  """With an active seq axis and no block-size override, ring dispatches
  to the shard_map + flash-kernel path (the design point)."""
  import importlib
  ra_mod = importlib.import_module(
      "easyparallellibrary_tpu.sequence.ring_attention")
  mesh = _seq_mesh(4)
  called = {}
  orig = ra_mod._ring_flash

  def spy(q, k, v, causal):
    called["flash"] = True
    return orig(q, k, v, causal)

  monkeypatch.setattr(ra_mod, "_ring_flash", spy)
  q, k, v = _qkv(seed=11)
  ra_mod.ring_attention(q, k, v, causal=True)
  assert called.get("flash")


@pytest.mark.slow
@pytest.mark.parametrize("causal", [True, False])
def test_ring_einsum_impl_matches_flash(causal):
  """The two ring implementations (global-array einsum vs shard_map +
  flash kernel with recommunicating backward) agree on values AND
  gradients."""
  def run(impl):
    epl.init(epl.Config({"sequence.parallelism": "ring",
                         "sequence.axis_size": 4,
                         "sequence.ring_impl": impl}))
    epl.current_plan().build_mesh()
    q, k, v = _qkv(seed=13)

    def loss(q, k, v):
      return jnp.mean(ring_attention(q, k, v, causal=causal) ** 2)

    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=causal))(
        q, k, v)
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return out, g

  out_f, g_f = run("flash")
  out_e, g_e = run("einsum")
  np.testing.assert_allclose(out_f, out_e, rtol=2e-5, atol=2e-6)
  for a, b in zip(g_f, g_e):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_ring_flash_indivisible_seq_raises():
  _seq_mesh(4)
  q, k, v = _qkv(S=30)
  with pytest.raises(ValueError):
    ring_attention(q, k, v, causal=True)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_einsum_impl_matches_flash(causal):
  """Ulysses' two head-sharded attention implementations (pure GSPMD
  einsum vs shard_map + flash kernel) agree on values and gradients."""
  def run(impl):
    epl.init(epl.Config({"sequence.parallelism": "ulysses",
                         "sequence.axis_size": 4,
                         "sequence.ulysses_impl": impl}))
    epl.current_plan().build_mesh()
    q, k, v = _qkv(seed=17)

    def loss(q, k, v):
      return jnp.mean(ulysses_attention(q, k, v, causal=causal) ** 2)

    out = jax.jit(
        lambda a, b, c: ulysses_attention(a, b, c, causal=causal))(q, k, v)
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return out, g

  out_f, g_f = run("flash")
  out_e, g_e = run("einsum")
  np.testing.assert_allclose(out_f, out_e, rtol=2e-5, atol=2e-6)
  for a, b in zip(g_f, g_e):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n", [2, 4])
def test_zigzag_ring_matches_full(n):
  """Zigzag causal layout: values match full attention exactly (the
  layout exchange + balanced half-block schedule is numerics-neutral)."""
  epl.init(epl.Config({"sequence.parallelism": "ring",
                       "sequence.axis_size": n,
                       "sequence.ring_layout": "zigzag"}))
  epl.current_plan().build_mesh()
  q, k, v = _qkv(S=32, seed=21)
  out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=True))(
      q, k, v)
  ref = _full_attention(q, k, v, causal=True)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_zigzag_ring_grads_match_full():
  epl.init(epl.Config({"sequence.parallelism": "ring",
                       "sequence.axis_size": 4,
                       "sequence.ring_layout": "zigzag"}))
  epl.current_plan().build_mesh()
  q, k, v = _qkv(S=32, seed=23)

  def loss_ring(q, k, v):
    return jnp.mean(ring_attention(q, k, v, causal=True) ** 2)

  def loss_full(q, k, v):
    return jnp.mean(_full_attention(q, k, v, causal=True) ** 2)

  g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
  g2 = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
  for a, b in zip(g1, g2):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_zigzag_noncausal_falls_back_to_contiguous():
  """Zigzag is causal-only; non-causal rings use the contiguous path
  (and still match full attention)."""
  epl.init(epl.Config({"sequence.parallelism": "ring",
                       "sequence.axis_size": 4,
                       "sequence.ring_layout": "zigzag"}))
  epl.current_plan().build_mesh()
  q, k, v = _qkv(S=32, seed=25)
  out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=False))(
      q, k, v)
  ref = _full_attention(q, k, v, causal=False)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_unblockable_lengths_fall_back_to_einsum():
  """Sequence lengths with no power-of-two block divisor (e.g. 1030 =
  2*5*103 per device) must not raise or truncate: ring and Ulysses fall
  back to their einsum formulations, which have no blocking constraint."""
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_blockable)
  assert not flash_blockable(515, d=8) and not flash_blockable(1030, d=8)
  assert flash_blockable(512, d=8) and flash_blockable(96, d=8)

  epl.init(epl.Config({"sequence.parallelism": "ring",
                       "sequence.axis_size": 2,
                       "sequence.ring_layout": "zigzag"}))
  epl.current_plan().build_mesh()
  # S=2060 -> per-device 1030 (even halves of 515, unblockable).
  q, k, v = _qkv(S=2060, H=2, D=8, seed=27)
  out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=True))(
      q, k, v)
  ref = _full_attention(q, k, v, causal=True)
  np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                             rtol=2e-5, atol=2e-6)

  out_u = jax.jit(lambda a, b, c: ulysses_attention(a, b, c, causal=True))(
      q, k, v)
  np.testing.assert_allclose(np.asarray(out_u), np.asarray(ref),
                             rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_dense_ring_matches_full_attention_both_layouts():
  """`sequence.ring_impl="dense"` (plain-XLA blocks — the pallas-free
  fallback and the compiled measurement path for the layout benchmarks)
  matches full attention, fwd and grad, under both causal layouts.
  ring_layout DEFAULTS to zigzag."""
  for layout in ("contiguous", "zigzag"):
    epl.init(epl.Config({"sequence.parallelism": "ring",
                         "sequence.axis_size": 8,
                         "sequence.ring_impl": "dense",
                         "sequence.ring_layout": layout}))
    epl.current_plan().build_mesh()
    B, S, H, D = 1, 128, 4, 16
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(r.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(r.randn(B, S, H, D), jnp.float32)

    def full(q):
      s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
      mask = jnp.tril(jnp.ones((S, S), bool))
      s = jnp.where(mask[None, None], s, -1e30)
      return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.jit(lambda q: ring_attention(q, k, v, causal=True))(q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full(q)),
                               rtol=1e-4, atol=1e-5)
    g1 = jax.jit(jax.grad(
        lambda q: jnp.sum(ring_attention(q, k, v, causal=True) ** 2)))(q)
    g2 = jax.jit(jax.grad(lambda q: jnp.sum(full(q) ** 2)))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-4)


def test_ring_layout_default_is_zigzag():
  assert epl.Config().sequence.ring_layout == "zigzag"
