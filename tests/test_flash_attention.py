"""Pallas flash attention tests (interpreter mode on CPU; same code runs
compiled on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easyparallellibrary_tpu.kernels import flash_attention


def _full_attention(q, k, v, causal=True):
  B, S, H, D = q.shape
  scale = 1.0 / np.sqrt(D)
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
  if causal:
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    scores = jnp.where(mask[None, None], scores, -1e30)
  probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
  return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _qkv(B=2, S=128, H=2, D=32, seed=0):
  r = np.random.RandomState(seed)
  mk = lambda: jnp.asarray(r.randn(B, S, H, D), jnp.float32)
  return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_full(causal):
  q, k, v = _qkv()
  out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
  ref = _full_attention(q, k, v, causal=causal)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def test_flash_multiblock():
  q, k, v = _qkv(S=256, seed=1)
  out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128)
  ref = _full_attention(q, k, v, causal=True)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match(causal):
  q, k, v = _qkv(S=64, seed=2)

  def loss_flash(q, k, v):
    return jnp.mean(flash_attention(q, k, v, causal=causal,
                                    block_q=32, block_k=32) ** 2)

  def loss_full(q, k, v):
    return jnp.mean(_full_attention(q, k, v, causal=causal) ** 2)

  g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
  g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
  for a, b in zip(g1, g2):
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_streaming_path_matches_full(causal, monkeypatch):
  """Force the long-sequence streaming kernels (grid-streamed KV with
  VMEM scratch accumulators) at test size and check against full
  attention — the resident/streaming dispatch must be invisible."""
  import importlib
  fa_mod = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  monkeypatch.setattr(fa_mod, "_RESIDENT_MAX_BYTES", 1)
  q, k, v = _qkv(S=256, seed=4)
  out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
  ref = _full_attention(q, k, v, causal=causal)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_streaming_grads_match(causal, monkeypatch):
  import importlib
  fa_mod = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  monkeypatch.setattr(fa_mod, "_RESIDENT_MAX_BYTES", 1)
  q, k, v = _qkv(S=128, seed=5)

  def loss_flash(q, k, v):
    return jnp.mean(flash_attention(q, k, v, causal=causal,
                                    block_q=32, block_k=32) ** 2)

  def loss_full(q, k, v):
    return jnp.mean(_full_attention(q, k, v, causal=causal) ** 2)

  g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
  g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
  for a, b in zip(g1, g2):
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


def test_flash_streaming_uneven_blocks(monkeypatch):
  """Streaming path with block_q != block_k exercises the causal
  index-map clamps on both grids."""
  import importlib
  fa_mod = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  monkeypatch.setattr(fa_mod, "_RESIDENT_MAX_BYTES", 1)
  q, k, v = _qkv(S=256, seed=6)

  def loss(attn):
    return jax.grad(lambda a, b, c: jnp.mean(attn(a, b, c) ** 2),
                    argnums=(0, 1, 2))(q, k, v)

  g1 = loss(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                            block_q=32, block_k=64))
  g2 = loss(lambda a, b, c: _full_attention(a, b, c, causal=True))
  for a, b in zip(g1, g2):
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


def test_flash_small_seq_single_block():
  q, k, v = _qkv(S=16, seed=3)
  out = flash_attention(q, k, v, causal=True)
  ref = _full_attention(q, k, v, causal=True)
  np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def test_flash_indivisible_raises():
  q, k, v = _qkv(S=96)
  with pytest.raises(ValueError):
    flash_attention(q, k, v, block_q=64, block_k=64)


def test_gpt_with_pallas_flash_matches_xla():
  import easyparallellibrary_tpu as epl
  from easyparallellibrary_tpu.models import GPT, GPTConfig

  epl.init()
  base = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
              d_ff=64, max_seq_len=32, dtype=jnp.float32)
  flash_model = GPT(GPTConfig(**base, attn_impl="pallas_flash"))
  xla_model = GPT(GPTConfig(**base, attn_impl="xla"))
  ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)),
                    jnp.int32)
  params = flash_model.init(jax.random.PRNGKey(0), ids)["params"]
  out_flash = flash_model.apply({"params": params}, ids)
  out_xla = xla_model.apply({"params": params}, ids)
  np.testing.assert_allclose(out_flash, out_xla, rtol=2e-4, atol=2e-5)


def _ref_with_lse(q, k, v, causal=True):
  B, S, H, D = q.shape
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(D)
  if causal:
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    s = jnp.where(mask[None, None], s, -1e30)
  lse = jax.nn.logsumexp(s, axis=-1)                        # [B, H, S]
  p = jnp.exp(s - lse[..., None])
  o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
  return o, lse.transpose(0, 2, 1)                          # [B, S, H]


@pytest.mark.parametrize("causal", [True, False])
def test_flash_lse_matches_full(causal):
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_attention_lse)
  q, k, v = _qkv(S=64, seed=7)
  o1, l1 = flash_attention_lse(q, k, v, causal=causal)
  o2, l2 = _ref_with_lse(q, k, v, causal=causal)
  np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-6)
  np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-6)


def test_flash_lse_cotangent_grads():
  """The lse output is differentiable: its cotangent folds into the
  kernel's delta term (ds = p*(dp - delta + dlse)); this is what the
  ring-attention merge relies on."""
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_attention_lse)
  q, k, v = _qkv(S=32, D=16, seed=9)

  def loss_flash(q, k, v):
    o, l = flash_attention_lse(q, k, v, causal=True)
    return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))

  def loss_ref(q, k, v):
    o, l = _ref_with_lse(q, k, v, causal=True)
    return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))

  g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
  g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
  for a, b in zip(g1, g2):
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_unknown_attn_impl_raises():
  import easyparallellibrary_tpu as epl
  from easyparallellibrary_tpu.models import GPT, GPTConfig
  epl.init()
  model = GPT(GPTConfig(vocab_size=64, num_layers=1, num_heads=2,
                        d_model=16, d_ff=32, max_seq_len=16,
                        attn_impl="flash"))  # typo for pallas_flash
  ids = jnp.zeros((1, 16), jnp.int32)
  with pytest.raises(ValueError, match="attn_impl"):
    model.init(jax.random.PRNGKey(0), ids)


def test_block_autotune_table_overrides_heuristic():
  """VERDICT r3 item 6 infrastructure: _default_block consults the
  autotuned (S, d, itemsize) table (to be written by an autotune run
  on hardware) and keeps the 512/1024 heuristic for unswept shapes."""
  import importlib
  fa = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  try:
    assert fa._default_block(4096, d=64) == 512        # resident regime
    assert fa._default_block(16384, d=64) == 1024      # streaming regime
    fa.set_block_want(4096, 64, 2, 2048)
    assert fa._default_block(4096, d=64) == 2048       # tuned override
    assert fa._default_block(4096, d=64, itemsize=4) == 512  # other key
    # Explicit want still wins over the table.
    assert fa._default_block(4096, 256, d=64) == 256
  finally:
    fa._BLOCK_TABLE.pop((4096, 64, 2), None)
