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
    assert fa._default_block(1024, d=64) == 256        # the cell's: swept
    assert fa._default_block(4096, d=64) == 512        # resident regime
    assert fa._default_block(16384, d=64) == 1024      # streaming regime
    fa.set_block_want(4096, 64, 2, 2048)
    assert fa._default_block(4096, d=64) == 2048       # tuned override
    assert fa._default_block(4096, d=64, itemsize=4) == 512  # other key
    # Explicit want still wins over the table.
    assert fa._default_block(4096, 256, d=64) == 256
  finally:
    fa._BLOCK_TABLE.pop((4096, 64, 2), None)


# ------------------------------------------- the tiles a head is walked in

# Candidate tiles of the sweep (PERF.md section 6, PR 43): square,
# tq > tk, and one with tq < tk.  At S 1024 tiles of 128 make 64 tiles a
# head, more than ``_UNROLL_TILES``, and are walked by ``fori_loop``; the
# others are unrolled at trace time: both forms of the loops are held
# here, and bit for bit in ``test_long_heads_walk_by_fori_loop``.
TILES = [(128, 128), (256, 256), (512, 512), (512, 256), (256, 128),
         (128, 256)]


def _dense(q, k, v, causal):
  """Plain attention on ``[B, H, S, D]`` in float32 with its logsumexp;
  the mask compares positions counted from 0 on both sides, so the key
  length may differ from the query length."""
  q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
  s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                 precision="highest") / np.sqrt(q.shape[-1])
  if causal:
    live = (jnp.arange(q.shape[2])[:, None] >= jnp.arange(k.shape[2])[None])
    s = jnp.where(live[None, None], s, -1e30)
  lse = jax.nn.logsumexp(s, axis=-1)
  o = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v,
                 precision="highest")
  return o, lse


def _rand(shape, dtype, seed):
  return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _close(got, want, dtype, what):
  got, want = (np.asarray(x, np.float32) for x in (got, want))
  tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
  err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-6)
  assert err <= tol, f"{what}: {err:.3g} of the reference's max"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("D", [64, 128], ids=["scale_folded", "scale_on_s"])
@pytest.mark.parametrize("S", [256, 1024])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tile", TILES, ids=lambda t: f"{t[0]}x{t[1]}")
def test_tiles_match_dense(tile, causal, S, D, dtype):
  """Forward, lse and all three gradients (with a cotangent on lse too)
  of every candidate tile against the dense reference.  D 64 folds the
  softmax scale (0.125) into the ``[tile, D]`` operand, D 128 keeps it on
  the score tile."""
  from easyparallellibrary_tpu.kernels.flash_attention import (
      flash_attention_lse)
  fa = _fa()
  assert fa._scale_folds(1 / np.sqrt(D), dtype) == (D == 64)
  q, k, v, w = (_rand((1, S, 1, D), dtype, seed) for seed in range(4))
  u = _rand((1, S, 1), jnp.float32, 4)

  def run(attend):
    def loss(q, k, v):
      o, l = attend(q, k, v)
      return (jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))
              + jnp.sum(l * u)), (o, l)
    (_, (o, l)), g = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        q, k, v)
    return (o, l) + g

  def dense(q, k, v):
    t = lambda x: x.transpose(0, 2, 1, 3)
    o, l = _dense(t(q), t(k), t(v), causal)
    return t(o), l.transpose(0, 2, 1)

  got = run(lambda q, k, v: flash_attention_lse(
      q, k, v, causal=causal, block_q=tile[0], block_k=tile[1]))
  want = run(dense)
  for name, g, r in zip(("out", "lse", "dq", "dk", "dv"), got, want):
    _close(g, r, dtype, name)


def _fa():
  import importlib
  return importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tile", [(128, 128), (256, 128), (128, 256)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_long_heads_walk_by_fori_loop(tile, causal, monkeypatch):
  """A head of more pairs than ``_UNROLL_PAIRS`` (S 2048 and up) is
  walked by ``fori_loop`` on traced bounds; forced here at S 512, where
  the unrolled walk of the same tiles must give the same bits."""
  fa = _fa()
  q, k, v, do = (_rand((1, 1, 512, 64), jnp.float32, i) for i in range(4))

  def run():
    out, lse8 = fa._fwd(q, k, v, causal, *tile)
    delta = jnp.sum(do * out, axis=-1)
    return (out, lse8) + tuple(fa._bwd_kernels(
        q, k, v, do, lse8, fa._tile8(delta), causal, *tile))

  unrolled = run()
  monkeypatch.setattr(fa, "_UNROLL_PAIRS", 0)
  looped = run()
  for a, b in zip(unrolled, looped):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  (want, want_lse), vjp = jax.vjp(
      lambda q, k, v: _dense(q, k, v, causal), q, k, v)
  np.testing.assert_allclose(looped[0], want, rtol=2e-5, atol=2e-6)
  np.testing.assert_allclose(looped[1][:, :, 0], want_lse, rtol=2e-5,
                             atol=2e-6)
  for g, r in zip(looped[2:], vjp((do, jnp.zeros_like(want_lse)))):
    np.testing.assert_allclose(g, r, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("S", [384, 640, 96])
def test_default_tile_that_does_not_divide_S_is_halved(S):
  """256 divides none of these: the default search halves it (384 and
  640 walk tiles of 128) or takes S itself (96)."""
  fa = _fa()
  assert fa._default_block(S, d=64, itemsize=4) == min(S, 128)
  q, k, v = _qkv(B=1, S=S, H=2, D=64, seed=S)
  out = flash_attention(q, k, v, causal=True)
  np.testing.assert_allclose(out, _full_attention(q, k, v), rtol=2e-5,
                             atol=2e-6)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,Skv,tile", [
    (256, 512, (128, 128)), (512, 256, (128, 128)), (256, 512, (128, 256)),
    (512, 256, (256, 128)), (1024, 256, (128, 128))])
def test_unequal_lengths_as_ring_attention_calls(S, Skv, tile, causal):
  """``_fwd`` / ``_bwd_kernels`` on ``[B, H, S, D]`` with a key length of
  their own and a caller's lse and delta tiles, as the ring's steps call
  them."""
  fa = _fa()
  q, do = (_rand((1, 2, S, 64), jnp.float32, i) for i in (0, 1))
  k, v = (_rand((1, 2, Skv, 64), jnp.float32, i) for i in (2, 3))
  out, lse8 = fa._fwd(q, k, v, causal, *tile)
  (want, want_lse), vjp = jax.vjp(
      lambda q, k, v: _dense(q, k, v, causal), q, k, v)
  np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
  np.testing.assert_allclose(lse8[:, :, 0], want_lse, rtol=2e-5, atol=2e-6)
  delta = jnp.sum(do * out, axis=-1)
  got = fa._bwd_kernels(q, k, v, do, lse8, fa._tile8(delta), causal, *tile)
  for g, r in zip(got, vjp((do, jnp.zeros_like(want_lse)))):
    np.testing.assert_allclose(g, r, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("S,Skv,tq,tk", [
    (1024, 1024, 256, 256), (1024, 1024, 128, 128), (1024, 1024, 512, 512),
    (1024, 1024, 512, 256), (1024, 1024, 256, 128), (1024, 1024, 128, 512),
    (256, 256, 256, 256), (256, 512, 128, 128), (512, 256, 128, 256),
    (768, 768, 256, 128), (64, 64, 16, 32), (48, 96, 16, 8)])
def test_causal_tile_counts_against_live_pairs(S, Skv, tq, tk):
  """The static counter and BOTH views of the kernels' loop bounds (key
  tiles of a row tile: forward and dQ; row tiles of a key tile: dK/dV)
  against a count of live pairs tile by tile."""
  fa = _fa()
  live = np.arange(S)[:, None] >= np.arange(Skv)[None]
  num_q, num_k = S // tq, Skv // tk
  pairs = live.reshape(num_q, tq, num_k, tk).sum(axis=(1, 3))
  kind = np.where(pairs == 0, "skip", np.where(pairs == tq * tk, "full",
                                               "mask"))
  for i in range(num_q):
    full, end = fa._key_tiles(i, tq, tk, num_k, True)
    assert list(kind[i]) == (["full"] * full + ["mask"] * (end - full)
                             + ["skip"] * (num_k - end))
  for j in range(num_k):
    lo, full = fa._row_tiles(j, tq, tk, num_q, True)
    assert list(kind[:, j]) == (["skip"] * lo + ["mask"] * (full - lo)
                                + ["full"] * (num_q - full))
  unmasked, masked, skipped = fa.causal_tile_counts(S, Skv, tq, tk)
  assert (unmasked, masked, skipped) == tuple(
      int((kind == x).sum()) for x in ("full", "mask", "skip"))
  assert (unmasked + masked) * tq * tk >= live.sum()


def test_the_train_cell_computes_at_most_a_quarter_over_the_triangle():
  """`gpt2l-train-zero1-4chip` a chip: S 1024, D 64, bfloat16.  The
  default tile walks 6 tiles with no mask and 4 with it of 16: 655,360
  pairs computed for the triangle's 524,800 (1.249; blocks of 512 made
  it 1.50)."""
  fa = _fa()
  t = fa._default_block(1024, d=64, itemsize=2)
  unmasked, masked, skipped = fa.causal_tile_counts(1024, 1024, t, t)
  assert (t, unmasked, masked, skipped) == (256, 6, 4, 6)
  assert (unmasked + masked) * t * t / (1024 * 1025 // 2) <= 1.25


def test_layers_share_one_trace_of_a_kernel(monkeypatch):
  """The launches are jitted: three layers of one shape trace the forward
  kernel's (unrolled) body as often as one layer does.  Traced a layer,
  the 36 layers of the train cell paid 7 s of set-up for it (PERF.md
  section 6, PR 43)."""
  fa = _fa()
  traced = []
  body = fa._fwd_kernel_resident

  def counting(*refs, **kw):
    traced.append(refs[0].shape)
    return body(*refs, **kw)

  monkeypatch.setattr(fa, "_fwd_kernel_resident", counting)

  def layers(n, S):
    x = _rand((1, S, 1, 16), jnp.float32, S)
    for _ in range(n):
      x = x + flash_attention(x, x, x, causal=True, block_q=8, block_k=8)
    return x

  layers(1, 40)
  once = len(traced)
  layers(3, 56)
  assert once >= 1 and len(traced) == 2 * once, traced
