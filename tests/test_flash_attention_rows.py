"""The resident flash kernels over operands kept in ROWS, ``[B, S, H x D]``
(kernels/flash_attention.py, PR 49): the heads that share a 128-lane tile
a grid step, each head's products contracted over the whole tile with the
other heads' lanes zeroed.  Interpret mode on the CPU; the same code
compiles for the chip (tests/test_kv_write.py, chip_smoke.py)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.flash_attention")

F32, BF16 = jnp.float32, jnp.bfloat16

# (D, H, S, causal, dtype): pairs at D 64, one head a tile at D 128, four
# at D 32; the cell's 20 heads and its S 1024 once each, at D 64
# (interpreted, a grid step is slow).
CASES = [
    (64, 2, 256, True, F32), (64, 2, 256, False, BF16),
    (64, 20, 256, True, BF16), (64, 2, 1024, True, BF16),
    (64, 4, 256, False, F32),
    (128, 2, 256, True, BF16), (128, 2, 256, False, F32),
    (32, 4, 256, True, F32), (32, 8, 256, False, BF16),
]


def _case_id(case):
  D, H, S, causal, dtype = case
  return (f"D{D}-H{H}-S{S}-{'causal' if causal else 'full'}-"
          f"{jnp.dtype(dtype).name}")


def _rand(shape, dtype, seed):
  return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _dense(q, k, v, causal):
  """Plain attention over ``[B, S, H, D]`` in float32."""
  q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                 precision="highest") / np.sqrt(q.shape[-1])
  if causal:
    S = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
  return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                    precision="highest")


def _fwd_bwd(attend, q, k, v, w):
  """out, dQ, dK, dV of ``attend`` under the cotangent ``w``."""
  out, vjp = jax.vjp(attend, q, k, v)
  return (out,) + vjp(w.astype(out.dtype))


def _forms(D, H, S, causal, dtype):
  """``{form: attend over [B, S, H, D]}``: the kernels in rows, in rows
  from ONE fused ``[B, S, 3 x H x D]`` operand, head-major, and dense."""
  tile = fa._default_block(S, d=D, itemsize=jnp.dtype(dtype).itemsize)
  flat = lambda x: x.reshape(x.shape[0], S, H * D)
  heads = lambda x: x.reshape(x.shape[0], S, H, D)
  t = lambda x: x.transpose(0, 2, 1, 3)
  return {
      "rows": lambda q, k, v: heads(fa._flash_rows(
          flat(q), flat(k), flat(v), D, causal, tile, tile)),
      "qkv": lambda q, k, v: heads(fa._flash_qkv(
          jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), D, causal,
          tile, tile)),
      "heads": lambda q, k, v: t(fa._flash(t(q), t(k), t(v), causal, tile,
                                           tile)),
      "dense": functools.partial(_dense, causal=causal),
  }


@functools.lru_cache(maxsize=None)
def _results(case, form):
  D, H, S, causal, dtype = case
  q, k, v, w = (_rand((1, S, H, D), dtype, seed) for seed in range(4))
  if form == "dense":
    q, k, v, w = (x.astype(jnp.float32) for x in (q, k, v, w))
  got = _fwd_bwd(_forms(*case)[form], q, k, v, w)
  return tuple(np.asarray(x, np.float32) for x in got)


def _gap(got, want):
  return np.abs(got - want).max() / max(np.abs(want).max(), 1e-6)


@pytest.mark.parametrize("form", ["rows", "qkv"])
@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_rows_match_the_dense_reference_and_the_head_major_kernels(case,
                                                                  form):
  """out, dQ, dK, dV of the rows form against the dense float32 reference
  and against the head-major kernels on the same values: the zeroed lanes
  add nothing to a float32 sum, so the two differ by the order of the
  MXU's sums at most."""
  D, H, S, causal, dtype = case
  assert fa.flash_layout(S, H, D, jnp.dtype(dtype).itemsize,
                         fused=True) == "rows"
  got = _results(case, form)
  dense, heads = _results(case, "dense"), _results(case, "heads")
  tol = 2e-2 if dtype == BF16 else 2e-5
  for name, g, d, h in zip(("out", "dq", "dk", "dv"), got, dense, heads):
    assert np.isfinite(g).all(), name
    assert _gap(g, d) <= tol, f"{name} against dense: {_gap(g, d):.3g}"
    # the head-major kernels' own distance from dense bounds this one
    assert _gap(g, h) <= max(_gap(h, d), 1e-6) * 1.5 + 1e-6, (
        f"{name} against head-major: {_gap(g, h):.3g}, head-major against "
        f"dense {_gap(h, d):.3g}")


@pytest.mark.parametrize("case", [c for c in CASES if c[2] == 256],
                         ids=_case_id)
def test_one_fused_operand_gives_what_three_operands_give(case):
  """q, k and v read as column blocks of one array are the same blocks:
  bit for bit the three-operand form's out and gradients."""
  for a, b in zip(_results(case, "qkv"), _results(case, "rows")):
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("causal", [True, False])
def test_the_entry_takes_rows_where_the_rule_says_and_is_differentiable(
    causal):
  """``flash_attention`` on ``[B, S, H, D]`` and ``flash_attention_qkv``
  on the fused projection agree with each other and with dense, under jit
  and grad, at a shape the rule lays out in rows."""
  B, S, H, D = 2, 128, 4, 64
  q, k, v, w = (_rand((B, S, H, D), F32, seed) for seed in range(4))
  flat = lambda x: x.reshape(B, S, H * D)

  def loss_entry(q, k, v):
    return jnp.sum(fa.flash_attention(q, k, v, causal=causal) * w)

  def loss_qkv(q, k, v):
    qkv = jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1)
    return jnp.sum(fa.flash_attention_qkv(qkv, H, causal=causal)
                   .reshape(B, S, H, D) * w)

  def loss_dense(q, k, v):
    return jnp.sum(_dense(q, k, v, causal) * w)

  want = jax.jit(jax.value_and_grad(loss_dense, (0, 1, 2)))(q, k, v)
  for loss in (loss_entry, loss_qkv):
    got = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
      np.testing.assert_allclose(g, r, rtol=5e-4, atol=2e-5)


def test_rows_under_fori_loop_give_the_unrolled_bits(monkeypatch):
  """A head of more pairs than ``_UNROLL_PAIRS`` walks its tiles by
  ``fori_loop``; the heads of a tile are still a Python loop around it."""
  case = (64, 2, 256, True, F32)
  q, k, v, w = (_rand((1, 256, 2, 64), F32, seed) for seed in range(4))
  unrolled = _fwd_bwd(_forms(*case)["rows"], q, k, v, w)
  monkeypatch.setattr(fa, "_UNROLL_PAIRS", 0)
  jax.clear_caches()
  looped = _fwd_bwd(_forms(*case)["rows"], q, k, v, w)
  for a, b in zip(unrolled, looped):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layers_share_one_trace_of_the_rows_kernels(monkeypatch):
  """As for the head-major launches (PR 43): the rows launches are behind
  the same jitted entries with everything static passed in, so three
  layers trace a kernel's unrolled body as often as one does."""
  traced = []
  body = fa._fwd_kernel_resident

  def counting(*refs, **kw):
    traced.append(refs[0].shape)
    return body(*refs, **kw)

  monkeypatch.setattr(fa, "_fwd_kernel_resident", counting)

  def layers(n, S):
    x = _rand((1, S, 3 * 128), F32, S)
    y = x[..., :128]
    for _ in range(n):
      y = y + fa.flash_attention_qkv(x, 2, causal=True)
    return y

  # lengths no other test of the file traces: the entries are cached
  layers(1, 384)
  once = len(traced)
  layers(3, 640)
  assert once >= 1 and len(traced) == 2 * once, traced
  assert all(len(shape) == 3 and shape[-1] == 128 for shape in traced)
