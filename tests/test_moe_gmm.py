"""The dropless expert layer's grouped matmul (kernels/moe_gmm.py).

One algorithm — each sorted row times its own group's matrix — with two
lowerings.  The contract under test: the Pallas kernel (interpreted here,
as ``tests/test_kv_write.py`` runs the write) equals ``ragged_dot`` and a
plain loop over the groups to rounding; a group without rows is skipped
and one with every row takes them all; rows of no group come out zeros
whatever the lowering left there; the visits are enumerated from the
group sizes alone; the dispatch rule declines what the kernel cannot tile;
and (in ``tests/test_kv_write.py``, the one file that describes a chip)
the kernel compiles for a described v5e at the cell's shapes, under a
``highest``-precision context too (PERF.md section 6: Mosaic refuses that
precision for 16-bit operands, so the kernel names its own).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

gmm = importlib.import_module("easyparallellibrary_tpu.kernels.moe_gmm")

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _backend_takes(monkeypatch, impl):
  monkeypatch.setattr(gmm, "_backend_impl", lambda: impl)


def _loop(lhs, rhs, sizes):
  """The plain meaning: a Python loop over the groups, float32."""
  out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
  r = 0
  for e, n in enumerate(sizes):
    out[r:r + n] = (np.asarray(lhs[r:r + n], np.float32)
                    @ np.asarray(rhs[e], np.float32))
    r += n
  return out


def _operands(M, K, N, E, dtype, seed=0):
  r = np.random.RandomState(seed)
  return (jnp.asarray(r.standard_normal((M, K)), dtype),
          jnp.asarray(r.standard_normal((E, K, N)) / np.sqrt(K), dtype))


SIZES = {
    "ragged": [0, 5, 0, 130, 1, 0, 20, 0],
    "empty_groups_first_and_last": [0, 0, 60, 0, 0, 70, 0, 0],
    "one_group_holds_every_row": [0, 0, 0, 300, 0, 0, 0, 0],
    "last_group_holds_every_row": [0, 0, 0, 0, 0, 0, 0, 300],
    "even": [37] * 8,
    "nothing_live": [0] * 8,
    "tile_aligned": [128, 0, 128, 0, 0, 0, 0, 0],
    "one_row_each": [1] * 8,
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_kernel_equals_ragged_dot_and_the_loop(case, dtype):
  """300 rows (not a multiple of the 128-row tile) over 8 groups: the
  kernel, ``ragged_dot`` and the loop agree on the live rows; the rows of
  no group are zeros from both lowerings."""
  M, K, N, E = 300, 128, 256, 8
  lhs, rhs = _operands(M, K, N, E, dtype, seed=len(case))
  sizes = jnp.asarray(SIZES[case], jnp.int32)
  with jax.default_matmul_precision("highest"):
    got = gmm.moe_gmm(lhs, rhs, sizes, impl="interpret")
    ref = gmm.moe_gmm(lhs, rhs, sizes, impl="reference")
  assert got.dtype == lhs.dtype and got.shape == (M, N)
  want = _loop(lhs, rhs, SIZES[case])
  scale = max(np.abs(want).max(), 1.0)
  for name, out in (("kernel", got), ("ragged_dot", ref)):
    out = np.asarray(out, np.float32)
    assert np.abs(out - want).max() <= TOL[dtype] * scale, name
    assert (out[sum(SIZES[case]):] == 0).all(), name


def test_dead_rows_are_zeros_whatever_they_hold():
  """NaN in the rows of no group (a dead position's gathered input) does
  not reach a live row, and comes out as zero."""
  M, K, N, E = 200, 128, 128, 4
  lhs, rhs = _operands(M, K, N, E, jnp.float32, seed=3)
  sizes = [50, 0, 70, 10]
  live = sum(sizes)
  lhs = lhs.at[live:].set(jnp.nan)
  for impl in ("interpret", "reference"):
    out = np.asarray(gmm.moe_gmm(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                                 impl=impl))
    assert np.isfinite(out).all(), impl
    assert (out[live:] == 0).all(), impl
    np.testing.assert_allclose(out[:live], _loop(lhs, rhs, sizes)[:live],
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("sizes,want", [
    ([0, 5, 0, 130, 1], [(1, 0), (3, 0), (3, 1), (4, 1)]),
    ([300, 0, 0, 0, 0], [(0, 0), (0, 1), (0, 2)]),
    ([0, 0, 0, 0, 0], []),
    ([128, 128, 44, 0, 0], [(0, 0), (1, 1), (2, 2)]),
], ids=["ragged", "one_group", "nothing", "aligned"])
def test_visits_skip_empty_groups_and_follow_group_order(sizes, want):
  """A visit is a (group, row tile) pair that share a row: none for an
  empty group, consecutive ones for a group that straddles a tile, and
  the entries beyond the count repeat the last visit (no new block)."""
  num_tiles = 3
  group_of, tile_of, first, count, starts, ends = gmm.visits(
      jnp.asarray(sizes, jnp.int32), num_tiles)
  n = int(count[0])
  got = list(zip(np.asarray(group_of)[:n].tolist(),
                 np.asarray(tile_of)[:n].tolist()))
  assert got == want
  assert len(group_of) == len(sizes) + num_tiles - 1
  if n:
    assert set(zip(np.asarray(group_of)[n:].tolist(),
                   np.asarray(tile_of)[n:].tolist())) <= {want[-1]}
    tiles = np.asarray(tile_of)[:n]
    np.testing.assert_array_equal(
        np.asarray(first)[:n],
        np.concatenate([[1], (tiles[1:] != tiles[:-1]).astype(int)]))
  np.testing.assert_array_equal(np.asarray(ends) - np.asarray(starts), sizes)


@pytest.mark.parametrize("lhs,rhs,dtype,sharded", [
    ((3072, 2048), (64, 2048, 3072), jnp.bfloat16, True),
    ((3072, 2048), (64, 2048, 3072), jnp.float16, False),
    ((3072, 100), (64, 100, 256), jnp.bfloat16, False),
    ((3072, 2048), (64, 2048, 200), jnp.bfloat16, False),
    ((3072, 65536), (4, 65536, 128), jnp.float32, False),
], ids=["on_a_mesh", "float16", "K_not_lanes", "N_not_lanes", "K_too_deep"])
def test_what_the_kernel_declines_takes_the_reference(
    monkeypatch, lhs, rhs, dtype, sharded):
  _backend_takes(monkeypatch, "pallas")
  assert gmm.resolve_moe_gmm_impl(lhs, rhs, dtype,
                                  sharded=sharded) == "reference"


def test_rule_follows_the_backend_and_sizes_the_tile(monkeypatch):
  """The cell's two products take the kernel on a TPU, with 2 MB and 1.5
  MB blocks of an expert's matrix; the CPU takes ``ragged_dot``."""
  cell = (((3072, 2048), (64, 2048, 3072)), ((3072, 1536), (64, 1536, 2048)))
  assert gmm.resolve_moe_gmm_impl(*cell[0], jnp.bfloat16) == "reference"
  _backend_takes(monkeypatch, "pallas")
  for shapes in cell:
    for dtype in (jnp.bfloat16, jnp.float32):
      assert gmm.resolve_moe_gmm_impl(*shapes, dtype) == "pallas"
  assert gmm.tile_n(2048, 3072, jnp.bfloat16) == 512
  assert gmm.tile_n(1536, 2048, jnp.bfloat16) == 512
  assert gmm.tile_n(2048, 3072, jnp.float32) == 256
  assert gmm.tile_n(64, 48, jnp.float32) == 48     # narrower than a lane tile


def test_a_typo_is_refused_and_none_applies_the_rule(monkeypatch):
  lhs, rhs = _operands(64, 128, 128, 2, jnp.float32)
  sizes = jnp.asarray([10, 20], jnp.int32)
  with pytest.raises(ValueError, match="impl must be one of"):
    gmm.moe_gmm(lhs, rhs, sizes, impl="palas")
  _backend_takes(monkeypatch, "interpret")
  a = gmm.moe_gmm(lhs, rhs, sizes)                  # the rule: the kernel
  b = gmm.moe_gmm(lhs, rhs, sizes, impl="reference")
  np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
