"""The fused serving step's attend over the live rows
(kernels/slot_attention.py).

One algorithm — every slot's chunk against that slot's own cache, causal
at its cursor — with two lowerings.  The contract under test: the Pallas
kernel (interpreted here, as ``tests/test_kv_write.py`` runs the write)
equals the einsum reference to rounding for every valid query, gives
zeros for what it does not compute (idle slots, the invalid tail of a
chunk), lets nothing at or beyond a slot's bound reach its output, the
dispatch rule declines what the kernel cannot tile (an engine built on it
is tests/test_slot_attention_engine.py's: a file of its own, so that the
two halves run on two workers).  Every case runs in both ORDERS a leaf is kept in
(serving/kv_cache.py, order note): ``positions`` ``[B, Lc, H_kv, hd]`` and
``rows`` ``[B, Lc, H_kv x hd]``.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easyparallellibrary_tpu.models.slot_core import slot_cache_attend

sa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.slot_attention")
kvw = importlib.import_module("easyparallellibrary_tpu.kernels.kv_write")

LC = 1040                      # the cells' leaf: 1024 + a chunk of 16
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}
ORDERS = ("positions", "rows")
in_both_orders = pytest.mark.parametrize("order", ORDERS)


def _leaf(x, order):
  """A ``[B, Lc, H_kv, hd]`` leaf in ``order``: heads folded into the
  minor dimension for ``rows``."""
  return x.reshape(x.shape[:2] + (-1,)) if order == "rows" else x


def _shape(B, Lc, Hkv, hd, order):
  return (B, Lc, Hkv * hd) if order == "rows" else (B, Lc, Hkv, hd)


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


def _check(q, ck, cv, cur, nv, dtype, block=None, poison=False,
           order="positions"):
  """Kernel against reference on the rows ``nv`` says are real; zeros
  elsewhere.  ``poison`` plants NaN in every row at or beyond a slot's
  bound (and in the whole of an idle slot) before the kernel reads.  The
  reference reads the leaf in ``order`` too: its two forms must agree."""
  B, C = q.shape[:2]
  cur, nv = np.asarray(cur, np.int32), np.asarray(nv, np.int32)
  want = sa.slot_attention_reference(
      q, jnp.asarray(_leaf(ck, order), dtype),
      jnp.asarray(_leaf(cv, order), dtype), jnp.asarray(cur))
  if order == "rows":
    np.testing.assert_array_equal(
        np.asarray(want, np.float32), np.asarray(sa.slot_attention_reference(
            q, jnp.asarray(ck, dtype), jnp.asarray(cv, dtype),
            jnp.asarray(cur)), np.float32))
  if poison:
    ck, cv = ck.copy(), cv.copy()
    for b in range(B):
      bound = cur[b] + nv[b] if nv[b] else 0
      ck[b, bound:] = np.nan
      cv[b, bound:] = np.nan
  got = sa.slot_attention_pallas(
      q, jnp.asarray(_leaf(ck, order), dtype),
      jnp.asarray(_leaf(cv, order), dtype), jnp.asarray(cur),
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
@in_both_orders
def test_kernel_equals_the_reference_to_rounding(order, C, heads, dtype):
  """Heads of 64 (two to a lane tile in rows: the stacked-query form)
  and of 128 (a head is its own lanes), one K/V head under 20 query
  heads (the hybrid) and groups of four."""
  H, Hkv, hd = heads
  block = 256
  cur = _cursors(C, block)
  q, ck, cv = _operands(len(cur), C, H, Hkv, hd, LC, dtype, seed=C)
  assert sa.slot_attn_fits(_shape(len(cur), LC, Hkv, hd, order), dtype, C,
                           H, hd)
  _check(q, ck, cv, cur, [C] * len(cur), dtype, block=block, order=order)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@in_both_orders
def test_the_rules_own_block_and_no_bound_given(order, dtype):
  """The block the rule picks for the leaf (1024 of 1040 rows, so a full
  slot's tail holds 16), and ``num_valid=None`` as ``generate()``'s
  decode calls it: every position real."""
  C, H, hd = 16, 4, 64
  cur = [0, 500, 1008, 1023, LC - C]
  q, ck, cv = _operands(len(cur), C, H, H, hd, LC, dtype, seed=3)
  assert sa.block_positions(_shape(len(cur), LC, H, hd, order), dtype, C,
                            H, hd) == \
      {jnp.float32: 1024, jnp.bfloat16: 1024}[dtype]
  got = _check(q, ck, cv, cur, [C] * len(cur), dtype, order=order)
  unbounded = sa.slot_attention_pallas(
      q, jnp.asarray(_leaf(ck, order), dtype),
      jnp.asarray(_leaf(cv, order), dtype),
      jnp.asarray(cur, jnp.int32), interpret=True)
  np.testing.assert_array_equal(np.asarray(unbounded, np.float32), got)


@pytest.mark.parametrize("heads", [(4, 4, 64), (20, 1, 128)],
                         ids=["mha", "one_kv_head"])
@in_both_orders
def test_idle_slots_and_partial_chunks_come_out_zeros(order, heads):
  """``num_valid`` 0 (an idle slot, whatever its stale cursor says) and
  the tail of a partial chunk: zeros, and the live rows beside them
  unmoved — also when every slot before the first live one idles."""
  H, Hkv, hd = heads
  C = 8
  cur = [300, 0, 513, 255, 1000, 64, 900]
  nv = [0, 0, 3, 8, 1, 0, 5]
  q, ck, cv = _operands(len(cur), C, H, Hkv, hd, LC, jnp.float32, seed=5)
  got = _check(q, ck, cv, cur, nv, jnp.float32, block=256, order=order)
  assert (got[[0, 1, 5]] == 0).all() and (got[2, 3:] == 0).all()
  assert np.abs(got[3]).min() > 0
  # every slot idle: the grid's one row lands on an idle slot
  assert (_check(q, ck, cv, cur, [0] * len(cur), jnp.float32,
                 order=order) == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", [256, None], ids=["block_256", "ruled"])
@in_both_orders
def test_nan_beyond_the_bound_does_not_reach_the_output(order, block, dtype):
  """Rows at or beyond ``cursor + num_valid`` hold NaN — in the bound's
  own block, in later blocks, in the leaf's edge block, in idle slots:
  the output is the clean cache's."""
  C, H, hd = 16, 4, 64
  cur = [0, 100, 250, 256, 511, 700, 1024, 40]
  nv = [16, 1, 16, 7, 2, 0, 16, 0]
  q, ck, cv = _operands(len(cur), C, H, H, hd, LC, dtype, seed=7)
  _check(q, ck, cv, cur, nv, dtype, block=block, poison=True, order=order)


# ---------------------------------------------------------------- the walk


def _pieces_by_loop(bound, block, granule, length):
  """``live_pieces`` as a loop: a slot's whole blocks, then its tail."""
  out = []
  for b, n in enumerate(bound):
    covered = min(-(-n // granule) * granule, length)
    out += [(b, k * block, block) for k in range(covered // block)]
    if covered % block:
      out.append((b, covered // block * block, covered % block))
  return out


@pytest.mark.parametrize("granule,length", [(16, LC), (128, 1152), (16, 1032)],
                         ids=["rows", "positions", "rows_odd_length"])
@pytest.mark.parametrize("bound", [
    [0, 1, 16, 64, 255, 256, 257, 1032],
    [0, 0, 300, 1025],
    [300, 1025, 0, 0],
    [129, 0, 0, 513, 0, 77],
    [0, 0, 0],
    [1032] * 4,
], ids=["each_edge", "idle_first", "idle_last", "idle_between", "all_idle",
        "all_full"])
def test_piece_list_is_the_loops(bound, granule, length):
  """Pieces in slot order, a slot's whole blocks before its tail, the rows
  covered each bound up to the granule (and no further than the slot's
  length: a leaf in rows of no whole number of granules), nothing for an
  idle slot; the count a value (one piece of no rows when every slot
  idles), the lists' length a shape."""
  block = 256
  slot, start, rows, count = map(np.asarray, sa.live_pieces(
      jnp.asarray(bound, jnp.int32), length, block, granule))
  want = _pieces_by_loop(bound, block, granule, length)
  assert count.shape == (1,) and count[0] == max(len(want), 1)
  assert slot.shape == start.shape == rows.shape == (
      len(bound) * -(-length // block),)
  got = list(zip(slot, start, rows))[:len(want)]
  assert [tuple(map(int, g)) for g in got] == want
  assert (rows[len(want):] == 0).all() and (start % block == 0).all()
  covered = np.zeros(len(bound), np.int64)
  np.add.at(covered, slot, rows)
  np.testing.assert_array_equal(
      covered, np.minimum(-(-np.asarray(bound) // granule) * granule, length))
  # whole blocks first: within a slot the sizes never grow
  for b in range(len(bound)):
    mine = rows[:len(want)][slot[:len(want)] == b]
    assert (np.diff(mine) <= 0).all() and (mine[:-1] == block).all()
  if not want:
    assert rows[0] == 0 and start[0] == 0


def _mixed_bounds(C, block, granule, Lc):
  """``(cursors, num_valid)``: bounds of 0 (idle), 1, a granule, one under
  a block, a block, one over, one inside a tail's last granule, the whole
  leaf; idle slots first, between and last."""
  bound = [0, 1, granule, block - 1, 0, block, block + 1,
           2 * block + granule + 3, Lc, 0]
  nv = [0 if n == 0 else min(n, C, 1 + i % C) for i, n in enumerate(bound)]
  nv[-2] = C
  cur = [n - v for n, v in zip(bound, nv)]
  return cur, nv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("form", ["rows_hd64", "rows_hd128", "rows_odd_length",
                                  "positions", "one_leaf"])
def test_the_walk_reads_under_each_bound_and_nothing_beyond(form, dtype):
  """Bounds that end in every part of a block, NaN from each bound on (so
  in the rest of the bound's granule, which the tail's copy fetches, and in
  what the buffer holds beyond the tail): the reference's output on the
  clean leaf, in the rows form at both head sizes (and over a leaf of no
  whole number of 16-row granules, whose last slot is full: the hybrid
  cell's 8200), in positions, and over one leaf whose values are its keys'
  leading columns."""
  C, block = 8, 256
  Lc = 1032 if form == "rows_odd_length" else LC
  if form == "one_leaf":
    H, hd, vw = 4, 64, 32
    shape = (10, Lc, 1, hd)
  else:
    H, hd = (4, 64) if form in ("rows_hd64", "positions") else (4, 128)
    vw = None
    shape = _shape(10, Lc, H, hd, "positions" if form == "positions"
                   else "rows")
  granule, length = sa.walk_geometry(shape, dtype)
  assert (granule, length) == (
      (128, 1152) if len(shape) == 4 else
      ({jnp.float32: 8, jnp.bfloat16: 16}[dtype], Lc))
  cur, nv = _mixed_bounds(C, block, granule, Lc)
  if form == "one_leaf":
    r = np.random.RandomState(11)
    q = jnp.asarray(r.standard_normal((10, C, H, hd)) / np.sqrt(hd), dtype)
    leaf = r.standard_normal(shape).astype(np.float32)
    want = sa.slot_attention_reference(
        q, jnp.asarray(leaf, dtype), None, jnp.asarray(cur, jnp.int32),
        v_width=vw, scale=0.37)
    for b in range(10):
      leaf[b, cur[b] + nv[b] if nv[b] else 0:] = np.nan
    got = sa.slot_attention_pallas(
        q, jnp.asarray(leaf, dtype), None, jnp.asarray(cur, jnp.int32),
        jnp.asarray(nv, jnp.int32), interpret=True, block=block, v_width=vw,
        scale=0.37)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    real = (np.arange(C)[None] < np.asarray(nv)[:, None])[:, :, None, None]
    assert np.isfinite(got).all() and (np.where(real, 0, got) == 0).all()
    np.testing.assert_allclose(np.where(real, got, 0),
                               np.where(real, want, 0), atol=TOL[dtype],
                               rtol=TOL[dtype])
    return
  q, ck, cv = _operands(10, C, H, H, hd, Lc, dtype, seed=9)
  _check(q, ck, cv, cur, nv, dtype, block=block, poison=True,
         order="positions" if form == "positions" else "rows")


# ---------------------------------------------------------------- the rule


@pytest.mark.parametrize("shape,dtype,chunk,heads,sharded,hd", [
    ((4, 36, 2, 16), jnp.float32, 4, 2, False, None),      # a toy leaf
    ((4, 120, 2, 16), jnp.float32, 4, 2, False, None),     # under one tile
    ((4, 1200, 2, 16), jnp.float32, 160, 2, False, None),  # chunk over a tile
    ((4, 272, 2, 12), jnp.float32, 16, 2, False, None),    # hd not whole
                                                           # sublanes
    ((4, 272, 2, 16), jnp.float16, 16, 2, False, None),    # a dtype not proven
    ((4, 272, 3, 16), jnp.float32, 16, 4, False, None),    # heads not in groups
    ((4, 272, 64, 256), jnp.float32, 128, 64, False, None),  # over the VMEM
    ((4, 272, 2, 16), jnp.float32, 16, 2, True, None),     # leaf spread over
                                                           # chips
    # kept in rows
    ((4, 120, 128), jnp.float32, 4, 2, False, 64),       # under 128 rows
    ((4, 1200, 128), jnp.float32, 160, 2, False, 64),    # chunk over 128
    ((4, 272, 384), jnp.float32, 16, 4, False, 96),      # hd no part of a tile
    ((4, 272, 384), jnp.float32, 16, 2, False, 192),     # nor whole tiles
    ((4, 272, 256), jnp.float16, 16, 4, False, 64),      # a dtype not proven
    ((4, 272, 384), jnp.float32, 16, 4, False, 128),     # heads not in groups
    ((4, 272, 16384), jnp.float32, 128, 128, False, 128),  # over the VMEM
    ((4, 272, 128), jnp.float32, 16, 2, True, 64),       # leaf spread over chips
    ((4, 268, 128), jnp.float32, 16, 2, False, 64),      # rows in no 8-row tiles
], ids=["toy_leaf", "short_leaf", "wide_chunk", "odd_hd", "f16",
        "odd_groups", "vmem", "sharded", "rows_short_leaf", "rows_wide_chunk",
        "rows_hd_96", "rows_hd_192", "rows_f16", "rows_odd_groups",
        "rows_vmem", "rows_sharded", "rows_odd_tiles"])
def test_what_the_kernel_declines_takes_the_reference(
    monkeypatch, shape, dtype, chunk, heads, sharded, hd):
  for impl in ("interpret", "pallas"):
    _backend_takes(monkeypatch, impl)
    assert sa.resolve_slot_attn_impl(shape, dtype, chunk, heads,
                                     sharded, head_dim=hd) == "reference"


@pytest.mark.parametrize("shape,chunk,heads,block", [
    ((96, 1040, 16, 64), 16, 16, 512),     # the GPT-2 medium cells
    ((128, 8200, 1, 128), 8, 20, 1024),    # the hybrid cell
    ((8, 1024, 16, 64), 1, 16, 512),       # generate()'s decode
    ((48, 16416, 4, 128), 32, 28, 1024),   # SmallThinker's full layers
    ((128, 4112, 8, 64), 16, 32, 1024),    # LFM2's attention layers
], ids=["gpt2m_cells", "hybrid_cell", "decode_1", "smallthinker_cell",
        "lfm2_cell"])
@in_both_orders
def test_rule_follows_the_backend_and_sizes_the_block(
    monkeypatch, order, shape, chunk, heads, block):
  """The cells' leaves as they were kept (positions) and as they are
  (rows: the same bytes a block, so the same blocks)."""
  dt = jnp.bfloat16
  hd = shape[3]
  shape = _shape(*shape, order)
  rule = lambda **kw: sa.resolve_slot_attn_impl(shape, dt, chunk, heads,
                                                head_dim=hd, **kw)
  assert rule() == "reference"         # this backend is the CPU
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert rule() == "pallas"
  assert rule(sharded=True) == "reference"
  assert sa.block_positions(shape, dt, chunk, heads, hd) == block


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


@in_both_orders
def test_slot_cache_attend_writes_then_reads_under_either_lowering(order):
  """The whole of ``slot_cache_attend``: the chunk's own K/V are in the
  cache before the attend reads it, under both pairs of lowerings, and
  the leaves come back bit-identical (every slot is fed: an idle slot's
  window is the rows write's to leave, tests/test_kv_write.py)."""
  C, H, hd = 8, 2, (64 if order == "rows" else 16)
  q, ck, cv = _operands(4, C, H, H, hd, 264, jnp.float32, seed=11)
  k = _operands(4, C, H, H, hd, 264, jnp.float32, seed=12)[0]
  v = _operands(4, C, H, H, hd, 264, jnp.float32, seed=13)[0]
  ck, cv = _leaf(ck, order), _leaf(cv, order)
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
  """The block the rule picks for the latent leaf: 1024 rows of 576
  bfloat16 values (1.125 MiB, within the 1.5 MiB a K block may take)."""
  assert sa.block_positions((96, 4104, 1, 576), jnp.bfloat16, 8, 20) == 1024
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


# ------------------------------------- a plain one-leaf attend on the tile grid --
#
# Where ``plain_tile_form`` holds, a latent leaf with no window and no
# selection is served by the tile grid the selected and the windowed forms
# run on: queries read from the step's flat batch where they lie, the
# result written to the same rows, live tiles alone, the decoding slots in
# a launch of their own, both under the first grid's name.


def _flat_case(nv, cursors, dtype, C=16, pad=9, seed=8):
  """Operands of one plain attend from a flat batch: ``len(nv)`` slots of 8
  heads of 48 on values of 32 over leaves of 264 rows, each slot's first
  ``nv[b]`` positions packed in slot order into ``sum(nv) + pad`` rows;
  NaN in every leaf row at or beyond a slot's bound.  Returns ``(flat,
  starts, dirty leaf, cursors, nv, want at the live rows)``."""
  rng = np.random.default_rng(seed)
  B, H, W, r, L = len(nv), 8, 48, 32, 264
  nv, cur = np.asarray(nv, np.int32), np.asarray(cursors, np.int32)
  q = jnp.asarray(rng.normal(size=(B, C, H, W)), dtype)
  leaf = rng.normal(size=(B, L, 1, W)).astype(np.float32)
  want = np.asarray(sa.slot_attention_reference(
      q, jnp.asarray(leaf, dtype), None, jnp.asarray(cur), v_width=r,
      scale=0.2), np.float32)
  for b in range(B):
    leaf[b, cur[b] + nv[b] if nv[b] else 0:] = np.nan
  live = np.arange(C)[None] < nv[:, None]
  flat = np.zeros((nv.sum() + pad, H, W), np.float32)
  flat[:nv.sum()] = np.asarray(q, np.float32)[live]
  return (jnp.asarray(flat, dtype), jnp.asarray(np.cumsum(nv) - nv, jnp.int32),
          jnp.asarray(leaf, dtype), jnp.asarray(cur), jnp.asarray(nv),
          want[live])


PLAIN_CASES = {
    # whole and partial chunks, an idle slot, decodes beside them
    "mixed": ([16, 3, 0, 1, 9, 1], [0, 125, 240, 200, 3, 248], 9),
    # the decoding slots' launch alone has work
    "only-decodes": ([1, 1, 0, 1], [0, 125, 40, 248], 9),
    # the other launch alone has work
    "no-decode": ([16, 3, 0, 5], [0, 125, 40, 200], 9),
    # neither has: each still visits one tile and must write nothing
    "idle": ([0, 0, 0, 0], [0, 125, 40, 200], 9),
    "last-slot-alone": ([0, 0, 0, 16], [0, 125, 40, 248], 9),
    # the last slot's one tile starts within 8 rows of the batch's end: it
    # is read from T - 8 on and worked a shift further down (_tile_shift)
    "tile-near-the-end": ([1, 7, 8, 9, 16, 0, 5],
                          [200, 0, 125, 40, 3, 77, 230], 2),
    # a decode in the batch's last row
    "decode-last": ([9, 1], [200, 0], 0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(PLAIN_CASES))
def test_plain_tile_form_equals_the_reference_flat_in_and_out(case, dtype):
  """``slot_attn`` on the tile grid (interpreted) against the einsums over
  every row: each live position's result at the row its query lies in,
  zeros at the rows no live position owns, nothing at or beyond a bound
  read (NaN planted there)."""
  nv, cursors, pad = PLAIN_CASES[case]
  flat, starts, leaf, cur, nv, want = _flat_case(nv, cursors, dtype, pad=pad)
  total = int(nv.sum())
  if case == "tile-near-the-end":
    T = flat.shape[0]
    assert int(starts[-1]) > T - 8 and int(starts[-1]) + 5 < T
  got = sa.slot_attention(flat, leaf, None, cur, nv, impl="interpret",
                          v_width=32, scale=0.2, starts=starts, chunk=16)
  assert got.shape == flat.shape[:2] + (32,) and got.dtype == dtype
  got = np.asarray(got, np.float32)
  assert np.isfinite(got).all()
  np.testing.assert_allclose(got[:total], want, atol=TOL[dtype],
                             rtol=TOL[dtype])
  assert (got[total:] == 0).all()


@pytest.mark.parametrize("block", [128, None], ids=["three-blocks", "ruled"])
def test_plain_tile_form_is_the_first_grid_in_slot_order(block):
  """Called with ``[B, C, H, W]`` (every position of every slot) the same
  launches give what the first grid gives: dead positions zeros."""
  flat, starts, leaf, cur, nv, _ = _flat_case(
      [16, 3, 0, 1, 9, 1], [0, 125, 240, 200, 3, 248], jnp.float32)
  rng = np.random.default_rng(1)
  q = jnp.asarray(rng.normal(size=(6, 16, 8, 48)), jnp.float32)
  first = sa.slot_attention_pallas(q, leaf, None, cur, nv, interpret=True,
                                   block=128, v_width=32, scale=0.2)
  tiled = sa.slot_attention_tiled_pallas(q, leaf, cur, nv, interpret=True,
                                         block=block, v_width=32, scale=0.2)
  assert tiled.shape == first.shape
  np.testing.assert_allclose(np.asarray(tiled), np.asarray(first), atol=2e-6,
                             rtol=2e-6)


def test_the_plain_launches_keep_the_first_grids_name_and_flat_operands():
  """Two launches (the slots that feed several positions, then the decoding
  ones) named ``slot_attn``, each handed the aliased ``[T, H, v]`` output,
  the rows' positions, ``q`` ``[T, H, W]`` and the leaf position-minor: no
  score operand, no ``[slots, chunk]``-ordered array."""
  T, B, C, H, W, r, L = 40, 4, 16, 8, 48, 32, 264
  closed = jax.make_jaxpr(lambda *a: sa.slot_attention_tiled_pallas(
      *a, interpret=True, starts=jnp.arange(B, dtype=jnp.int32) * 9, chunk=C,
      v_width=r, scale=0.2))(
          jnp.zeros((T, H, W)), jnp.zeros((B, L, 1, W)),
          jnp.zeros((B,), jnp.int32), jnp.full((B,), 9, jnp.int32))
  (inner,) = [e for e in closed.jaxpr.eqns if e.primitive.name in (
      "pjit", "jit")]
  calls = [e for e in inner.params["jaxpr"].jaxpr.eqns
           if e.primitive.name == "pallas_call"]
  for call, tp in zip(calls, (8, 1), strict=True):
    assert call.params["name"] == sa.SLOT_ATTN
    assert [tuple(v.aval.shape) for v in call.invars[-4:]] == [
        (T, H, r), (tp * H, 1), (T, H, W), (B, 1, W, L)]
    assert [tuple(v.aval.shape) for v in call.outvars] == [(T, H, r)]


def test_the_flat_batch_is_the_tile_kernels_alone():
  flat, starts, leaf, cur, nv, _ = _flat_case([9, 1], [200, 0], jnp.float32)
  with pytest.raises(ValueError, match="plain_tile_form"):
    sa.slot_attention(flat, leaf, None, cur, nv, impl="reference",
                      v_width=32, scale=0.2, starts=starts, chunk=16)
  with pytest.raises(ValueError, match="plain_tile_form"):
    sa.slot_attention(flat, leaf, leaf, cur, nv, impl="interpret",
                      starts=starts, chunk=16)


# The serving cells' attends, by shape: (leaf, heads, values' width or head
# size, chunk, flat width) -> whether a PLAIN leaf there takes the tile grid.
CELL_SHAPES = {
    # GigaChat3.5: 64 heads x chunk 32 are 2,048 query rows a slot on the
    # first grid; four tiles of 8 positions on the tile grid
    "gigachat35": ((128, 4224, 1, 576), 64, 512, 32, 2048, True),
    "gigachat35-narrow-side": ((128, 4224, 1, 576), 64, 512, 32, 1024, True),
    # GLM-4.7-Flash: 20 heads are no whole sublane tiles of bfloat16, and a
    # chunk of 8 is one tile
    "glm47flash": ((96, 4104, 1, 576), 20, 512, 8, 384, False),
    "glm47flash-chunk-16": ((96, 4112, 1, 576), 20, 512, 16, 768, False),
    "sixteen-heads-chunk-8": ((96, 4104, 1, 576), 16, 512, 8, 384, False),
    # at full width the flat batch IS [slots, chunk] order
    "gigachat35-full-width": ((128, 4224, 1, 576), 64, 512, 32, 4096, False),
    # dots3's and GLM-5's latent leaves select their rows (not plain: the
    # mixer never asks), but their shapes would tile
    "dots3-full-layer": ((32, 12832, 1, 576), 128, 512, 32, 512, True),
    "glm5-a-chip": ((32, 10784, 1, 576), 64, 512, 32, 512, True),
    # the K/V cells' leaves, kept in rows: a pair is no one-leaf cache
    "gpt2m": ((96, 1040, 1024), 16, 64, 16, 768, False),
    "jamba2": ((128, 8200, 128), 20, 128, 8, 512, False),
    "lfm2": ((128, 4112, 512), 32, 64, 16, 1024, False),
    "smallthinker-full-layer": ((48, 16416, 512), 28, 128, 32, 768, False),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_the_plain_rule_at_the_cells_shapes(cell):
  """``plain_tile_form`` is shape arithmetic: the tile kernel fits the
  leaf, a chunk is more than one tile of positions, the batch is narrower
  than ``slots x chunk``, the kernel was resolved."""
  shape, H, vw, C, T, takes = CELL_SHAPES[cell]
  narrower = T < shape[0] * C
  assert sa.plain_tile_form("pallas", narrower, shape, jnp.bfloat16, C, H,
                            vw) == takes
  assert sa.plain_tile_form("interpret", narrower, shape, jnp.bfloat16, C, H,
                            vw) == takes
  # no kernel, no tile form; nor for a lowering nobody resolved
  for impl in ("reference", None):
    assert not sa.plain_tile_form(impl, narrower, shape, jnp.bfloat16, C, H,
                                  vw)
  if takes:
    assert sa.tile_positions(C, H) == 8 and sa.decodes_apart(C)
  if cell.startswith("gigachat35"):
    # a block of 2048 rows within the 24 MiB the tile kernels may ask for
    assert sa._tile_block(4224, 576, jnp.bfloat16, 8 * 64, 512, False) == 2048
