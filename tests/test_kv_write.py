"""The serving step's in-place K/V window write (kernels/kv_write.py).

One algorithm — write each slot's ``chunk``-wide window at its cursor —
with two lowerings.  The contract under test: the Pallas kernel leaves
the WHOLE cache leaf bit-identical to ``vmap(dynamic_update_slice)``
(K and V, every cursor a tile can see), the dispatch rule declines what
the kernel cannot tile, the engine commits the same tokens and ends on
the same cursors under either lowering, and the program compiled for a
described v5e holds the kernel in place of the scatter loop with no copy
of a cache leaf.  Every case runs in both ORDERS a leaf is kept in
(serving/kv_cache.py, order note): ``positions`` ``[B, Lc, H, hd]`` and
``rows`` ``[B, Lc, H x hd]``, whose kernel also leaves an idle slot's
window unwritten.
"""

import dataclasses
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import generate
from easyparallellibrary_tpu.models.slot_core import slot_cache_attend
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, Request, kv_cache as kv_lib)
from easyparallellibrary_tpu.serving.speculative import NgramDrafter

kvw = importlib.import_module("easyparallellibrary_tpu.kernels.kv_write")
sa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.slot_attention")

MAX_SEQ, CHUNK = 1024, 16
LC = MAX_SEQ + CHUNK           # 1040: the cell's leaf, 8 tiles and 16 rows


ORDERS = ("positions", "rows")
in_both_orders = pytest.mark.parametrize("order", ORDERS)
# Heads of the toy leaves: 2 x 16 stays in positions, 2 x 64 = 128 fills
# a lane tile and is kept in rows.
HEADS = {"positions": (2, 16), "rows": (2, 64)}


def _backend_takes(monkeypatch, impl):
  """What a test steers: the lowering the backend would take."""
  monkeypatch.setattr(kvw, "_backend_impl", lambda: impl)


def _bits(x):
  x = np.asarray(x)
  return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _operands(B, Lc, H, hd, C, dtype, seed=0, order="positions"):
  """Random leaves (in ``order``) and chunks, salted with the values
  arithmetic would not carry through unchanged."""
  r = np.random.RandomState(seed)

  def salted(shape):
    x = r.standard_normal(shape).astype(np.float32)
    flat = x.reshape(-1)
    flat[r.randint(0, flat.size, 24)] = np.tile(
        [-0.0, np.nan, np.inf, -np.inf], 6)
    return jnp.asarray(x, dtype)

  leaf = (B, Lc, H * hd) if order == "rows" else (B, Lc, H, hd)
  return (salted(leaf), salted(leaf),
          salted((B, C, H, hd)), salted((B, C, H, hd)))


CURSORS = {
    "zeros": [0] * 16,
    "mixed": [0, 1, 15, 16, 17, 100, 111, 128, 129, 255, 256, 300, 511,
              640, 900, 1007],
    # Every offset at which a 16-wide window crosses a 128 boundary (and
    # 112, the last that does not), over several tiles.
    "straddle": [128 * (i % 7) + 112 + i for i in range(16)],
    # max_seq_len: the last legal window, rows 1024..1039 of 1040.
    "last_window": [MAX_SEQ] * 8 + [MAX_SEQ - i for i in range(1, 9)],
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(CURSORS))
@in_both_orders
def test_kernel_leaves_the_whole_leaf_bit_identical(order, case, dtype):
  """Odd and even cursors, windows inside one tile (stripe) and across
  two, the last legal window; bfloat16 packs two rows into a sublane
  word, which the rows form moves through float32."""
  cur = jnp.asarray(CURSORS[case], jnp.int32)
  ck, cv, k, v = _operands(len(CURSORS[case]), LC, *HEADS[order], CHUNK,
                           dtype, order=order)
  assert kvw.kv_write_fits(ck.shape, dtype, CHUNK)
  want_k, want_v = kvw.kv_write_reference(ck, cv, k, v, cur)
  got_k, got_v = jax.jit(
      lambda *a: kvw.kv_write(*a, impl="interpret"))(ck, cv, k, v, cur)
  np.testing.assert_array_equal(_bits(got_k), _bits(want_k))
  np.testing.assert_array_equal(_bits(got_v), _bits(want_v))
  # The window did move: the reference is not the leaf it started from.
  assert (_bits(want_k) != _bits(ck)).any()


@pytest.mark.parametrize("C,Lc,cursors", [
    (1, 256, [0, 127, 128, 255]),       # generate()'s decode: no slack row
    (32, 288, [0, 97, 127, 200, 256]),  # a wider chunk straddles earlier
    (128, 384, [0, 1, 127, 128, 256]),  # a window as wide as a tile
], ids=["decode_1", "chunk_32", "chunk_128"])
@in_both_orders
def test_other_chunk_widths_are_bit_identical(order, C, Lc, cursors):
  cur = jnp.asarray(cursors, jnp.int32)
  ck, cv, k, v = _operands(len(cursors), Lc, *HEADS[order], C, jnp.bfloat16,
                           seed=1, order=order)
  assert kvw.kv_write_fits(ck.shape, ck.dtype, C)
  want = kvw.kv_write_reference(ck, cv, k, v, cur)
  got = kvw.kv_write(ck, cv, k, v, cur, impl="interpret")
  for g, w in zip(got, want):
    np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("shape,dtype,chunk,sharded", [
    ((4, 36, 2, 16), jnp.float32, 4, False),       # under one tile
    ((4, 1200, 2, 16), jnp.float32, 160, False),   # window over two tiles
    ((4, 272, 2, 12), jnp.float32, 16, False),     # hd not whole sublanes
    ((4, 272, 2, 16), jnp.float16, 16, False),     # a dtype it was not
                                                   # proven on
    ((4, 272, 64, 128), jnp.float32, 16, False),   # tiles over the VMEM
    ((4, 272, 2, 16), jnp.float32, 16, True),      # leaf spread over chips
    # kept in rows
    ((4, 12, 128), jnp.bfloat16, 4, False),        # under one stripe
    ((4, 1200, 128), jnp.float32, 160, False),     # window over 128 rows
    ((4, 272, 96), jnp.float32, 16, False),        # W not whole lane tiles
    ((4, 272, 128), jnp.float16, 16, False),       # a dtype not proven
    ((4, 272, 16384), jnp.float32, 128, False),    # stripes over the VMEM
    ((4, 272, 128), jnp.float32, 16, True),        # leaf spread over chips
], ids=["short_leaf", "wide_chunk", "odd_hd", "f16", "vmem", "sharded",
        "rows_short_leaf", "rows_wide_chunk", "rows_odd_width", "rows_f16",
        "rows_vmem", "rows_sharded"])
def test_what_the_kernel_declines_takes_the_reference(
    monkeypatch, shape, dtype, chunk, sharded):
  for impl in ("interpret", "pallas"):
    _backend_takes(monkeypatch, impl)
    assert kvw.resolve_kv_write_impl(shape, dtype, chunk, sharded) == \
        "reference"


@in_both_orders
def test_rule_follows_the_backend(monkeypatch, order):
  ck, cv, k, v = _operands(4, LC, *HEADS[order], CHUNK, jnp.bfloat16,
                           order=order)
  shape = ck.shape
  assert kvw.resolve_kv_write_impl(shape, jnp.bfloat16, CHUNK) == \
      "reference"                      # this backend is the CPU
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert kvw.resolve_kv_write_impl(shape, jnp.bfloat16, CHUNK) == "pallas"
  assert kvw.resolve_kv_write_impl(shape, jnp.bfloat16, CHUNK,
                                   sharded=True) == "reference"
  # A typo'd impl must not fall through to the kernel.
  with pytest.raises(ValueError, match="impl must be one of"):
    kvw.kv_write(ck, cv, k, v, jnp.zeros((4,), jnp.int32), impl="mosaic")


@in_both_orders
def test_declined_shape_runs_the_reference_through_the_dispatcher(
    monkeypatch, order):
  """A backend that takes the kernel, a leaf shorter than a tile (in rows:
  one whose width is no whole lane tile, which ``cache_leaves`` would not
  fold but the reference must still take): ``slot_cache_attend`` (impl
  unresolved, as ``generate()`` calls it) must come out equal to the
  reference write, not fail in the kernel."""
  _backend_takes(monkeypatch, "interpret")
  ck, cv, k, v = _operands(3, 36, 2, 16, 4, jnp.float32, order=order)
  cur = jnp.asarray([0, 7, 32], jnp.int32)
  _, got_k, got_v = slot_cache_attend(k, k, v, ck, cv, cur, jnp.float32)
  want_k, want_v = kvw.kv_write_reference(ck, cv, k, v, cur)
  np.testing.assert_array_equal(_bits(got_k), _bits(want_k))
  np.testing.assert_array_equal(_bits(got_v), _bits(want_v))


# ------------------------------------------------------------------ engine

SERVE = GPTConfig(vocab_size=64, num_layers=2, num_heads=2, d_model=32,
                  d_ff=64, max_seq_len=256, dtype=jnp.float32)
# The same cut with heads that fill a lane tile (2 x 64 = 128): its
# leaves are kept in rows (the tier-1 cuts are narrower and never fold).
SERVE_IN = {"positions": SERVE,
            "rows": dataclasses.replace(SERVE, d_model=128, d_ff=256)}


def _serve(monkeypatch, impl, drafter=None, order="positions"):
  """Five greedy requests over three slots, prompts long enough that
  prefill chunks and decode tokens share steps and that decode cursors
  walk through a tile boundary one row at a time."""
  _backend_takes(monkeypatch, impl)
  tracer = trace_lib.install(trace_lib.Tracer(enabled=True))
  try:
    model = GPT(SERVE_IN[order])
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                   prefill_chunk=8, drafter=drafter)
    r = np.random.RandomState(0)
    prompts = [r.randint(0, 64, (n,)).astype(np.int32)
               for n in (118, 3, 121, 40, 126)]
    for i, p in enumerate(prompts):
      eng.submit(Request(uid=i, prompt=p, max_new_tokens=14))
    out = eng.run()
    facts = [e for e in tracer.events()
             if e["ph"] == "M" and e["name"] == "serving/kv_write_impl"]
    return (eng, {u: np.asarray(t) for u, t in out.items()},
            np.asarray(jax.device_get(eng._cursors)), facts, model,
            params, prompts)
  finally:
    trace_lib.install(None)


@pytest.mark.parametrize("drafter", [None, "ngram"],
                         ids=["fused_step", "speculative_step"])
@in_both_orders
def test_engine_commits_the_same_under_either_lowering(monkeypatch, order,
                                                       drafter):
  epl.init()
  mk = lambda: NgramDrafter(k=3, ngram_max=3) if drafter else None
  eng_k, out_k, cur_k, facts_k, model, params, prompts = _serve(
      monkeypatch, "interpret", mk(), order)
  eng_r, out_r, cur_r, facts_r, *_ = _serve(monkeypatch, "reference", mk(),
                                            order)
  assert eng_k.cache_layout["kv_order"] == order
  # Which write each run timed is on record, not inferred.
  assert eng_k.lowerings["kv_write_impl"] == "interpret"
  assert eng_r.lowerings["kv_write_impl"] == "reference"
  assert [f["args"] for f in facts_k] == [{"impl": "interpret"}]
  assert [f["args"] for f in facts_r] == [{"impl": "reference"}]
  assert sorted(out_k) == sorted(out_r) == list(range(len(prompts)))
  for uid in out_k:
    np.testing.assert_array_equal(out_k[uid], out_r[uid])
  np.testing.assert_array_equal(cur_k, cur_r)
  # Each step compiled once under its lowering.
  assert eng_k._step_fn._cache_size() == eng_r._step_fn._cache_size() == 1
  # And the kernel-written engine still equals the one-request oracle.
  want = np.asarray(generate(model, params,
                             jnp.asarray(prompts[2])[None], 14))[0]
  np.testing.assert_array_equal(out_k[2], want)


def test_trace_metadata_outlives_the_ring_and_clear():
  """What is decided once must still be in the export after the ring has
  turned over and after the benchmark clears it at its window's start."""
  tr = trace_lib.Tracer(enabled=True, ring_capacity=4)
  tr.metadata("serving/kv_write_impl", {"impl": "pallas"})
  for i in range(10):
    tr.instant(f"tick{i}")
  tr.clear()
  tr.instant("after")
  events = tr.events()
  facts = [e for e in events if e["name"] == "serving/kv_write_impl"]
  assert facts == [{"ph": "M", "name": "serving/kv_write_impl", "pid": 0,
                    "tid": 0, "args": {"impl": "pallas"}}]
  assert trace_lib.validate_trace({"traceEvents": events}) == events
  # Recorded again, it replaces; a disabled tracer records nothing.
  tr.metadata("serving/kv_write_impl", {"impl": "reference"})
  assert [e["args"] for e in tr.events()
          if e["name"] == "serving/kv_write_impl"] == [{"impl": "reference"}]
  off = trace_lib.Tracer(enabled=False)
  off.metadata("serving/kv_write_impl", {"impl": "pallas"})
  assert off.events() == [e for e in off.events() if e["ph"] == "M"
                          and e["name"] != "serving/kv_write_impl"]


@in_both_orders
def test_engine_on_a_mesh_of_chips_takes_the_reference(monkeypatch, order):
  _backend_takes(monkeypatch, "interpret")
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  assert kv_lib.kv_write_impl(SERVE_IN[order], 3, 8, mesh) == "reference"
  assert kv_lib.kv_write_impl(SERVE_IN[order], 3, 8, None) == "interpret"


# ------------------------------------------------- the order, and who reads it


def test_idle_slots_are_left_unwritten_by_the_rows_form():
  """``num_valid == 0``: the rows kernel visits fed slots only, so an
  idle slot's leaf is bit for bit what it was, a fed slot's what the
  reference makes it; with no slot fed at all nothing moves (the grid's
  one row writes its stripe back as it was).  The positions form, which
  does not read ``num_valid``, writes every window as it always did."""
  cur = jnp.asarray([5, 100, 0, 1024, 17, 640], jnp.int32)
  nv = jnp.asarray([0, 16, 0, 3, 1, 0], jnp.int32)
  fed = np.asarray(nv) > 0
  for dtype in (jnp.bfloat16, jnp.float32):
    ck, cv, k, v = _operands(6, LC, 2, 64, CHUNK, dtype, order="rows")
    want = kvw.kv_write_reference(ck, cv, k, v, cur)
    got = kvw.kv_write(ck, cv, k, v, cur, nv, impl="interpret")
    for g, w, old in zip(got, want, (ck, cv)):
      np.testing.assert_array_equal(_bits(g)[fed], _bits(w)[fed])
      np.testing.assert_array_equal(_bits(g)[~fed], _bits(old)[~fed])
    none = kvw.kv_write(ck, cv, k, v, cur, jnp.zeros_like(nv),
                        impl="interpret")
    np.testing.assert_array_equal(_bits(none[0]), _bits(ck))
    np.testing.assert_array_equal(_bits(none[1]), _bits(cv))
  ck, cv, k, v = _operands(6, LC, 2, 16, CHUNK, jnp.float32)
  got = kvw.kv_write(ck, cv, k, v, cur, nv, impl="interpret")
  want = kvw.kv_write_reference(ck, cv, k, v, cur)
  np.testing.assert_array_equal(_bits(got[0]), _bits(want[0]))


def test_cache_leaves_fold_where_the_heads_fill_the_lanes():
  """One rule on the shape: GPT-2 medium (16 x 64 = 1024), GPT-2 large
  (20 x 64 = 1280) and the hybrid (one K/V head of 128) are kept in rows,
  the same bytes as before; a narrow cut, a dtype the kernels were not
  proven on and the 576-wide latent leaf (4.5 lane tiles) stay as they
  were.  ``cache_layout`` says which."""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoeConfig
  from easyparallellibrary_tpu.models.jamba import JambaConfig
  gpt = lambda **kw: GPTConfig(vocab_size=64, max_seq_len=1024,
                               dtype=jnp.bfloat16, **kw)
  cases = [
      (gpt(num_layers=2, num_heads=16, d_model=1024, d_ff=64), 96, 16,
       (96, 1040, 1024), "rows"),
      (gpt(num_layers=2, num_heads=20, d_model=1280, d_ff=64), 4, 16,
       (4, 1040, 1280), "rows"),
      (JambaConfig(num_layers=8, max_seq_len=8192), 128, 8,
       (128, 8200, 128), "rows"),
      (gpt(num_layers=2, num_heads=2, d_model=32, d_ff=64), 4, 16,
       (4, 1040, 2, 16), "positions"),
      (dataclasses.replace(gpt(num_layers=2, num_heads=16, d_model=1024,
                               d_ff=64), dtype=jnp.float16), 4, 16,
       (4, 1040, 16, 64), "positions"),
      (GlmMoeConfig(num_layers=2, vocab_size=64, max_seq_len=4096), 96, 8,
       (96, 4104, 1, 576), "positions"),
  ]
  for cfg, slots, chunk, shape, order in cases:
    assert kv_lib.kv_leaf_shape(cfg, slots, chunk) == shape
    leaves = kv_lib.cache_leaves(cfg, slots, chunk)
    under_cursor = [l for path, l in
                    jax.tree_util.tree_leaves_with_path(leaves)
                    if path[1].key in ("attn", "latent")]
    assert under_cursor and {l.shape for l in under_cursor} == {shape}
    layout = kv_lib.cache_layout(cfg, slots, chunk)
    assert layout["kv_order"] == order
    size = jnp.dtype(cfg.dtype).itemsize
    assert layout["kv_bytes" if "latent_bytes" not in layout
                  else "latent_bytes"] == (
        len(under_cursor) * int(np.prod(shape)) * size)
    assert kv_lib.cache_bytes(cfg, slots, chunk) == sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(leaves))


def test_shardings_tell_kv_by_its_key_and_split_a_folded_leaf_over_model():
  """A leaf kept in rows has the rank recurrent state has: K/V is told by
  its key (``attn``).  On ``model:2`` the folded leaf splits its minor
  dimension (head-major: whole heads), a leaf in positions its heads, and
  recurrent state and a one-head leaf stay replicated."""
  from jax.sharding import PartitionSpec as P
  from easyparallellibrary_tpu.models.jamba import JambaConfig
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  spec_of = lambda cfg: {
      path[-1].key: sh.spec for path, sh in
      jax.tree_util.tree_leaves_with_path(
          kv_lib.kv_cache_shardings(cfg, mesh)[0])}
  rows = spec_of(SERVE_IN["rows"])
  assert rows == {"cached_key": P(None, None, "model"),
                  "cached_value": P(None, None, "model")}
  assert spec_of(SERVE) == {"cached_key": P(None, None, "model", None),
                            "cached_value": P(None, None, "model", None)}
  # The hybrid's one K/V head does not divide the axis; its recurrent
  # state never splits.  Two K/V heads of 64 do, state still does not.
  hybrid = JambaConfig(num_layers=8, d_model=512, num_heads=4,
                       num_kv_heads=1, d_ff=64, mamba_dt_rank=4,
                       vocab_size=64, max_seq_len=128)
  assert len(kv_lib.kv_leaf_shape(hybrid, 2, 4)) == 3
  assert set(spec_of(hybrid).values()) == {P()}
  wide = dataclasses.replace(hybrid, d_model=256, num_kv_heads=2)
  got = spec_of(wide)
  assert got["cached_key"] == got["cached_value"] == P(None, None, "model")
  assert got["conv_state"] == got["ssm_state"] == P()
  kv, _ = kv_lib.allocate_kv_cache(wide, 2, 4, mesh)
  placed = {path[-1].key: x.sharding.spec for path, x in
            jax.tree_util.tree_leaves_with_path(kv)}
  assert placed == got


def test_sanitize_zeroes_the_same_rows_in_both_orders():
  """The guarded engine's sanitize program follows the leaf's rank: the
  masked slots' rows from their start row up are zeros, everything else
  is untouched, in a cache kept in rows as in one kept in positions."""
  epl.init()
  mask = np.asarray([True, False, True])
  start = np.asarray([0, 5, 100], np.int32)
  zeroed = {}
  for order in ORDERS:
    model = GPT(SERVE_IN[order])
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                   prefill_chunk=8, resilience=True)
    assert eng.cache_layout["kv_order"] == order
    ones = jax.tree_util.tree_map(jnp.ones_like, eng._kv)
    out = eng._sanitize_fn(ones, mask, start)
    for leaf in jax.tree_util.tree_leaves(out):
      x = np.asarray(leaf).reshape(leaf.shape[0], leaf.shape[1], -1)
      rows = (x == 0).all(axis=-1)
      assert ((x == 0) | (x == 1)).all() and (rows == (x == 0).any(-1)).all()
      zeroed.setdefault(order, rows)
      np.testing.assert_array_equal(rows, zeroed[order])
  np.testing.assert_array_equal(zeroed["rows"], zeroed["positions"])
  want = mask[:, None] & (np.arange(264)[None] >= start[:, None])
  np.testing.assert_array_equal(zeroed["rows"], want)


# --------------------------------------------------- compiled for the chip


@pytest.fixture(scope="module")
def four_chips():
  """The devices of a described four-chip v5e host."""
  from jax.experimental import topologies
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no libtpu here, or another process holds it
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  return list(topo.devices)


@pytest.fixture(scope="module")
def one_chip(four_chips):
  return SingleDeviceSharding(four_chips[0])


def _compiled_text(jitted, *args) -> str:
  """Optimised HLO of ``jitted`` for ``args`` (shapes on the described
  chip), compiled outside the persistent cache: an entry written for a
  chip that is not attached cannot be read back and only warns."""
  from jax.experimental.compilation_cache import compilation_cache
  cache_was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    return jitted.lower(*args).compile().as_text()
  finally:
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _leaf_lines(entry, *shapes):
  """``(opcode, line)`` of the entry computation's instructions whose
  result is an array of one of ``shapes`` (HLO shape prefixes)."""
  for line in entry.splitlines():
    m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) (\S+?)\(", line)
    if m and m.group(2).startswith(shapes):
      yield m.group(3), line


@in_both_orders
def test_compiled_for_v5e_holds_the_kernel_and_no_copy_of_a_leaf(
    one_chip, order):
  """``slot_cache_attend`` at the GPT-2 medium cells' shapes, in the order
  the cells keep their leaves in (rows) and in the one they did
  (positions), compiled for a described v5e: one ``kv_write`` custom call
  whose two leaf operands alias its outputs, no ``while`` left of the
  scatter loop, and no copy or relayout of a leaf.  In positions the
  transposes around the call are bitcasts and the reference attend's two
  fusions read the written leaves straight from the kernel; in rows the
  attend is the kernel (the einsums would relay the whole leaf out of
  rows: the two rules take and decline a leaf together)."""
  B, H, hd = 96, 16, 64
  dt = jnp.bfloat16
  spec = lambda shape, d=dt: jax.ShapeDtypeStruct(shape, d,
                                                  sharding=one_chip)
  shape = (B, LC, H * hd) if order == "rows" else (B, LC, H, hd)
  new, leaf = spec((B, CHUNK, H, hd)), spec(shape)
  fn = lambda q, k, v, ck, cv, cur: slot_cache_attend(
      q, k, v, ck, cv, cur, dt, write_impl="pallas",
      attn_impl="pallas" if order == "rows" else "reference")
  text = _compiled_text(jax.jit(fn, donate_argnums=(3, 4)),
                        new, new, new, leaf, leaf, spec((B,), jnp.int32))
  entry = text[text.index("\nENTRY "):]
  calls = [l for l in entry.splitlines() if " custom-call(" in l
           and "kv_write" in l.split("=")[0]]
  assert len(calls) == 1, entry
  # The leaves follow the scalar-prefetch vectors and the two chunks:
  # the cursors in positions; in rows the grid's dynamic bound (the
  # number of fed slots) and four vectors.
  first = 7 if order == "rows" else 3
  aliasing = re.search(r"output_to_operand_aliasing=\{(.*?)\}, ", calls[0])
  assert aliasing and f"{{0}}: ({first}, {{}})" in aliasing.group(1) \
      and f"{{1}}: ({first + 1}, {{}})" in aliasing.group(1), calls[0]
  assert " while(" not in text
  # Instructions of the entry computation whose result is a whole leaf
  # (in any order of its dimensions): parameters, bitcasts and the
  # kernel's own results only.
  for opcode, line in _leaf_lines(
      entry, f"bf16[{B},{LC},{H},{hd}]", f"bf16[{B},{H},{hd},{LC}]",
      f"bf16[{B},{LC},{H * hd}]"):
    assert opcode in ("parameter", "bitcast", "get-tuple-element"), line
  if order == "positions":
    # The two attention fusions take the written leaves straight from
    # the kernel, through a bitcast.
    readers = [l for l in entry.splitlines() if " fusion(" in l
               and "kind=kOutput" in l]
    assert len(readers) == 2, entry


@pytest.mark.parametrize("B,C,H,Hkv,hd,Lc", [
    (96, CHUNK, 16, 16, 64, LC),        # the GPT-2 medium cells
    (128, 8, 20, 1, 128, 8200),         # the hybrid cell: grouped heads
], ids=["gpt2m_cells", "hybrid_cell"])
@in_both_orders
def test_compiled_for_v5e_attends_in_one_kernel_with_no_score_tensor(
    one_chip, order, B, C, H, Hkv, hd, Lc):
  """``slot_cache_attend`` with both kernels (kernels/slot_attention.py
  beside the write), compiled for a described v5e at the serving cells'
  shapes: one ``kv_write`` and one ``slot_attn`` custom call, no score
  tensor ``[B, H, C, Lc]`` in any form.  Kept in ROWS, as the cells keep
  them, no instruction but the kernels, parameters and bitcasts yields a
  whole leaf in any order of its dimensions: no copy and no transpose of
  a leaf, GPT-2's or the hybrid's.  Kept in POSITIONS (what both were):
  none for GPT-2's position-minor leaf, and the hybrid's hd-minor leaf
  is copied into the view the kernels share and back, which is what
  keeping it in rows took away."""
  dt = jnp.bfloat16
  spec = lambda shape, d=dt: jax.ShapeDtypeStruct(shape, d,
                                                  sharding=one_chip)
  shape = (B, Lc, Hkv * hd) if order == "rows" else (B, Lc, Hkv, hd)
  new, leaf = spec((B, C, Hkv, hd)), spec(shape)
  fn = lambda q, k, v, ck, cv, cur, nv: slot_cache_attend(
      q, k, v, ck, cv, cur, dt, write_impl="pallas", attn_impl="pallas",
      num_valid=nv)
  text = _compiled_text(
      jax.jit(fn, donate_argnums=(3, 4)), spec((B, C, H, hd)), new, new,
      leaf, leaf, spec((B,), jnp.int32), spec((B,), jnp.int32))
  entry = text[text.index("\nENTRY "):]
  named = lambda name: [l for l in entry.splitlines() if " custom-call("
                        in l and name in l.split("=")[0]]
  assert len(named("kv_write")) == 1 and len(named("slot_attn")) == 1, entry
  assert " while(" not in text
  assert not re.search(rf"\[{B},(?:{Hkv},{H // Hkv}|{H}),{C},{Lc}\]", text)
  minor = f"bf16[{B},{Hkv},{hd},{Lc}]"
  major = f"bf16[{B},{Lc},{Hkv},{hd}]"
  folded = f"bf16[{B},{Lc},{Hkv * hd}]"
  # The hybrid's hd-minor leaf in positions is copied into the shared
  # view and back, counted below; nothing else may copy a leaf.
  relaid = order == "positions" and hd == 128
  views = (minor,) if relaid else (minor, major, folded)
  allowed = {"parameter", "bitcast", "get-tuple-element"} | (
      {"copy"} if relaid else set())
  for opcode, line in _leaf_lines(entry, *views):
    assert opcode in allowed, line
  if relaid:
    # An hd-minor leaf is relaid to position-minor for the write and
    # back (PR 26); the attend reads that view and adds no relayout.
    copies = [l for l in entry.splitlines() if re.match(
        rf"\s*(?:ROOT )?%\S+ = bf16\[{B},{Lc},{Hkv},{hd}\]\S* copy\(", l)]
    assert len(copies) == 4, entry


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@in_both_orders
def test_slot_attn_compiles_for_v5e_under_a_highest_precision_context(
    one_chip, order, dtype):
  """A caller's ``jax.default_matmul_precision("highest")`` (the chip
  smoke's float32 cut runs under one) reaches the kernel's two
  contractions when it is traced: Mosaic takes it for float32 operands
  and refuses it for 16-bit ones ("Bad lhs type"), so the kernel names
  the default for those itself."""
  from easyparallellibrary_tpu.kernels.slot_attention import (
      slot_attention_pallas)
  B, C, H, hd = 8, CHUNK, 16, 64
  spec = lambda shape, d=dtype: jax.ShapeDtypeStruct(shape, d,
                                                     sharding=one_chip)
  leaf = spec((B, LC, H * hd) if order == "rows" else (B, LC, H, hd))
  with jax.default_matmul_precision("highest"):
    # a fresh function: the jitted wrapper's own trace cache is keyed on
    # shapes, not on the context
    text = _compiled_text(
        jax.jit(lambda *a: slot_attention_pallas.__wrapped__(*a)),
        spec((B, C, H, hd)), leaf, leaf,
        spec((B,), jnp.int32), spec((B,), jnp.int32))
  assert "slot_attn" in text


def test_ssm_scan_compiled_for_v5e_moves_the_state_once(one_chip):
  """The selective-scan kernel (kernels/ssm_scan.py) at the hybrid cell's
  shapes, compiled for a described v5e (here beside the other compile of
  this file: one process describes the chip): one Mosaic custom call
  named ``ssm_scan`` whose state operand aliases its output, no ``while``
  (the reference lowering is a scan), and no copy of the ``[128, 16,
  5120]`` state anywhere in the program."""
  from easyparallellibrary_tpu.kernels.ssm_scan import ssm_scan_pallas
  B, C, N, Di = 128, 8, 16, 5120
  f32, bf16 = jnp.float32, jnp.bfloat16
  spec = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=one_chip)
  args = (spec((B, N, Di), f32), spec((B, C, Di), bf16),
          spec((B, C, Di), f32), spec((B, C, N), f32), spec((B, C, N), f32),
          spec((B, C, Di), bf16), spec((N, Di), f32), spec((Di,), f32),
          spec((B,), jnp.int32), spec((B,), jnp.bool_))
  text = _compiled_text(jax.jit(ssm_scan_pallas, donate_argnums=0), *args)
  entry = text[text.index("\nENTRY "):]
  calls = [l for l in entry.splitlines() if " custom-call(" in l
           and "tpu_custom_call" in l]
  assert len(calls) == 1 and "ssm_scan" in calls[0].split("=")[0], entry
  # operand 2 (after the two scalar-prefetch vectors) is the state,
  # output 1 the new state
  assert "{1}: (2, {})" in calls[0], calls[0]
  assert " while(" not in text
  state = f"f32[{B},{N},{Di}]"
  for line in entry.splitlines():
    m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) (\S+?)\(", line)
    if m and m.group(2).startswith(state):
      assert m.group(3) in ("parameter", "bitcast",
                            "get-tuple-element"), line


@pytest.mark.parametrize("slots,vocab", [(96, 50304), (128, 65536)],
                         ids=["gpt2m_cells", "hybrid_cell"])
def test_sampling_tail_compiled_for_v5e_sorts_in_a_branch_alone(
    one_chip, slots, vocab):
  """``sample_token_slots`` (serving/engine.py) at the serving cells'
  logits, compiled for a described v5e (here, beside the other compiles:
  one process describes the chip): the TPU compiler keeps both
  conditionals and the one sort inside a branch, so a step whose slots
  are all greedy runs no sort; flattened into a select, both sides
  would run and the gate would buy nothing."""
  from easyparallellibrary_tpu.serving import sample_token_slots
  from easyparallellibrary_tpu.testing.hlo import op_sites
  spec = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=one_chip)
  text = _compiled_text(
      jax.jit(sample_token_slots), spec((slots, vocab), jnp.float32),
      spec((slots, 2), jnp.uint32), spec((slots,), jnp.float32),
      spec((slots,), jnp.int32), spec((slots,), jnp.float32))
  unconditional, conditional = op_sites(text, "sort")
  assert unconditional == [] and len(conditional) == 1, (
      unconditional, conditional)
  always, nested = op_sites(text, "conditional")
  assert len(always) == 1 and len(nested) == 1, (always, nested)


# ------------------------------------------------------- the one-leaf form
# A layer whose cache is ONE tensor (models/glm_moe.py: the latent of
# multi-head latent attention): ``cached_v = v = None``.

LATENT = 576                   # kv_lora_rank 512 + qk_rope_head_dim 64


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("hd,C", [(LATENT, 8), (40, 8), (64, 1)],
                         ids=["latent_576", "hd_40", "decode"])
def test_one_leaf_is_written_bit_identical(hd, C, dtype):
  """hd 576 (the latent row), a width that is no lane multiple, and a
  one-token decode: the whole leaf equals ``dynamic_update_slice``'s and
  the second place comes back ``None`` from both lowerings."""
  if dtype == jnp.bfloat16 and hd % 16:
    hd = 48                    # 16-bit rows fill whole sublane tiles
  Lc = 264
  cursors = jnp.asarray([0, 1, 120, 124, 127, 128, 250, Lc - C], jnp.int32)
  ck, _, k, _ = _operands(len(cursors), Lc, 1, hd, C, dtype, seed=hd)
  assert kvw.kv_write_fits(ck.shape, dtype, C)
  got, none = kvw.kv_write(ck, None, k, None, cursors, impl="interpret")
  want, none_too = kvw.kv_write(ck, None, k, None, cursors,
                                impl="reference")
  assert none is None and none_too is None
  assert (_bits(got) == _bits(want)).all()


def test_two_leaves_are_what_they_were_beside_the_one_leaf_form():
  """The pair's results do not depend on the one-leaf generalisation:
  K as one leaf equals K of the pair, bit for bit."""
  cursors = jnp.asarray([3, 125, 200], jnp.int32)
  ck, cv, k, v = _operands(3, 264, 2, 32, 8, jnp.float32, seed=9)
  pair_k, pair_v = kvw.kv_write(ck, cv, k, v, cursors, impl="interpret")
  one_k, _ = kvw.kv_write(ck, None, k, None, cursors, impl="interpret")
  one_v, _ = kvw.kv_write(cv, None, v, None, cursors, impl="interpret")
  assert (_bits(pair_k) == _bits(one_k)).all()
  assert (_bits(pair_v) == _bits(one_v)).all()


@pytest.mark.parametrize("backend,sharded,want", [
    ("pallas", False, "pallas"), ("pallas", True, "reference"),
    ("reference", False, "reference")],
    ids=["tpu", "tpu_on_a_mesh", "cpu"])
def test_rule_takes_the_latent_leaf_on_a_tpu(monkeypatch, backend, sharded,
                                             want):
  """The cell's leaf ``[96, 4104, 1, 576]``: the kernel on a TPU, the
  reference on the CPU and on a mesh of chips."""
  _backend_takes(monkeypatch, backend)
  for dtype in (jnp.bfloat16, jnp.float32):
    assert kvw.resolve_kv_write_impl((96, 4104, 1, LATENT), dtype, 8,
                                     sharded=sharded) == want


def test_one_leaf_write_compiles_for_v5e_with_no_copy_of_the_leaf(one_chip):
  """The latent leaf at the cell's size, compiled for a described v5e: one
  ``kv_write`` custom call whose ONE leaf operand aliases its output, and
  no copy of the leaf."""
  B, Lc, C = 96, 4104, 8
  spec = lambda shape, d=jnp.bfloat16: jax.ShapeDtypeStruct(
      shape, d, sharding=one_chip)
  fn = lambda leaf, rows, cur: kvw.kv_write(leaf, None, rows, None, cur,
                                            impl="pallas")[0]
  text = _compiled_text(jax.jit(fn, donate_argnums=(0,)),
                        spec((B, Lc, 1, LATENT)), spec((B, C, 1, LATENT)),
                        spec((B,), jnp.int32))
  entry = text[text.index("\nENTRY "):]
  calls = [l for l in entry.splitlines() if " custom-call(" in l
           and "kv_write" in l.split("=")[0]]
  assert len(calls) == 1, entry
  assert "output_to_operand_aliasing={{}: (2, {})}" in calls[0], calls[0]
  assert " while(" not in text
  for line in entry.splitlines():
    m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) (\S+?)\(", line)
    if m and (f"[{B},{Lc},1,{LATENT}]" in m.group(2)
              or f"[{B},1,{LATENT},{Lc}]" in m.group(2)):
      assert m.group(3) in ("parameter", "bitcast", "get-tuple-element",
                            "custom-call"), line


# ------------------------------------ the expert decoder, compiled for v5e


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("highest", [False, True],
                         ids=["default_precision", "highest_precision"])
def test_moe_gmm_compiles_for_v5e_at_the_cells_shapes(one_chip, highest,
                                                      dtype):
  """Both grouped matmuls of an expert layer, 3072 sorted assignments over
  64 experts, compiled for a described v5e: ONE Mosaic custom call named
  ``moe_gmm`` each, also when the caller's context says ``highest``
  (which Mosaic refuses for bfloat16 operands unless the kernel names its
  own: the trap PERF.md section 6 names)."""
  gmm = importlib.import_module("easyparallellibrary_tpu.kernels.moe_gmm")
  spec = lambda shape, d=dtype: jax.ShapeDtypeStruct(shape, d,
                                                     sharding=one_chip)
  for K, N in ((2048, 3072), (1536, 2048)):
    fn = jax.jit(lambda a, b, s: gmm.moe_gmm_pallas.__wrapped__(a, b, s))
    with jax.default_matmul_precision("highest" if highest else "default"):
      text = _compiled_text(fn, spec((3072, K)), spec((64, K, N)),
                            spec((64,), jnp.int32))
    calls = [l for l in text.splitlines() if " custom-call(" in l
             and 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1 and "moe_gmm" in calls[0].split("=")[0], calls


def _assert_no_leaf_copied(text, kv):
  """No instruction of the compiled program ``text`` copies a cache leaf
  of ``kv`` of 16 MiB or more (a window over the context, a recurrence's
  state; a convolution's few inputs are selected anew every step), in
  whatever order of its dimensions: such a leaf is written in place and
  read where it lies."""
  names = {"bfloat16": "bf16", "float32": "f32"}
  leaves = {(names[str(leaf.dtype)], leaf.size)
            for leaf in jax.tree_util.tree_leaves(kv)
            if leaf.size * leaf.dtype.itemsize >= 1 << 24}
  assert leaves
  for dtype, dims in re.findall(r"= (\w+)\[([\d,]+)\]\S* copy(?:-start)?\(",
                                text):
    size = 1
    for d in dims.split(","):
      size *= int(d)
    assert (dtype, size) not in leaves, f"{dtype}[{dims}] is copied"


def _abstract_step(model, slots, C, one_chip, **engine):
  """The plain fused step as the engine builds it for ``model`` at
  ``slots x C``, with every kernel's Pallas lowering, and abstract
  arguments for it that sit on the described chip.  ``engine``: what else
  ``_build_step`` reads of an engine (recurrent state, experts) and, as
  ``<name>_impl``, the other entries of its record of lowerings."""
  import types
  from flax import linen as nn
  from easyparallellibrary_tpu.serving.engine import (
      flat_width, narrow_width)
  on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
  params = jax.tree_util.tree_map(on_chip, nn.meta.unbox(jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"])))
  kv = jax.tree_util.tree_map(
      on_chip, kv_lib.cache_leaves(model.cfg, slots, C))
  lowerings = dict(
      kv_write_impl="pallas", slot_attn_impl="pallas",
      **{name: engine.pop(name) for name in list(engine)
         if name.endswith("_impl")})
  engine = types.SimpleNamespace(**{**dict(
      model=model, num_slots=slots, chunk=C,
      flat_width=flat_width(slots, C),
      flat_narrow=narrow_width(flat_width(slots, C), slots),
      lowerings=lowerings, _recurrent=False, _experts=False, slot_axis=None,
      _jit_step=lambda step, donate, **kw: jax.jit(
          step, donate_argnums=(1, 2))), **engine})
  step = ContinuousBatchingEngine._build_step(engine, True)
  spec = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=one_chip)
  i32, f32 = jnp.int32, jnp.float32
  return step, (
      params, kv, spec((slots,), i32), spec((slots, C), i32),
      spec((slots,), i32), spec((slots,), jnp.bool_),
      spec((slots,), i32), spec((slots,), jnp.bool_),   # prev, from_prev
      spec((slots, 2), jnp.uint32), spec((slots,), i32),
      spec((slots,), f32), spec((slots,), i32), spec((slots,), f32))


@pytest.mark.parametrize("C", [8, 16], ids=["one_width", "two_widths"])
def test_expert_step_compiled_for_v5e_holds_its_three_kernels(one_chip, C):
  """The fused step of a two-layer cut (one dense, one expert layer) of
  models/glm_moe.py at GLM-4.7-Flash's widths and 96 slots, compiled for a
  described v5e as the engine builds it: one ``kv_write`` and one
  ``slot_attn`` a layer over ONE latent leaf, two ``moe_gmm`` an expert
  layer; no copy of a ``[96, 4096 + C, 1, 576]`` leaf in either order of
  its dimensions, no ``[positions, 64, ..]`` dispatch tensor, no ``while``
  loop (a scatter or a binary search would be one).  At its cell's chunk
  of 8 the step has ONE width (384 rows, whose half rounds up to 256: more
  than half, serving/engine.py ``narrow_width``); at a chunk of 16 it has
  two (768 / 384), the write and the attend outside the conditionals and
  in the program ONCE (models/slot_core.py ``SplitLayer``), the expert layer's
  ``moe_gmm`` on either side of one.  (Not at 128 slots x 8: there the
  ONE-width program copies the leaf four times too.)"""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoe, GlmMoeConfig
  from easyparallellibrary_tpu.serving.engine import (
      flat_width, narrow_width)
  epl.init()
  slots, Lc = 96, 4096 + C
  widths = 1 + (narrow_width(flat_width(slots, C), slots)
                < flat_width(slots, C))
  assert widths == {8: 1, 16: 2}[C]
  cfg = GlmMoeConfig(num_layers=2, vocab_size=32768)
  step, args = _abstract_step(GlmMoe(cfg), slots, C, one_chip,
                              moe_gmm_impl="pallas", _experts=True)
  text = _compiled_text(step, *args)
  calls = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
  assert (calls("kv_write"), calls("slot_attn"), calls("moe_gmm")) == (
      2, 2, 2 * widths), text.count("tpu_custom_call")
  assert " while(" not in text
  _assert_no_leaf_copied(text, args[1])
  for line in text.splitlines():
    m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) (\S+?)\(", line)
    if m and (m.group(2).startswith(f"bf16[{slots},{Lc},1,576]")
              or m.group(2).startswith(f"bf16[{slots},1,576,{Lc}]")):
      assert m.group(3) in ("parameter", "bitcast", "get-tuple-element",
                            "custom-call"), line
  assert not re.search(rf"\[{slots * C},64,\d+", text)


def test_lfm2_step_compiled_for_v5e_holds_its_three_kernels(one_chip):
  """The fused step of a two-layer cut (conv + dense, attention + experts)
  of models/lfm2_moe.py at LFM2-8B-A1B's widths, 128 slots x chunk 16,
  compiled for a described v5e as the engine builds it for a model with
  recurrent state AND routed experts: one ``kv_write`` and one
  ``slot_attn`` over the ``[128, 4112, 512]`` leaf kept in rows (8 K/V
  heads of 64 under 32 query heads), outside the step's conditionals and
  in the program once; two ``moe_gmm`` on either side of one; no copy or
  transpose of a leaf, no ``while`` loop (the window is advanced by
  selects)."""
  from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
  epl.init()
  slots, C = 128, 16
  cfg = Lfm2MoeConfig(layer_types=("conv", "full_attention"),
                      num_dense_layers=1, vocab_size=32768)
  step, args = _abstract_step(Lfm2Moe(cfg), slots, C, one_chip,
                              _recurrent=True, moe_gmm_impl="pallas",
                              _experts=True)
  text = _compiled_text(step, *args)
  calls = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
  assert (calls("kv_write"), calls("slot_attn"), calls("moe_gmm")) == (
      1, 1, 4), text.count("tpu_custom_call")
  assert " while(" not in text
  _assert_no_leaf_copied(text, args[1])
  for line in text.splitlines():
    m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) (\S+?)\(", line)
    if m and m.group(2).startswith(f"bf16[{slots},4112,"):
      assert m.group(3) in ("parameter", "bitcast", "get-tuple-element",
                            "custom-call"), line


def test_dots3_step_compiled_for_v5e_holds_its_kernels(one_chip):
  """The fused step of a two-layer cut (a selecting layer + dense, a window
  layer + 32 held experts of 256) of models/dots3_note.py at
  dots3-note-prev's widths and its cell's geometry, 32 slots x chunk 32 at
  a context of 12,800, compiled for a described v5e as the engine builds
  it, at two widths: three ``kv_write`` (the latent leaf, the index leaf in
  rows, the ring), two each of ``dsa_index``, ``slot_attn_sel`` and
  ``slot_attn_win`` (the slots that feed several positions, then the
  decoding ones), outside the conditionals and in the program ONCE
  (models/slot_core.py ``SplitLayer``), as the one ``while``, the threshold's 32
  counting passes; two ``moe_gmm`` on either side of a conditional; no copy
  of a cache leaf in either order of its dimensions; no ``[slots, chunk,
  heads, Lc]`` score tensor."""
  from easyparallellibrary_tpu.models.dots3_note import Dots3Note, Dots3NoteConfig
  from easyparallellibrary_tpu.models.layer_kinds import FULL, SLIDING
  epl.init()
  slots, C = 32, 32
  cfg = Dots3NoteConfig(vocab_size=19008, layer_types=(FULL, SLIDING),
                        experts_held=(0, 32), max_seq_len=12800)
  step, args = _abstract_step(Dots3Note(cfg), slots, C, one_chip,
                              moe_gmm_impl="pallas", _experts=True,
                              dsa_index_impl="pallas")
  text = _compiled_text(step, *args)
  calls = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
  assert [calls(n) for n in ("kv_write", "dsa_index", "slot_attn_sel",
                             "slot_attn_win", "moe_gmm", "slot_attn")] == [
      3, 2, 2, 2, 4, 0], text.count("tpu_custom_call")
  assert text.count(" while(") == 1
  _assert_no_leaf_copied(text, args[1])
  leaves = [f"bf16[{slots},12832,1,576]", f"bf16[{slots},1,576,12832]",
            f"bf16[{slots},12832,128]", f"bf16[{slots},640,1,1088]",
            f"bf16[{slots},1,1088,640]"]
  for line in text.splitlines():
    m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) (\S+?)\(", line)
    if m and m.group(2).startswith(tuple(leaves)):
      assert m.group(3) in ("parameter", "bitcast", "get-tuple-element",
                            "custom-call"), line
  assert not re.search(rf"\[{slots},{C},64,12832\]", text)
  # The two attends read and write the flat batch of 512 rows where it lies
  # (PR 48): their result is ``[T, H, r]``, no ``[slots, chunk x H, r]``
  # buffer in either split of its rows exists, and the queries are not
  # padded by a tile of rows.
  T = 512
  for H, r, W in ((128, 512, 576), (64, 1024, 1088)):
    assert f"bf16[{T},{H},{r}]" in text
    assert not re.search(
        rf"bf16\[{slots},{C * H},{r}\]|bf16\[{slots},{C},{H},{r}\]"
        rf"|bf16\[{slots * C // 8},8,{H},{r}\]|bf16\[{T + 8},{H},{W}\]", text)


def test_smallthinker_step_compiled_for_v5e_holds_its_kernels(one_chip):
  """The fused step of a two-layer cut (a full layer without positions, a
  window layer with rotary) of models/smallthinker.py at
  SmallThinker-21BA3B's widths and its cell's geometry, 48 slots x chunk 32
  at a context of 16,384, compiled for a described v5e as the engine builds
  it, at two widths: two ``kv_write`` (the full pair, the ring), one
  ``slot_attn`` (28 on 4 heads of 128 in rows) and two ``slot_attn_kvwin``
  (the decoding slots, then the prefilling ones), outside the conditionals
  and in the program ONCE (models/slot_core.py ``SplitLayer``); two ``moe_gmm`` a
  layer on either side of a conditional; no copy of a cache leaf, the rings
  ``[48, 4224, 512]`` among them; no ``[slots, chunk, heads, rows]`` score
  tensor."""
  from easyparallellibrary_tpu.models.smallthinker import (
      SmallThinker, SmallThinkerConfig)
  epl.init()
  slots, C = 48, 32
  cfg = SmallThinkerConfig(window_layout=(0, 1), rope_layout=(0, 1))
  step, args = _abstract_step(
      SmallThinker(cfg), slots, C, one_chip, moe_gmm_impl="pallas",
      _experts=True, kv_win_write_impl="pallas", kv_win_attn_impl="pallas")
  text = _compiled_text(step, *args)
  calls = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
  assert [calls(n) for n in ("kv_write", "slot_attn", "slot_attn_kvwin",
                             "moe_gmm")] == [2, 1, 2, 8], text.count(
                                 "tpu_custom_call")
  _assert_no_leaf_copied(text, args[1])
  kv = args[1]
  assert kv["block_0"]["attn"]["cached_key"].shape == (slots, 16416, 512)
  assert kv["block_1"]["attn"]["cached_key"].shape == (slots, 4224, 512)
  assert not re.search(rf"\[{slots},{C},28,(4224|16416)\]", text)
  assert not re.search(rf"\[{slots},28,{C},(4224|16416)\]", text)


def test_gigachat_step_compiled_for_v5e_holds_its_kernels(one_chip):
  """The fused step of a two-layer cut (a linear layer + dense, a full layer
  + 16 held experts of 256) of models/gigachat.py at GigaChat3.5's widths
  and its cell's geometry, 128 slots x chunk 32 at a context of 4096,
  compiled for a described v5e as the engine builds it, at two widths: one
  ``gdn_scan`` whose state operand aliases its output, one ``kv_write`` and
  TWO ``slot_attn`` over the latent leaf (PR 51: the plain leaf on the tile
  grid, the slots that feed several positions and then the decoding ones),
  each outside the conditionals and in the program ONCE
  (models/slot_core.py ``SplitLayer``); two ``moe_gmm``
  on either side of a conditional; no copy of the 537 MB matrix state nor
  of the latent leaf, which at 128 slots is allocated 4224 rows long so
  that the chip keeps it position-minor (serving/kv_cache.py
  ``kv_leaf_shape``); no ``while`` (the reference scan is one); the attend
  reads ``[T, 64, 576]`` and writes ``[T, 64, 512]`` where the flat batch
  lies, and no ``[128, 32, 64, ..]`` array of queries or of attended rows
  exists in any split of its dimensions."""
  from easyparallellibrary_tpu.models.gigachat import (
      GigaChat, GigaChatConfig)
  epl.init()
  slots, C = 128, 32
  cfg = GigaChatConfig(vocab_size=16032, num_layers=2,
                       full_attention_layers=(1,), first_k_dense=1,
                       experts_held=(0, 16), max_seq_len=4096)
  step, args = _abstract_step(GigaChat(cfg), slots, C, one_chip,
                              moe_gmm_impl="pallas", _experts=True,
                              gdn_scan_impl="pallas", _recurrent=True)
  text = _compiled_text(step, *args)
  calls = lambda name: len(re.findall(rf"%{name}[.\d]* = ", text))
  assert [calls(n) for n in ("gdn_scan", "kv_write", "slot_attn",
                             "moe_gmm")] == [1, 1, 2, 4], text.count(
                                 "tpu_custom_call")
  assert " while(" not in text
  _assert_no_leaf_copied(text, args[1])
  # Both launches stand in the entry computation: outside the conditionals.
  entry = text[text.index("\nENTRY "):]
  assert len(re.findall(r"%slot_attn[.\d]* = ", entry)) == 2
  T, H = 2048, 64
  for width in (576, 512):
    assert f"bf16[{T},{H},{width}]" in text
    assert not re.search(
        rf"bf16\[{slots},{C},{H},{width}\]|bf16\[{slots},{C * H},{width}\]"
        rf"|bf16\[{slots},1,{C * H},{width}\]"
        rf"|bf16\[{slots},1,{H},{C},{width}\]"
        rf"|bf16\[{slots * C // 8},8,{H},{width}\]"
        rf"|bf16\[{slots * C},{H * width}\]|bf16\[{T + 8},{H},{width}\]",
        text)
  kv = args[1]
  assert kv["block_0"]["linear"]["delta_state"].shape == (slots, 64, 128,
                                                          128)
  assert kv["block_0"]["linear"]["conv_state"].shape == (slots, 3, 16384)
  assert kv["block_1"]["latent"]["cached_latent"].shape == (slots, 4224, 1,
                                                            576)
  scan = [l for l in text.splitlines() if re.match(r"\s*%gdn_scan", l)][0]
  # operand 2 (after the two scalar-prefetch vectors) is the state,
  # output 1 the new state
  assert "{1}: (2, {})" in scan, scan


def _flat_cuts():
  """Two-layer cuts of the four decoders at their cells' widths and, but
  for the expert decoder's chunk, geometry: ``name -> (model, slots, C, engine attributes, kernel calls a
  two-width step (kv_write, slot_attn, ssm_scan, moe_gmm): once what a
  split layer's mixer calls, twice what stands in a conditional,
  models/slot_core.py ``slot_layers``), vocabulary)``."""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoe, GlmMoeConfig
  from easyparallellibrary_tpu.models.jamba import Jamba, JambaConfig
  from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
  experts = dict(moe_gmm_impl="pallas", _experts=True)
  return {
      "gpt2m": (GPT(GPTConfig(vocab_size=50304, num_layers=2, num_heads=16,
                              d_model=1024, d_ff=4096, max_seq_len=1024)),
                96, 16, {}, (4, 4, 0, 0), 50304),
      "jamba2": (Jamba(JambaConfig(num_layers=2, attn_layer_period=2,
                                   attn_layer_offset=1, vocab_size=32768)),
                 128, 8, dict(_recurrent=True, ssm_scan_impl="pallas"),
                 (1, 1, 2, 0), 32768),
      # at a chunk of 16: its cell's 96 x 8 has one width
      "glm47": (GlmMoe(GlmMoeConfig(num_layers=2, vocab_size=32768)),
                96, 16, experts, (2, 2, 0, 4), 32768),
      "lfm2": (Lfm2Moe(Lfm2MoeConfig(
          layer_types=("conv", "full_attention"), num_dense_layers=1,
          vocab_size=32768)), 128, 16, dict(_recurrent=True, **experts),
               (1, 1, 0, 4), 32768)}


@pytest.mark.parametrize("name", ["gpt2m", "jamba2", "glm47", "lfm2"])
def test_flat_step_for_v5e_multiplies_the_width_and_heads_the_slots(
    one_chip, name):
  """The plain fused step as the engine builds it, lowered and compiled
  for a described v5e: every matrix product of a position-wise layer has
  ``flat_width(slots, C)`` rows or, in the narrow side of the layers'
  conditional, ``narrow_width`` of them, as many of the one as of the
  other, and none ``slots x C``; the head's has ``slots`` and stands there
  once; a split layer's kernels stand there once, outside the conditionals,
  a whole layer's on either side; no cache leaf is copied.  The
  one exception is named: a Mamba layer's two small projections between
  its convolution and its scan (``x_proj``: 5120 -> 192, ``dt_proj``: 160
  -> 5120) stay with the ``[slots, C, ..]`` operands the scan takes, on
  either side."""
  from easyparallellibrary_tpu.serving.engine import (
      flat_width, narrow_width)
  epl.init()
  model, slots, C, engine, kernel_calls, vocab = _flat_cuts()[name]
  step, args = _abstract_step(model, slots, C, one_chip, **engine)
  T = flat_width(slots, C)
  assert T < slots * C
  dots = []     # (lhs shape, result's last dimension)
  for line in step.lower(*args).as_text().splitlines():
    m = re.search(r"stablehlo\.dot_general .*: \(tensor<([\dx]+)x\w+>, "
                  r"tensor<[\dx]+x\w+>\) -> tensor<([\dx]+)x\w+>", line)
    if m:
      dots.append(([int(d) for d in m.group(1).split("x")],
                   int(m.group(2).split("x")[-1])))
  heads = [lhs for lhs, out in dots if out == vocab]
  assert heads == [[slots, heads[0][-1]]], heads
  wide = [(lhs, out) for lhs, out in dots
          if lhs[0] == slots * C or lhs[:2] == [slots, C]]
  if name == "jamba2":
    assert sorted(out for _, out in wide) == [192, 192, 5120, 5120], wide
  else:
    assert not wide, wide
  assert sum(lhs[0] == T for lhs, _ in dots) >= 8, dots
  narrow = narrow_width(T, slots)
  assert narrow < T
  assert (sum(lhs[0] == narrow for lhs, _ in dots)
          == sum(lhs[0] == T for lhs, _ in dots)), dots
  text = _compiled_text(step, *args)
  calls = lambda kernel: len(re.findall(rf"%{kernel}[.\d]* = ", text))
  assert tuple(calls(k) for k in (
      "kv_write", "slot_attn", "ssm_scan", "moe_gmm")) == kernel_calls
  assert " while(" not in text
  _assert_no_leaf_copied(text, args[1])



@pytest.mark.parametrize("cell,chunk", [
    ("gpt2m-chat-steady", None), ("jamba2-3b-reasoning-backlog", None),
    ("glm47flash-agent-backlog", 16),
    ("lfm2moe-chat-steady", None), ("dots3note-longdoc-backlog", None),
    ("smallthinker-mixedlen-backlog", None),
    ("gigachat35-decode-backlog", None)])
def test_a_serving_cells_step_compiled_for_v5e_copies_no_leaf(one_chip, cell,
                                                              chunk):
  """The step of each serving configuration of the benchmark AT ITS FULL
  DEPTH and geometry, as the engine builds it, compiled for a described
  v5e: no cache leaf is copied, on either side of a conditional or
  outside one.  A split layer's leaves are no conditional's to change
  (models/slot_core.py ``SplitLayer``); GPT-2's K/V pairs do stand inside one,
  all 24 layers of them, where the compiler writes them in place: this is
  the guard of that (with every mixer inside one conditional it copied
  leaves of 100 to 540 MB in the four other configurations: PERF.md,
  PR 41).  The expert cell's own geometry has one width (96 x 8:
  tests/test_flat_step.py); its decoder is compiled at a chunk of 16,
  where it has two."""
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  from easyparallellibrary_tpu.serving.engine import (
      flat_width, narrow_width)
  from perfbench.harness import manifest as manifest_lib
  epl.init()
  man = manifest_lib.Manifest(root)
  cell_file = man.cell_file(cell)
  config_file = man.config_file(man.workload(cell)["config"])
  engine = {}
  if cell_file["runner"] == "serve":
    from perfbench.reference import gpt2
    from perfbench.runners import epl_gpt
    model = GPT(epl_gpt.gpt_config(gpt2.GPT2Config.from_file(config_file),
                                   cell_file["model"]))
  else:
    family = cell_file["family"]
    glue = importlib.import_module(f"perfbench.runners.epl_{family}")
    model, _ = glue.build_model(glue.ref_config(config_file),
                                cell_file["model"])
    engine = dict(_recurrent=kv_lib.has_recurrent_state(model.cfg))
    if family == "jamba":
      engine.update(ssm_scan_impl="pallas")
    else:
      engine.update(moe_gmm_impl="pallas", _experts=True)
    if family == "dots3_note":
      engine.update(dsa_index_impl="pallas")
    if family == "smallthinker":
      engine.update(kv_win_write_impl="pallas", kv_win_attn_impl="pallas")
    if family == "gigachat3_5":
      engine.update(gdn_scan_impl="pallas")
  slots = cell_file["engine"]["num_slots"]
  C = chunk or cell_file["engine"]["prefill_chunk"]
  T = flat_width(slots, C)
  assert narrow_width(T, slots) < T
  step, args = _abstract_step(model, slots, C, one_chip, **engine)
  text = _compiled_text(step, *args)
  # more than the sampler's two conditionals: the layers'
  assert text.count(" conditional(") > 2
  _assert_no_leaf_copied(text, args[1])


@pytest.mark.parametrize("B,S,H,D,dtype,causal", [
    (8, 1024, 20, 64, jnp.bfloat16, True),    # gpt2l-train-zero1-4chip, a chip
    (2, 1024, 4, 256, jnp.float32, False),    # the widest head still unrolled
    (2, 2048, 4, 64, jnp.float32, False),     # past _UNROLL_PAIRS: fori_loop
    (1, 8192, 2, 64, jnp.bfloat16, True),     # where the resident regime ends
], ids=["train_cell", "unrolled_f32_d256", "looped_f32", "resident_end"])
def test_flash_kernels_compile_for_v5e_inside_their_vmem(
    one_chip, monkeypatch, B, S, H, D, dtype, causal):
  """The three resident flash kernels (kernels/flash_attention.py), a
  whole head a grid step, compiled by Mosaic for a described v5e at the
  default tile: interpret mode knows no VMEM, and a head unrolled past
  ``_UNROLL_PAIRS`` is refused here for its temporaries (the float32 case
  at S 2048 compiles only because it is walked by ``fori_loop``)."""
  from easyparallellibrary_tpu.kernels import flash_attention
  fa = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  assert fa._resident_ok(S, S, D, jnp.dtype(dtype).itemsize)
  x = jax.ShapeDtypeStruct((B, S, H, D), dtype, sharding=one_chip)
  loss = lambda q, k, v: jnp.sum(
      flash_attention(q, k, v, causal=causal).astype(jnp.float32) ** 2)
  text = _compiled_text(jax.jit(jax.value_and_grad(loss, (0, 1, 2))),
                        x, x, x)
  # One Mosaic call a kernel; under autodiff alone the instruction's name
  # wraps the kernel's (``transpose_jvp_flash_dq__``).
  calls = [re.search(r"flash_(fwd|dkv|dq)", line.split(" = ")[0]).group(0)
           for line in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in line]
  assert sorted(calls) == ["flash_dkv", "flash_dq", "flash_fwd"], calls


def test_flash_rows_compile_for_v5e_from_the_one_projection(one_chip,
                                                            monkeypatch):
  """The train cell's call a chip, ``flash_attention_qkv`` on ``[8, 1024,
  3 x 1280]`` bfloat16: Mosaic takes the three column blocks of the one
  operand (blocks of whole 128-lane tiles, two heads each), and the
  program around the three kernels copies and transposes nothing."""
  fa = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  qkv = jax.ShapeDtypeStruct((8, 1024, 3840), jnp.bfloat16,
                             sharding=one_chip)
  loss = lambda qkv: jnp.sum(fa.flash_attention_qkv(
      qkv, 20, causal=True).astype(jnp.float32) ** 2)
  text = _compiled_text(jax.jit(jax.value_and_grad(loss)), qkv)
  calls = [line for line in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in line]
  assert sorted(re.search(r"flash_(fwd|dkv|dq)", c.split(" = ")[0]).group(0)
                for c in calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
  for call in calls:
    operands = call.split("operand_layout_constraints={")[1]
    assert operands.count("bf16[8,1024,3840]{2,1,0}") == 3, call
  assert " copy(" not in text and " transpose(" not in text


def _head_shaped(line, dims):
  """Whether an HLO instruction's result holds ``dims`` in any order and
  layout (dimensions of 1 aside)."""
  m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]", line)
  return bool(m) and sorted(
      int(d) for d in m.group(1).split(",") if d not in ("", "1")) == dims


def test_train_step_compiled_for_four_v5e_relays_out_no_head_array(
    four_chips, monkeypatch, layers=2):
  """`gpt2l-train-zero1-4chip`'s step at GPT-2 large's widths (1280 wide,
  20 heads of 64, S 1024, 8 rows a chip, remat with ``dots_flash``,
  ZeRO-1 over ``data:4``), cut to a few layers, as XLA plans it for a
  described ``v5e:2x2``.  At PR 48 the plan of the full depth held 473
  ``copy`` instructions of a ``bf16[8,20,1024,64]``-shaped array, 13.1 a
  layer, 47 ms of a 425 ms step (PERF.md section 6, PR 49): XLA keeps an
  array with a 64-wide minor dimension position-minor, a Mosaic call wants
  it row-major, and every q, k, v, o, dO, dQ, dK, dV paid the relayout,
  again under remat.  Now no array of that shape exists: no ``copy`` and
  no ``transpose`` of one, no copy of the projection's ``[8, 1024, 3840]``
  or of an ``[8, 1024, 1280]`` operand in an attention block either, and
  one ``flash_fwd``, one ``flash_dkv``, one ``flash_dq`` a layer."""
  import optax
  from easyparallellibrary_tpu.models.gpt import make_gpt_train_step
  from easyparallellibrary_tpu.parallel import TrainState, parallelize
  from easyparallellibrary_tpu.parallel.api import (
      batch_sharding, state_shardings)
  from easyparallellibrary_tpu.runtime import zero as zero_lib
  fa = importlib.import_module(
      "easyparallellibrary_tpu.kernels.flash_attention")
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  epl.init(epl.Config({"zero.level": "v1"}), devices=four_chips)
  with epl.replicate(1):
    model = GPT(GPTConfig(
        vocab_size=50304, num_layers=layers, num_heads=20, d_model=1280,
        d_ff=5120, max_seq_len=1024, dtype=jnp.bfloat16,
        param_dtype=jnp.float32, remat=True, attn_impl="pallas_flash",
        remat_policy="dots_flash", loss_chunk=256))
  mesh = epl.current_plan().build_mesh()
  assert dict(zip(mesh.axis_names, mesh.devices.shape))["data"] == 4
  tx = optax.adamw(3e-4, weight_decay=0.01)

  def init_fn(key):
    params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

  key = jax.random.PRNGKey(0)
  abstract = jax.eval_shape(init_fn, key)
  shardings = zero_lib.shard_opt_state(
      abstract, state_shardings(abstract, mesh), mesh, "v1")
  # the parameters are boxed: pair the leaves, not the trees
  leaves, treedef = jax.tree_util.tree_flatten(abstract)
  state = jax.tree_util.tree_unflatten(treedef, [
      jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
      for a, s in zip(leaves, jax.tree_util.tree_leaves(shardings))])
  batch = {"ids": jax.ShapeDtypeStruct((32, 1025), jnp.int32,
                                       sharding=batch_sharding(mesh))}
  step = parallelize(make_gpt_train_step(model), mesh, shardings)
  text = _compiled_text(step.jitted, state, batch, key)

  moved = [line for line in text.splitlines()
           if re.search(r" (copy|transpose)\(", line)]
  assert moved, "the plan's weight copies at least"
  heads = [line for line in moved if _head_shaped(line, [8, 20, 64, 1024])]
  assert not heads, heads[:3]
  activations = [line for line in moved
                 if re.match(r"\s*%\S+ = (bf16|f32)\[8,1024,(1280|3840)\]"
                             r"\S* copy\(", line) and "/attn/" in line]
  assert not activations, activations[:3]
  assert not re.findall(r"pad_add_fusion", text)
  calls = [re.search(r"flash_(fwd|dkv|dq)", line.split(" = ")[0])
           for line in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in line]
  assert sorted(m.group(0) for m in calls) == sorted(
      ["flash_fwd", "flash_dkv", "flash_dq"] * layers)
