"""KV cache layouts for continuous-batching inference — contiguous
slots and the paged block pool.

Two memory plans share this module.  The CONTIGUOUS layout is vLLM's
insight shrunk to one level: instead of allocating a fresh
``[B, max_seq_len, H, hd]`` cache per ``generate()`` call (models/gpt.py
legacy decode), ONE cache of ``num_slots`` request slots is allocated at
engine start and reused for the life of the server.  The PAGED layout
(``serving.paged.*``; docs/serving.md "Paged KV cache") is the full
two-level design: K/V lives in a pool of fixed-size blocks
(:func:`allocate_paged_kv_cache`), each slot owns a grown-on-demand
block list behind an on-device block table, and a host-side
:class:`BlockAllocator` (free list + refcounts) turns retired requests'
worst-case tail reservations into extra concurrent requests.  A slot is the unit of admission: a request owns
exactly one slot from admission to retirement, its write offset tracked
by a per-slot cursor (the cursor *vector* models/slot_core.py's
``slot_cache_attend`` consumes).  Eviction is free-list bookkeeping on
the host — no device work: stale K/V left by the previous occupant is
never attendable because the mask only exposes positions the current
request's own tokens have written (see slot_cache_attend's docstring;
tests/test_serving.py asserts the no-leakage property).

Eight kinds of per-slot state live in the contiguous cache, chosen per
layer from the model's own layer kinds (:func:`cache_leaves`).  Three are
rows under the slot's cursor: the K/V pair of an attention layer; the
LATENT leaf of multi-head latent attention (models/glm_moe.py), which is
ONE tensor a layer whose values are its keys' leading columns
(``check_latent_cache`` refuses what only a K/V pair is built for); and
the SPARSE_LATENT pair of a latent attention that selects its rows
(models/dots3_note.py): that latent leaf and, beside it, the indexer's
keys ``[num_slots, Lc, index_head_dim]``.  Two are rows that do NOT grow
with the served context, rings of ``cfg.ring_length(chunk)`` rows
(position ``p`` at row ``p mod R``): the WINDOW_LATENT leaf of a latent
attention behind a window, whose width and head count are its own, and
the WINDOW_KV pair of an attention layer behind a window
(models/smallthinker.py), ``cached_key`` / ``cached_value`` as an
attention layer's in everything but their length, beside ordinary
attention leaves in the same model: two kinds of K/V in one cache.  Three
are recurrent, with no position axis, and no cursor can roll them back
(serving/_capabilities.py ``check_recurrent_state``): a Mamba layer's
convolution window and float32 scan state (models/jamba.py), a CONV
layer's window alone, the whole state of a gated short convolution
(models/lfm2_moe.py), and a GATED_DELTA layer's convolution window and its
state, a float32 matrix a value head (models/gigachat.py: 4.19 MB a slot
and layer whatever the context, beside the LATENT leaf of the model's one
full attention in four: recurrent and latent leaves in one cache).

Placement: the cache is materialized directly into its sharded layout on
the mesh (same jit-with-out-shardings trick as
``create_sharded_train_state``), heads sharded over the tensor-parallel
``model`` axis so each TP shard holds exactly the head slice its
column-parallel QKV produces — cache reads/writes stay local, and GSPMD
inserts no resharding around the attention.

Layout note: the per-slot length is ``max_seq_len + chunk``
(:func:`cache_length`), one chunk longer than any request can grow.  The
fused step unconditionally writes a full ``chunk``-wide K/V window at
every slot's cursor (static shapes — masking, not shape, expresses
partial validity), so the window must never clamp against the end of the
buffer; ``jax.lax.dynamic_update_slice`` would otherwise shift the write
and corrupt earlier positions.

Order note: a K/V leaf is kept in ROWS, ``[num_slots, Lc, H_kv x hd]``
(heads folded into the minor dimension, head-major), wherever the heads'
width fills whole 128-lane tiles (:func:`kv_leaf_shape`: GPT-2 medium's
1024, the hybrid's one head of 128): the TPU then keeps a position's
values contiguous, the step's K/V chunk IS rows of that width, a window
is a stripe of rows, and both kernels read the leaf as it lies.  A
narrower leaf stays ``[num_slots, Lc, H_kv, hd]``, which the TPU keeps
POSITION-minor (``hd`` minor would pad its lanes), and so does the latent
leaf (576 wide, 4.5 lane tiles: padding it would cost memory the cell
does not have).  The order is one rule on the shape, read off the leaf's
rank by everything downstream (rank 3 = rows, rank 4 = positions): no
configuration field, environment variable or setter.  The paged pool
keeps ``[num_blocks, block_size, H, hd]``.
"""

from __future__ import annotations

import heapq
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.models.layer_kinds import (
    ATTENTION, CONV, FULL, GATED_DELTA, LATENT, MAMBA, SLIDING,
    SPARSE_LATENT, WINDOW_KV, WINDOW_LATENT)

# Layer kinds whose state is a recurrence's: no position axis, nothing a
# cursor can roll back.
RECURRENT = (MAMBA, CONV, GATED_DELTA)
# Layer kinds that keep latent rows in place of a K/V pair: one leaf under
# the cursor; that leaf and the indexer's keys; one ring.
LATENT_KINDS = (LATENT, SPARSE_LATENT, WINDOW_LATENT)
# The layer type whose ``cfg.latent_dims`` says a kind's sizes, where a
# model's latent kinds differ by layer (models/dots3_note.py).
_LAYER_TYPE = {SPARSE_LATENT: FULL, WINDOW_LATENT: SLIDING}

# Pool index of the reserved null/trash block: block tables default-fill
# with it (unallocated table slots resolve there), and the fused step's
# padding-token writes land there.  Never handed out by BlockAllocator;
# its rows are garbage-but-FINITE by construction (they only ever receive
# real projection outputs), which is all slot/paged attention requires of
# unattendable rows — and the resilient engine's sanitize pass zeroes it
# alongside any poisoned slot, since a NaN-params step poisons padding
# writes too.
NULL_BLOCK = 0


def cache_length(cfg, chunk: int) -> int:
  """Per-slot cache length: ``max_seq_len`` plus one chunk of slack so
  the fused step's fixed-width write window never clamps (module
  docstring)."""
  return cfg.max_seq_len + int(chunk)


def layer_kinds(cfg) -> Tuple[str, ...]:
  """Per layer, which state it keeps in a slot: what the model's config
  says (``cfg.layer_kinds()``, models/jamba.py), attention everywhere for
  a model that says nothing (GPT)."""
  kinds = getattr(cfg, "layer_kinds", None)
  return tuple(kinds()) if kinds is not None else (
      (ATTENTION,) * cfg.num_layers)


def recurrent_kinds(cfg) -> Tuple[str, ...]:
  """The kinds of recurrent state the model's layers keep (a Mamba layer's
  window and scan state, a conv layer's window, a gated delta layer's
  window and matrix state), in :data:`RECURRENT`'s order: what a refusal
  names."""
  kinds = layer_kinds(cfg)
  return tuple(kind for kind in RECURRENT if kind in kinds)


def has_recurrent_state(cfg) -> bool:
  """Whether some layer keeps a recurrence's state, which no cursor can
  roll back (serving/_capabilities.py refuses what would need to)."""
  return bool(recurrent_kinds(cfg))


def latent_kinds(cfg) -> Tuple[str, ...]:
  """The kinds of latent cache the model's layers keep, in
  :data:`LATENT_KINDS`' order: what a refusal names."""
  kinds = layer_kinds(cfg)
  return tuple(kind for kind in LATENT_KINDS if kind in kinds)


def has_latent_cache(cfg) -> bool:
  """Whether some layer keeps latent rows in place of a K/V pair
  (serving/_capabilities.py refuses what is built for pairs only)."""
  return bool(latent_kinds(cfg))


def has_kv_window(cfg) -> bool:
  """Whether some layer keeps its K/V pair as a ring behind a window,
  which overwrites what a cursor moved back would need again
  (serving/_capabilities.py refuses what would move one)."""
  return WINDOW_KV in layer_kinds(cfg)


def latent_leaf_shapes(cfg, kind: str, num_slots: int,
                       chunk: int) -> Dict[str, Tuple[int, ...]]:
  """The leaves a layer of a latent ``kind`` keeps, by name.  A model
  with one latent attention (models/glm_moe.py) says its row's width as
  ``cfg.latent_dim``; one whose layers differ (models/dots3_note.py) says
  each kind's through ``cfg.latent_dims(layer_type)``."""
  Lc = cache_length(cfg, chunk)
  if kind == LATENT:
    return {"cached_latent": (num_slots, Lc, 1, cfg.latent_dim)}
  if kind not in _LAYER_TYPE:
    raise ValueError(f"{kind!r} is no latent kind: {LATENT_KINDS}")
  dims = cfg.latent_dims(_LAYER_TYPE[kind])
  if kind == SPARSE_LATENT:
    return {"cached_latent": (num_slots, Lc, 1, dims.latent_dim),
            "cached_index": (num_slots, Lc, dims.indexer.head_dim)}
  return {"cached_latent": (num_slots, cfg.ring_length(chunk), 1,
                            dims.latent_dim)}


def delta_state_shape(cfg, num_slots: int) -> Tuple[int, int, int, int]:
  """A gated delta layer's state: ``[num_slots, Hv, dk, dv]``, value-head
  major, so that a head's matrix is one run of tiles."""
  return (num_slots, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
          cfg.linear_value_head_dim)


def kv_heads(cfg) -> Tuple[int, int]:
  """``(H_kv, hd)`` of one cache row under a cursor: the model's K/V head
  count (its query heads when it has no fewer) and the head size, the
  model's OWN where it says one (``cfg.head_dim``: models/smallthinker.py's
  28 heads of 128 on a ``d_model`` of 2560), ``d_model / num_heads``
  elsewhere; for a model with ONE latent attention one head of
  ``kv_lora_rank + qk_rope_head_dim`` values (a model whose latent kinds
  differ has no one answer: :func:`latent_leaf_shapes`)."""
  if LATENT in layer_kinds(cfg):
    return 1, cfg.latent_dim
  hd = getattr(cfg, "head_dim", None)
  if hd is None:
    if cfg.d_model % cfg.num_heads:
      raise ValueError(f"d_model {cfg.d_model} must divide into "
                       f"{cfg.num_heads} heads")
    hd = cfg.d_model // cfg.num_heads
  return getattr(cfg, "num_kv_heads", None) or cfg.num_heads, hd


def kv_leaf_shape(cfg, num_slots: int, chunk: int,
                  ring: bool = False) -> Tuple[int, ...]:
  """Shape of one leaf of rows under a cursor, in the order it is kept
  (module docstring, order note): a K/V pair whose heads' width ``H_kv x
  hd`` is a whole number of 128-lane tiles, in a 16-bit or 32-bit float,
  is kept in rows, ``[num_slots, Lc, H_kv x hd]``; every other leaf (a
  narrower pair, the latent leaf) ``[num_slots, Lc, H_kv, hd]``, and where
  the slots fill whole lane tiles with ``Lc`` up to whole lane tiles of rows
  (below).  ``ring``:
  a window layer's leaf of the same pair, ``cfg.ring_length(chunk)`` rows
  in place of ``Lc`` (kind :data:`WINDOW_KV`)."""
  if SPARSE_LATENT in layer_kinds(cfg):
    return latent_leaf_shapes(cfg, SPARSE_LATENT, num_slots,
                              chunk)["cached_latent"]
  Hkv, hd = kv_heads(cfg)
  lead = (num_slots, cfg.ring_length(chunk) if ring
          else cache_length(cfg, chunk))
  if (not has_latent_cache(cfg) and (Hkv * hd) % 128 == 0
      and jnp.dtype(cfg.dtype) in (jnp.dtype(jnp.bfloat16),
                                   jnp.dtype(jnp.float32))):
    return lead + (Hkv * hd,)
  if not ring and num_slots % 128 == 0:
    # A leaf kept in positions (a ring's length is the window's business).
    # The TPU keeps as an array's minor dimension the one that pads least.
    # Slots that fill whole lane tiles would win over a length that does
    # not (4096 + 32 rows pad to 4224), the leaf would lie SLOT-minor, and
    # every step would copy it to the position-minor order the write and
    # the attend read, and back (0.6 GB twice a step at 128 slots of 4128
    # rows: seen in the step compiled for a described v5e).  So such a
    # leaf is allocated up to whole lane tiles of rows: nothing reads the
    # rows past ``cache_length``, and no padding is left to prefer.
    lead = (num_slots, -(-lead[1] // 128) * 128)
  return lead + (Hkv, hd)


def cache_leaves(cfg, num_slots: int, chunk: int) -> Dict[str, Any]:
  """The slot cache as shapes: the pytree :func:`allocate_kv_cache`
  fills, one entry a layer BY ITS KIND — one manager for every kind of
  state:

  * attention: ``{"attn": {"cached_key", "cached_value"}}``, each
    ``[num_slots, Lc, H_kv x hd]`` (rows) or ``[num_slots, Lc, H_kv, hd]``
    (positions; :func:`kv_leaf_shape`) in the compute dtype, read under
    the slot's cursor;
  * Mamba: ``{"mamba": {"conv_state": [num_slots, d_conv - 1, d_inner]``
    in the compute dtype (the convolution's last inputs, which are
    produced in it), ``"ssm_state": [num_slots, d_state, d_inner]``
    float32}}``, no position axis: the whole state is the request's;
  * conv: ``{"conv": {"conv_state": [num_slots, conv_L_cache - 1,
    d_model]}}`` in the compute dtype (the last products the gated short
    convolution carries), the layer's whole state;
  * gated delta: ``{"linear": {"conv_state": [num_slots,
    linear_conv_kernel_dim - 1, 2 Hk dk + Hv dv]`` in the compute dtype
    (the last inputs of the convolution over queries, keys and values),
    ``"delta_state": [num_slots, Hv, dk, dv]`` float32}}`` (a matrix a
    value head, value-head major), no position axis;
  * latent: ``{"latent": {"cached_latent": [num_slots, Lc, 1,
    kv_lora_rank + qk_rope_head_dim]}}`` in the compute dtype, read under
    the slot's cursor as keys and, its leading ``kv_lora_rank`` columns,
    as values;
  * sparse latent: that leaf and ``"cached_index": [num_slots, Lc,
    index_head_dim]`` (kept in rows), the keys the layer's indexer scores
    to select what the attend reads;
  * window latent: ``{"latent": {"cached_latent": [num_slots, R, 1,
    width]}}``, a ring of ``R = cfg.ring_length(chunk)`` rows whatever the
    served context (:func:`latent_leaf_shapes`);
  * window K/V: ``{"attn": {"cached_key", "cached_value"}}`` as an
    attention layer's, each a ring of ``R`` rows (``[num_slots, R, H_kv x
    hd]`` or ``[num_slots, R, H_kv, hd]``) whatever the served context.
  """
  kinds = layer_kinds(cfg)
  if ATTENTION in kinds or LATENT in kinds:
    kv = jax.ShapeDtypeStruct(kv_leaf_shape(cfg, num_slots, chunk),
                              cfg.dtype)
  if WINDOW_KV in kinds:
    kv_ring = jax.ShapeDtypeStruct(
        kv_leaf_shape(cfg, num_slots, chunk, ring=True), cfg.dtype)
  out = {}
  for i, kind in enumerate(kinds):
    if kind == ATTENTION:
      out[f"block_{i}"] = {"attn": {"cached_key": kv, "cached_value": kv}}
    elif kind == WINDOW_KV:
      out[f"block_{i}"] = {"attn": {"cached_key": kv_ring,
                                    "cached_value": kv_ring}}
    elif kind == LATENT:
      out[f"block_{i}"] = {"latent": {"cached_latent": kv}}
    elif kind in (SPARSE_LATENT, WINDOW_LATENT):
      out[f"block_{i}"] = {"latent": {
          name: jax.ShapeDtypeStruct(shape, cfg.dtype) for name, shape in
          latent_leaf_shapes(cfg, kind, num_slots, chunk).items()}}
    elif kind == MAMBA:
      out[f"block_{i}"] = {"mamba": {
          "conv_state": jax.ShapeDtypeStruct(
              (num_slots, cfg.mamba_d_conv - 1, cfg.d_inner), cfg.dtype),
          "ssm_state": jax.ShapeDtypeStruct(
              (num_slots, cfg.mamba_d_state, cfg.d_inner), jnp.float32)}}
    elif kind == CONV:
      out[f"block_{i}"] = {"conv": {
          "conv_state": jax.ShapeDtypeStruct(
              (num_slots, cfg.conv_L_cache - 1, cfg.d_model), cfg.dtype)}}
    elif kind == GATED_DELTA:
      out[f"block_{i}"] = {"linear": {
          "conv_state": jax.ShapeDtypeStruct(
              (num_slots, cfg.linear_conv_kernel_dim - 1,
               cfg.linear_conv_dim), cfg.dtype),
          "delta_state": jax.ShapeDtypeStruct(
              delta_state_shape(cfg, num_slots), jnp.float32)}}
    else:
      raise ValueError(f"layer {i}: no cache for layer kind {kind!r}")
  return out


def kv_spec(rank: int = 4) -> P:
  """PartitionSpec of one K/V leaf: heads over the TP axis, slots and
  positions replicated.  Heads are dimension 2 of ``[num_slots, Lc, H,
  hd]`` and, head-major, the whole of dimension 2 of a leaf kept in rows
  (``rank`` 3), so the split over ``model`` cuts whole heads either
  way."""
  return P(*((None, None, constants.MODEL_AXIS) + (None,) * (rank - 3)))


def slot_axis(mesh: Optional[Mesh]) -> Optional[Tuple[str, int]]:
  """``(name, size)`` of the mesh axis an engine's slots (and a model's
  held experts) are DIVIDED over: the ``expert`` axis where it is larger
  than one; ``None`` on every other mesh and without one.  Slot ``s`` of
  ``num_slots`` lives on chip ``s // (num_slots / size)``; the fused step
  runs inside a ``shard_map`` over the axis (serving/engine.py), so each
  chip holds whole leaves of its own slots and every kernel rule is
  resolved for what a chip holds (:func:`step_lowerings`)."""
  if mesh is None:
    return None
  size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
      constants.EXPERT_AXIS, 1)
  return (constants.EXPERT_AXIS, size) if size > 1 else None


def kv_cache_shardings(cfg, mesh: Optional[Mesh]):
  """(kv_shardings_pytree, cursor_sharding) matching
  :func:`allocate_kv_cache`'s structure, or (None, None) without a mesh.

  On a mesh that divides the slots (:func:`slot_axis`) every leaf and the
  cursors are split over that axis along their leading, slots dimension
  and nothing else applies.

  K/V heads shard over ``model`` only when the cache's head count
  actually divides the axis; otherwise the leaf is replicated (a 1-sized
  or absent model axis degrades to replication anyway).  K/V is told from
  everything else by its key (``attn``), not its rank (a leaf kept in
  rows has the rank recurrent state has).  Recurrent state is replicated:
  a sharded state is not built; so is a latent leaf (one head).
  """
  if mesh is None:
    return None, None
  divided = slot_axis(mesh)
  if divided is not None:
    by_slot = NamedSharding(mesh, P(divided[0]))
    return jax.tree_util.tree_map(lambda leaf: by_slot,
                                  cache_leaves(cfg, 1, 1)), by_slot
  sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
  tp = sizes.get(constants.MODEL_AXIS, 1)
  split = (tp > 1 and ATTENTION in layer_kinds(cfg)
           and kv_heads(cfg)[0] % tp == 0)
  rep = NamedSharding(mesh, P())

  def place(path, leaf):
    if split and any(getattr(k, "key", None) == "attn" for k in path):
      return NamedSharding(mesh, kv_spec(len(leaf.shape)))
    return rep

  kv = jax.tree_util.tree_map_with_path(place, cache_leaves(cfg, 1, 1))
  return kv, rep


def _under_cursor(cfg) -> bool:
  """Whether some layer keeps rows under a cursor (a K/V pair or a latent
  leaf): what the window write and the attend work on."""
  kinds = layer_kinds(cfg)
  return ATTENTION in kinds or has_latent_cache(cfg)


def _one_impl(impls) -> str:
  """The one lowering a step's calls of a kernel share: the kernel only
  if every leaf it is called on takes it."""
  impls = set(impls)
  return impls.pop() if len(impls) == 1 else "reference"


def _mixed_latent(cfg) -> bool:
  """Whether the model's latent kinds differ by layer
  (models/dots3_note.py): each kind's leaves are resolved apart."""
  return any(kind in _LAYER_TYPE for kind in layer_kinds(cfg))


def kv_write_impl(cfg, num_slots: int, chunk: int,
                  mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the fused step's window write into the cache
  :func:`allocate_kv_cache` builds for the same arguments — the
  dispatch rule of kernels/kv_write.py applied to its K/V leaf: the
  Pallas kernel on a TPU when the leaf sits whole on one chip and fits
  the kernel's tiles, ``vmap(dynamic_update_slice)`` everywhere else.
  Resolved once by whoever builds a step over the cache; ``None`` for a
  model without an attention layer.  A latent leaf is written by the same
  kernel in its one-leaf form."""
  from easyparallellibrary_tpu.kernels.kv_write import (
      resolve_kv_write_impl)
  if not _under_cursor(cfg):
    return None
  sharded = mesh is not None and mesh.size > 1
  if _mixed_latent(cfg):
    # Every leaf of every latent kind, a window layer's as a ring.
    return _one_impl(
        resolve_kv_write_impl(shape, cfg.dtype, chunk, sharded=sharded,
                              ring=kind == WINDOW_LATENT)
        for kind in latent_kinds(cfg) for shape in
        latent_leaf_shapes(cfg, kind, num_slots, chunk).values())
  return resolve_kv_write_impl(
      kv_leaf_shape(cfg, num_slots, chunk), cfg.dtype, chunk,
      sharded=sharded)


def slot_attn_impl(cfg, num_slots: int, chunk: int,
                   mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the fused step's attend over the same K/V leaf —
  the dispatch rule of kernels/slot_attention.py, resolved once like
  :func:`kv_write_impl`: the kernel that reads each slot's live rows on
  a TPU when the leaf sits whole on one chip and fits its blocks, the
  einsums over every row everywhere else; ``None`` for a model without
  an attention layer."""
  from easyparallellibrary_tpu.kernels.slot_attention import (
      resolve_slot_attn_impl, resolve_tile_attn_impl)
  if not _under_cursor(cfg):
    return None
  sharded = mesh is not None and mesh.size > 1
  if _mixed_latent(cfg):
    # The selected and the windowed forms of the one-leaf attend.
    impls = []
    for kind in latent_kinds(cfg):
      dims = cfg.latent_dims(_LAYER_TYPE[kind])
      shape = latent_leaf_shapes(cfg, kind, num_slots,
                                 chunk)["cached_latent"]
      impls.append(resolve_tile_attn_impl(
          shape, cfg.dtype, chunk, dims.num_heads, dims.kv_lora_rank,
          ring=kind == WINDOW_LATENT, sharded=sharded))
    return _one_impl(impls)
  return resolve_slot_attn_impl(
      kv_leaf_shape(cfg, num_slots, chunk), cfg.dtype, chunk, cfg.num_heads,
      sharded=sharded, head_dim=kv_heads(cfg)[1])


def slot_attn_walk(cfg, num_slots: int, chunk: int,
                   tile_out: Optional[str] = None):
  """``(granule, length)`` of ``slot_attn``'s walk over this model's leaf
  (kernels/slot_attention.py:walk_geometry): what
  ``serving/attn_rows_read`` rounds each live bound up to, and holds it
  to.  ``None`` for a model none of whose layers takes that walk: no rows
  under a cursor, every such layer a selected or windowed latent one, or
  (``tile_out``, the step's :func:`tile_attn_out`, ``"flat"``) a plain
  latent leaf that the tile grid serves."""
  from easyparallellibrary_tpu.kernels.slot_attention import walk_geometry
  if not _under_cursor(cfg) or _mixed_latent(cfg) or tile_out == "flat":
    return None
  return walk_geometry(kv_leaf_shape(cfg, num_slots, chunk), cfg.dtype)


def kv_win_write_impl(cfg, num_slots: int, chunk: int,
                      mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the ring write of a window layer's K/V pair
  (:data:`WINDOW_KV`): ``kv_write``'s rule on the ring's shape with
  ``ring=True``, resolved apart from the full layers' write (one may take
  its kernel where the other declines); ``None`` for a model without such
  a layer."""
  if not has_kv_window(cfg):
    return None
  from easyparallellibrary_tpu.kernels.kv_write import (
      resolve_kv_write_impl)
  return resolve_kv_write_impl(
      kv_leaf_shape(cfg, num_slots, chunk, ring=True), cfg.dtype, chunk,
      sharded=mesh is not None and mesh.size > 1, ring=True)


def kv_win_attn_impl(cfg, num_slots: int, chunk: int,
                     mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the attend over that ring (``slot_attn_kvwin``, the
  pair form of the tile-grid attend of kernels/slot_attention.py),
  resolved like :func:`kv_win_write_impl`; ``None`` for a model without a
  window layer over K/V pairs."""
  if not has_kv_window(cfg):
    return None
  from easyparallellibrary_tpu.kernels.slot_attention import (
      resolve_tile_attn_impl)
  return resolve_tile_attn_impl(
      kv_leaf_shape(cfg, num_slots, chunk, ring=True), cfg.dtype, chunk,
      cfg.num_heads, kv_heads(cfg)[1], ring=True,
      sharded=mesh is not None and mesh.size > 1)


def dsa_index_impl(cfg, num_slots: int, chunk: int,
                   mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the index scores of a layer that selects what it
  attends — the dispatch rule of kernels/dsa_index.py applied to its index
  leaf, resolved once like :func:`kv_write_impl`; ``None`` for a model
  without such a layer."""
  if SPARSE_LATENT not in layer_kinds(cfg):
    return None
  from easyparallellibrary_tpu.kernels.dsa_index import (
      resolve_dsa_index_impl)
  return resolve_dsa_index_impl(
      latent_leaf_shapes(cfg, SPARSE_LATENT, num_slots,
                         chunk)["cached_index"],
      cfg.dtype, chunk, cfg.latent_dims(FULL).indexer.num_heads,
      sharded=mesh is not None and mesh.size > 1)


def ssm_scan_impl(cfg, num_slots: int, chunk: int,
                  mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the fused step's selective scan over the recurrent
  state :func:`allocate_kv_cache` builds — the dispatch rule of
  kernels/ssm_scan.py applied to its ``ssm_state`` leaf, resolved once
  like :func:`kv_write_impl`; ``None`` for a model without a Mamba layer
  (a conv layer's window is advanced by selects XLA fuses: no kernel)."""
  if MAMBA not in layer_kinds(cfg):
    return None
  from easyparallellibrary_tpu.kernels.ssm_scan import (
      resolve_ssm_scan_impl)
  return resolve_ssm_scan_impl(
      (num_slots, cfg.mamba_d_state, cfg.d_inner), cfg.dtype, chunk,
      sharded=mesh is not None and mesh.size > 1)


def gdn_scan_impl(cfg, num_slots: int, chunk: int,
                  mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the fused step's gated delta rule over the matrix
  state :func:`allocate_kv_cache` builds — the dispatch rule of
  kernels/gdn_scan.py applied to its ``delta_state`` leaf and the
  convolution's width, resolved once like :func:`kv_write_impl`; ``None``
  for a model without such a layer."""
  if GATED_DELTA not in layer_kinds(cfg):
    return None
  from easyparallellibrary_tpu.kernels.gdn_scan import (
      resolve_gdn_scan_impl)
  return resolve_gdn_scan_impl(
      delta_state_shape(cfg, num_slots), cfg.linear_conv_dim, cfg.dtype,
      chunk, sharded=mesh is not None and mesh.size > 1)


def moe_gmm_impl(cfg, num_slots: int, chunk: int,
                 mesh: Optional[Mesh] = None) -> Optional[str]:
  """The lowering of the fused step's grouped matmuls over a dropless
  expert layer's sorted assignments (``num_slots x chunk x
  num_experts_per_tok`` rows) — the dispatch rule of kernels/moe_gmm.py
  applied to both of a layer's products (gate and up as one, then down),
  resolved once like :func:`kv_write_impl`: the kernel only if it takes
  both; ``None`` for a model without such a layer."""
  E = getattr(cfg, "n_routed_experts", 0)
  if not E:
    return None
  held = getattr(cfg, "experts_held", None)
  if held is not None:
    E = held[1]          # the stacks hold this chip's experts
  from easyparallellibrary_tpu.kernels.moe_gmm import resolve_moe_gmm_impl
  rows = num_slots * chunk * cfg.num_experts_per_tok
  D, F = cfg.d_model, cfg.moe_d_ff
  sharded = mesh is not None and mesh.size > 1
  return _one_impl(resolve_moe_gmm_impl((rows, k), (E, k, n), cfg.dtype,
                                        sharded=sharded)
                   for k, n in ((D, 2 * F), (F, D)))


def tile_attn_out(cfg, impl: Optional[str], narrower: bool, num_slots: int,
                  chunk: int) -> Optional[str]:
  """Where the attends that run on the tile grid of
  kernels/slot_attention.py read their queries and write their result,
  given what :func:`slot_attn_impl` resolved and whether the flat batch is
  ``narrower`` than ``num_slots x chunk``
  (kernels/slot_attention.py:tile_attn_out, which the mixer asks too):
  ``"flat"``, the step's token-flat batch where it lies; ``"slots"``,
  arrays in ``[slots, chunk]`` order gathered from it and back; ``None``
  for a model with no such attend.  The layers that select their rows or
  sit behind a latent window always run there; a PLAIN latent leaf
  (:data:`LATENT`) does where ``plain_tile_form`` holds for the leaf
  :func:`kv_leaf_shape` gives ``num_slots`` and ``chunk`` (then ``"flat"``:
  the tile kernel can tile it, a chunk is more than one tile of positions
  and the batch is narrower), and keeps the first grid, ``None`` here,
  everywhere else.  No lowering a caller could name: what the step does,
  derived from what the other rules resolved."""
  from easyparallellibrary_tpu.kernels import slot_attention
  kinds = layer_kinds(cfg)
  if SPARSE_LATENT in kinds or WINDOW_LATENT in kinds:
    return slot_attention.tile_attn_out(impl, narrower)
  if LATENT in kinds:
    dims = cfg.latent_dims()
    if slot_attention.plain_tile_form(
        impl, narrower, kv_leaf_shape(cfg, num_slots, chunk), cfg.dtype,
        chunk, dims.num_heads, dims.kv_lora_rank):
      return "flat"
  return None


def attn_tile(cfg, lowerings: Dict[str, Optional[str]],
              chunk: int) -> Optional[Tuple[int, int]]:
  """``(tile, decode)`` of the step's one-leaf attends where they run on
  the tile grid (the kernel resolved, and the layers select their rows, sit
  behind a latent window or are a plain leaf the grid serves): the chunk
  positions a live tile of a slot that feeds several is worked as, and
  those a slot that feeds one costs (1 where a chunk of tiles has the
  decoding slots' launch, a whole tile elsewhere).  What
  ``serving/attn_tile_positions`` counts by; ``None`` where no layer runs
  there."""
  from easyparallellibrary_tpu.kernels import slot_attention
  if lowerings.get("slot_attn_impl") not in ("pallas", "interpret"):
    return None
  if _mixed_latent(cfg):
    heads = cfg.latent_dims(_LAYER_TYPE[latent_kinds(cfg)[0]]).num_heads
  elif lowerings.get("tile_attn_out") == "flat":
    heads = cfg.latent_dims().num_heads
  else:
    return None
  tile = slot_attention.tile_positions(chunk, heads)
  return tile, 1 if slot_attention.decodes_apart(chunk) else tile


# The rules above in the order every record of a step's lowerings is kept
# in.  A rule's name is at once the decoders' keyword, the trace metadata's
# suffix (``serving/<name>``) and the diagnostic bundle's key.
_RULES = (kv_write_impl, slot_attn_impl, kv_win_write_impl, kv_win_attn_impl,
          dsa_index_impl, ssm_scan_impl, gdn_scan_impl, moe_gmm_impl)


def step_lowerings(cfg, num_slots: int, chunk: int,
                   mesh: Optional[Mesh] = None,
                   width: Optional[int] = None) -> Dict[str, Optional[str]]:
  """What a fused step over the cache :func:`allocate_kv_cache` builds for
  the same arguments is lowered to: each rule's answer under the rule's
  name, in :data:`_RULES`' order; ``None`` where the model has no layer the
  rule is about; on a mesh that divides the slots (:func:`slot_axis`) each
  rule is applied to one chip's share of them.  THE record whoever builds
  such a step resolves once (the
  engine, a draft model's rollout) and hands ``slot_step_logits`` the
  entries of that are not ``None`` (:func:`resolved`).  Last, under
  ``tile_attn_out``, what follows from them and from the flat batch's
  ``width`` (a chip's rows; ``None``: every position of every slot) with
  no rule of its own: :func:`tile_attn_out`."""
  divided = slot_axis(mesh)
  if divided is not None:
    # Inside the step's ``shard_map`` a kernel is handed what ONE chip
    # holds, whole: its share of the slots, and no partitioner in the way.
    num_slots, mesh = num_slots // divided[1], None
  record = {rule.__name__: rule(cfg, num_slots, chunk, mesh)
            for rule in _RULES}
  record["tile_attn_out"] = tile_attn_out(
      cfg, record["slot_attn_impl"],
      width is not None and width < num_slots * chunk, num_slots, chunk)
  return record


def resolved(lowerings: Dict[str, Optional[str]]) -> Dict[str, str]:
  """The lowerings of a :func:`step_lowerings` record some rule resolved:
  the keywords ``slot_step_logits`` is handed (a model takes those of its
  own kinds of layer and no others)."""
  return {rule.__name__: lowerings[rule.__name__] for rule in _RULES
          if lowerings.get(rule.__name__) is not None}


def recorded(lowerings: Dict[str, Optional[str]]) -> Dict[str, str]:
  """Every entry of the record that says something, :func:`resolved`'s
  and what follows from them: the metadata a run records and the line an
  engine logs."""
  return {name: said for name, said in lowerings.items() if said is not None}


def allocate_kv_cache(cfg, num_slots: int, chunk: int,
                      mesh: Optional[Mesh] = None
                      ) -> Tuple[Dict[str, Any], jax.Array]:
  """Preallocate the slot cache of a model config.

  Returns ``(kv, cursors)``: ``kv`` is a pytree shaped exactly like the
  ``"cache"`` collection the model's slot-mode decode reads/writes
  (:func:`cache_leaves`: K/V leaves for attention layers, convolution and
  scan state for Mamba layers, the window of a conv layer), all zero; ``cursors`` the int32
  ``[num_slots]`` write-offset vector (all zero).  With a mesh, every
  leaf materializes already sharded (jit + out_shardings — no
  host-memory spike, no transfer).
  """
  if num_slots < 1:
    raise ValueError(f"num_slots must be >= 1: {num_slots}")
  if chunk < 1:
    raise ValueError(f"prefill chunk must be >= 1: {chunk}")
  leaves = cache_leaves(cfg, num_slots, chunk)
  kv_shardings, cur_sharding = kv_cache_shardings(cfg, mesh)

  def build():
    kv = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), leaves)
    return kv, jnp.zeros((num_slots,), jnp.int32)

  if kv_shardings is None:
    # epl-lint: disable=recompile-hazard — allocation-time one-shot:
    # runs once per engine construction (jit materializes the zeros
    # DIRECTLY in their layout, never through a host buffer)
    return jax.jit(build)()
  # epl-lint: disable=recompile-hazard — same one-shot allocation, mesh
  # path (out_shardings places each leaf as it is created)
  return jax.jit(build, out_shardings=(kv_shardings, cur_sharding))()


def cache_layout(cfg, num_slots: int, chunk: int) -> Dict[str, Any]:
  """What the slot cache holds, by kind of state: bytes and leaves of
  K/V (under a cursor), of recurrent state (no position axis: ``state_*``
  counts a Mamba layer's two leaves, a conv layer's one and a gated delta
  layer's two, whatever else the model keeps beside them) and, for a
  model that has them, of latent rows (under a cursor, one leaf a layer),
  of an indexer's keys (``index_*``, under the cursor beside a latent
  leaf) and of window rings (``window_*``, latent rows or K/V pairs, whose
  bytes do not depend on the served context); and ``kv_order``, the order the leaves under a
  cursor are kept in
  (``"rows"`` or ``"positions"``: module docstring, order note; ``None``
  for a model that keeps none), which says which form of the window write
  and of the attend a step runs.  The engine records it (trace metadata
  ``serving/cache_layout``)."""
  names = {ATTENTION: "kv", MAMBA: "state", CONV: "state",
           GATED_DELTA: "state", LATENT: "latent", SPARSE_LATENT: "latent",
           WINDOW_LATENT: "window", WINDOW_KV: "window"}
  kinds = layer_kinds(cfg)
  groups = ["kv", "state"]
  groups += ["latent"] * (LATENT in kinds or SPARSE_LATENT in kinds)
  groups += ["index"] * (SPARSE_LATENT in kinds)
  groups += ["window"] * (WINDOW_LATENT in kinds or WINDOW_KV in kinds)
  out = {f"{name}_{what}": 0
         for name in groups for what in ("bytes", "leaves")}
  leaves = cache_leaves(cfg, num_slots, chunk)
  for i, kind in enumerate(kinds):
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        leaves[f"block_{i}"]):
      name = "index" if path[-1].key == "cached_index" else names[kind]
      out[f"{name}_bytes"] += (int(np.prod(leaf.shape))
                               * jnp.dtype(leaf.dtype).itemsize)
      out[f"{name}_leaves"] += 1
  out["kv_order"] = None if not _under_cursor(cfg) else (
      "rows" if len(kv_leaf_shape(cfg, num_slots, chunk)) == 3
      else "positions")
  return out


def cache_bytes(cfg, num_slots: int, chunk: int) -> int:
  """Total cache footprint in bytes (every leaf of every layer: K and V,
  latent and index rows, rings, convolution windows, scan and delta state)
  — the number the admission knobs trade against HBM."""
  layout = cache_layout(cfg, num_slots, chunk)
  return sum(v for k, v in layout.items() if k.endswith("_bytes"))


# ------------------------------------------------------------ paged cache --


def blocks_per_slot(cfg, block_size: int) -> int:
  """Block-table width: virtual context rows per slot == ``max_seq_len``
  exactly.  ``block_size`` must divide ``max_seq_len``: the paged
  attend's softmax/V reductions then run over the SAME length as the
  ``generate(use_cache=True)`` oracle's cache, which is what keeps the
  paged engine greedy bit-exact (a longer padded length regroups XLA's
  vectorized partial sums — measured 1-ulp drift — even though the tail
  terms are exact zeros)."""
  if block_size < 1:
    raise ValueError(f"block_size must be >= 1: {block_size}")
  if cfg.max_seq_len % block_size:
    raise ValueError(
        f"serving.paged.block_size {block_size} must divide max_seq_len "
        f"{cfg.max_seq_len}: the paged attend's reduction length "
        f"(blocks_per_slot * block_size) must equal the oracle's cache "
        f"length for the greedy bit-exactness contract to hold")
  return cfg.max_seq_len // block_size


def default_num_blocks(cfg, num_slots: int, block_size: int) -> int:
  """Auto pool size: every slot can reach ``max_seq_len`` (plus the null
  block) — byte-parity with the contiguous layout, so enabling paging is
  never a capacity REGRESSION by default.  The memory win is opt-in:
  size ``serving.paged.num_blocks`` below this (or raise ``num_slots``
  above the contiguous budget) and on-demand allocation turns unused
  tail capacity into extra concurrent requests."""
  return num_slots * blocks_per_slot(cfg, block_size) + 1


def allocate_paged_kv_cache(cfg, num_blocks: int, block_size: int,
                            mesh: Optional[Mesh] = None) -> Dict[str, Any]:
  """Preallocate the paged K/V pools for a GPT config.

  Returns the ``"cache"``-collection pytree GPT's paged decode
  reads/writes: ``{"block_i": {"attn": {"cached_key"/"cached_value":
  [num_blocks, block_size, H, hd]}}}``.  Heads sit at the same axis
  index as in the slot layout of either order (dimension 2, head-major),
  so :func:`kv_cache_shardings` serves both.
  Block ``NULL_BLOCK`` is the reserved trash block (module constant).
  """
  mb = blocks_per_slot(cfg, block_size)
  if num_blocks < mb + 1:
    raise ValueError(
        f"num_blocks {num_blocks} cannot hold even one full-length "
        f"request: need >= blocks_per_slot + 1 = {mb + 1} (one null "
        f"block plus max_seq_len/block_size per request)")
  if cfg.d_model % cfg.num_heads:
    raise ValueError(f"d_model {cfg.d_model} must divide into "
                     f"{cfg.num_heads} heads")
  H, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
  shape = (num_blocks, block_size, H, hd)
  kv_shardings, _ = kv_cache_shardings(cfg, mesh)

  def build():
    leaf = lambda: jnp.zeros(shape, cfg.dtype)
    return {f"block_{i}": {"attn": {"cached_key": leaf(),
                                    "cached_value": leaf()}}
            for i in range(cfg.num_layers)}

  if kv_shardings is None:
    # epl-lint: disable=recompile-hazard — allocation-time one-shot
    # (see allocate_kv_cache: pool zeros materialize in place, once)
    return jax.jit(build)()
  # epl-lint: disable=recompile-hazard — same one-shot allocation on
  # the mesh path
  return jax.jit(build, out_shardings=kv_shardings)()


def paged_cache_bytes(cfg, num_blocks: int, block_size: int) -> int:
  """Paged-pool footprint in bytes (both K and V, all layers) — the
  paged twin of :func:`cache_bytes`, and the number the long-tail
  benchmark holds fixed while raising concurrency."""
  H, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
  per_leaf = num_blocks * block_size * H * hd
  return 2 * cfg.num_layers * per_leaf * jnp.dtype(cfg.dtype).itemsize


class BlockAllocator:
  """Host-side free-list + refcounts over the paged K/V pool.

  Lowest-free-first (a heap) keeps block assignment deterministic for a
  given request order, mirroring :class:`SlotAllocator`.  Refcounts
  carry the copy-on-write prefix sharing that
  ``serving/prefix_cache.py`` builds on this pool: a block starts at
  refcount 1 (its allocating slot), the radix tree adds one reference
  when it registers the block's content, and every slot that maps the
  block through a prefix match adds another — so a block's count is
  ``owning slot + tree entry + sharers``, and ``decref`` returns it to
  the free list only when the LAST holder lets go.  Shared blocks are
  read-only by construction (matching stops strictly before the first
  divergent/partial block; writes always land past the shared region —
  prefix_cache.py's COW rule), so sharing needs no device copy.  Block
  ``NULL_BLOCK`` is reserved, never allocated and NEVER shared: its
  rows are garbage by design (trash writes land there), so the tree
  refuses to register it.
  """

  def __init__(self, num_blocks: int, block_size: int):
    if num_blocks < 2:
      raise ValueError(f"num_blocks must be >= 2 (one null block plus at "
                       f"least one allocatable): {num_blocks}")
    if block_size < 1:
      raise ValueError(f"block_size must be >= 1: {block_size}")
    self.num_blocks = num_blocks
    self.block_size = block_size
    self._free: List[int] = list(range(1, num_blocks))
    heapq.heapify(self._free)
    self._ref: Dict[int, int] = {}

  @property
  def num_free(self) -> int:
    return len(self._free)

  @property
  def num_used(self) -> int:
    return len(self._ref)

  def alloc(self) -> Optional[int]:
    """Claim the lowest free block at refcount 1, or None when empty."""
    if not self._free:
      return None
    blk = heapq.heappop(self._free)
    self._ref[blk] = 1
    return blk

  def incref(self, block: int) -> None:
    """Add a reference (prefix-cache tree entries and COW prefix
    sharers: serving/prefix_cache.py)."""
    if block not in self._ref:
      raise ValueError(f"block {block} is not allocated")
    self._ref[block] += 1

  def decref(self, block: int) -> None:
    """Drop a reference; the block returns to the free list at zero."""
    if block not in self._ref:
      raise ValueError(f"block {block} is not allocated (double free?)")
    self._ref[block] -= 1
    if self._ref[block] == 0:
      del self._ref[block]
      heapq.heappush(self._free, block)

  def refcount(self, block: int) -> int:
    return self._ref.get(block, 0)

  def fragmentation(self, used_tokens: int) -> float:
    """Internal fragmentation: the fraction of allocated token capacity
    no resident token occupies (last-block slack across slots).  0.0
    when nothing is allocated."""
    cap = self.num_used * self.block_size
    if cap <= 0:
      return 0.0
    return max(0.0, 1.0 - used_tokens / cap)

  def __repr__(self):
    return (f"BlockAllocator(num_blocks={self.num_blocks}, "
            f"block_size={self.block_size}, free={self.num_free}, "
            f"used={self.num_used})")


class SlotAllocator:
  """Host-side free-list over the cache's request slots.

  Lowest-free-first allocation keeps slot assignment deterministic for a
  given request order (exactness tests replay schedules).  Freeing does
  no device work: the cache mask makes stale K/V unreachable, so
  "eviction" is purely returning the slot id to the list.
  """

  def __init__(self, num_slots: int):
    if num_slots < 1:
      raise ValueError(f"num_slots must be >= 1: {num_slots}")
    self.num_slots = num_slots
    self._free: List[int] = list(range(num_slots))
    self._used = set()

  @property
  def num_free(self) -> int:
    return len(self._free)

  def alloc(self) -> Optional[int]:
    """Claim the lowest free slot, or None when full."""
    if not self._free:
      return None
    slot = min(self._free)
    self._free.remove(slot)
    self._used.add(slot)
    return slot

  def free(self, slot: int):
    if slot not in self._used:
      raise ValueError(f"slot {slot} is not allocated (double free?)")
    self._used.remove(slot)
    self._free.append(slot)

  def __repr__(self):
    return (f"SlotAllocator(num_slots={self.num_slots}, "
            f"free={sorted(self._free)})")
