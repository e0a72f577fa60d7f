"""The slot-mode core: the device entry of every served decoder and what
its layers share to step through a slot cache.

Below every decoder (``models/gpt.py`` and its five siblings import it; it
imports none of them and nothing of ``serving/``): the write-then-attend
over a K/V pair under per-slot cursors (:func:`slot_cache_attend`) and its
paged twin, the map between a step's ``[slots, C]`` chunk positions and the
token-flat batch the position-wise layers run on (:class:`SlotRows`,
:func:`slot_rows`, :func:`flat_ids`), the stack of layers at one or two
widths of that batch (:func:`slot_layers`, :class:`SplitLayer`,
:func:`child_of`) and the two entries the serving engine calls
(:func:`slot_step_logits`, :func:`paged_step_logits`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

def slot_cache_attend(q, k, v, cached_k, cached_v, cursors, dtype,
                      write_impl=None, attn_impl=None, num_valid=None):
  """Slot-indexed KV-cache attention — the shared core of the legacy
  single-request decode step and the serving engine's fused
  prefill+decode step (serving/engine.py).

  ``q``/``k``/``v`` are ``[B, C, H, hd]`` projections of this step's C
  new tokens per slot (C == 1 for pure decode), ``cached_k``/``cached_v``
  are per-slot caches in either order (``[B, Lc, H x hd]``, kept in rows,
  or ``[B, Lc, H, hd]``: serving/kv_cache.py's order note; the write and
  the attend read the order off the leaf's rank), and ``cursors`` is an
  int32 ``[B]`` vector of write offsets — how many tokens each slot
  already holds.  Grouped K/V heads (models/jamba.py): ``k``/``v`` and the cache
  leaves may carry ``H_kv < H`` heads, each shared by ``H / H_kv`` query
  heads; with ``H_kv == H`` the program is the one it always was.  Token ``i`` of slot ``b`` lands at cache position
  ``cursors[b] + i`` and attends causally over positions
  ``<= cursors[b] + i``, so a chunk replays exactly the dense causal
  prefill for its token range.  ``Lc`` must be at least
  ``max(cursors) + C`` (the serving cache is over-allocated by one chunk,
  kv_cache.cache_length) so the write never clamps.

  Slots whose chunk is only partially valid write garbage K/V beyond
  their valid tokens; that region sits at positions ``> cursors[b] + i``
  for every valid query ``i``, is masked here, and is overwritten before
  the cursor ever reaches it (the next chunk's write window covers it).
  Stale K/V from a previous slot occupant is masked the same way — a
  reused slot only ever attends to positions its own tokens have
  written.  An IDLE slot's window (``num_valid == 0``) may stay
  unwritten (the rows form of the write visits fed slots only): nothing
  reads it for the same reason.

  ``num_valid`` (int32 ``[B]``; ``None`` = all ``C`` positions real,
  which is what ``generate()``'s decode means) says how many of the
  chunk's positions each slot really feeds; 0 is an idle slot.  A slot's
  device cursor outlives its request (the engine zeroes it only when the
  next request starts), so the cursor alone cannot tell a dead slot:
  ``cursors[b] + num_valid[b]`` is the slot's BOUND, the first row no
  valid query of this step can see.

  FINITENESS INVARIANT: the reference attend masks a stale position's
  softmax probability to zero, but its probability-weighted V sum still
  contracts over every cache position and ``0 * NaN = NaN`` — so callers
  must never leave NON-FINITE values in cache rows they will not
  overwrite before the next read.  Garbage-but-finite stale rows are
  fine (their exact-0 probability annihilates them).  The one producer
  of non-finite rows is a poisoned device step under serving resilience:
  the engine zeroes the bad step's writes before the slot is read again
  — a retried slot's rows above its committed cursor, a quarantined slot
  whole (engine._sanitize_slots) — so the invariant holds without taxing
  this hot path.  The kernel attend copies of a slot's rows at or beyond its bound only
  the rest of the bound's own granule, and masks V as well as the scores
  over whatever its buffer holds beyond the bound, so no stale row reaches its output
  whatever it holds; callers keep the invariant all the same, because
  which attend a step was built with is the rule's to decide.  What the
  kernel does not compute — an idle slot, positions ``>= num_valid`` —
  comes out as zeros where the reference gives garbage-but-finite
  values; either way the next layer writes finite K/V for those
  positions and nothing reads their logits.

  The window write has two lowerings with bit-identical results
  (kernels/kv_write.py), and so has the attend, equal to rounding
  (kernels/slot_attention.py: two einsums over every row of every slot,
  or one kernel that reads the rows under each slot's bound alone).  ``write_impl`` / ``attn_impl`` name them, ``None`` applies
  each dispatch rule to the shapes at hand (the serving engine resolves
  both once when it builds its step and passes them down).

  Returns ``(out [B, C, H, hd], new_cached_k, new_cached_v)``.
  """
  from easyparallellibrary_tpu.kernels.kv_write import kv_write
  from easyparallellibrary_tpu.kernels.slot_attention import slot_attention
  cached_k, cached_v = kv_write(cached_k, cached_v, k, v, cursors,
                                num_valid, impl=write_impl)
  out = slot_attention(q, cached_k, cached_v, cursors, num_valid,
                       impl=attn_impl)
  return out.astype(dtype), cached_k, cached_v


@dataclasses.dataclass
class PagedInfo:
  """Per-step paged-decode routing, threaded through the model to every
  attention layer (the paged twin of the ``slot_cursors`` vector).
  Built once per fused step by :func:`paged_step_logits`; deliberately a
  PLAIN dataclass (not a pytree) so the static ``impl`` string rides
  along without entering any jit signature.

  ``write_idx`` int32 ``[T]`` — flat pool row (block * block_size +
  offset) each token's K/V scatter-writes to; padding tokens and
  positions past the virtual length are pre-routed to the null block
  (serving/kv_cache.NULL_BLOCK).  ``tables_tok`` int32 ``[T, MB]`` —
  each token's slot block-table row.  ``positions`` int32 ``[T]`` —
  absolute positions (the causal bound).  ``impl`` — resolved
  paged-attention implementation (kernels/paged_attention.py dispatch).
  """
  write_idx: Any
  tables_tok: Any
  positions: Any
  impl: str = "reference"


def paged_cache_attend(q, k, v, k_pages, v_pages, paged_info, dtype):
  """Paged-pool KV attention — the block-table twin of
  :func:`slot_cache_attend`, sharing its contracts: write this step's
  K/V first, then attend with the per-token causal bound masking
  everything newer or stale; garbage rows are masked-but-contracted, so
  the FINITENESS INVARIANT (slot_cache_attend docstring) applies to
  pool rows verbatim — including the null block, which absorbs padding
  writes (the resilient engine's sanitize pass zeroes it with any
  poisoned slot).

  ``q``/``k``/``v`` are ``[T, H, hd]`` flat-token projections;
  ``k_pages``/``v_pages`` ``[NB, bs, H, hd]`` pools.  The attend itself
  dispatches through ``kernels.paged_attention`` (Pallas on TPU, the
  bit-exact jnp reference elsewhere).

  Returns ``(out [T, H, hd], new_k_pages, new_v_pages)``.
  """
  from easyparallellibrary_tpu.kernels.paged_attention import (
      paged_attention)
  NB, bs, H, hd = k_pages.shape
  flat = (NB * bs, H, hd)
  k_pages = k_pages.reshape(flat).at[paged_info.write_idx].set(
      k.astype(k_pages.dtype)).reshape(NB, bs, H, hd)
  v_pages = v_pages.reshape(flat).at[paged_info.write_idx].set(
      v.astype(v_pages.dtype)).reshape(NB, bs, H, hd)
  out = paged_attention(q, k_pages, v_pages, paged_info.tables_tok,
                        paged_info.positions, impl=paged_info.impl)
  return out.astype(dtype), k_pages, v_pages


def paged_step_logits(model, params, kv, tokens, slot_ids, positions,
                      valid, block_tables, impl: str = "reference"):
  """Flat-token scoring against the paged KV cache — the paged twin of
  :func:`slot_step_logits` and THE device entry of the token-flat
  serving step (serving/engine.py).

  One call scores ``tokens`` (int32 ``[T]``, each tagged with its slot
  and absolute position) against the paged pools: token ``t`` writes
  K/V at its slot's block-table row for ``positions[t]`` and attends its
  own causal prefix through the table.  Prefill chunks, one-token
  decodes, and speculative drafts of DIFFERENT slots ride one flat
  batch; compute is proportional to ``T`` (the scheduled-token budget),
  not ``num_slots * chunk``.  Invalid (padding) tokens write to the
  null block and their logits are garbage the scheduler never consumes.

  Returns ``(logits [T, vocab], new_kv)``.
  """
  T = tokens.shape[0]
  MB = block_tables.shape[1]
  bs = None
  for leaf in jax.tree_util.tree_leaves(kv):
    bs = leaf.shape[1]
    break
  L = MB * bs
  tables_tok = jnp.take(block_tables, slot_ids, axis=0)      # [T, MB]
  blk = jnp.take_along_axis(
      tables_tok, jnp.clip(positions // bs, 0, MB - 1)[:, None],
      axis=1)[:, 0]
  real_idx = blk * bs + positions % bs
  # Padding tokens — and any position past the virtual length (a draft
  # rollout's overshoot) — write to the null block's rows instead.
  trash_idx = jnp.arange(T, dtype=jnp.int32) % bs
  write_idx = jnp.where(valid & (positions < L), real_idx, trash_idx)
  info = PagedInfo(write_idx=write_idx, tables_tok=tables_tok,
                   positions=positions, impl=impl)
  logits, mut = model.apply(
      {"params": params, "cache": kv}, tokens[:, None], decode=True,
      paged_info=info, mutable=["cache"])
  return logits[:, 0], mut["cache"]


@dataclasses.dataclass
class SlotRows:
  """The map between a fused step's ``[slots, C]`` chunk positions and the
  token-flat batch its position-wise layers run on, threaded through a
  model to every layer that owns per-slot state (the contiguous cache's
  twin of :class:`PagedInfo`; a PLAIN dataclass for the same reason).
  Built once a step by :func:`slot_rows`.

  In slot mode a model's residual stream is ``[T, 1, D]``, one token a
  batch row as in the paged step: row ``t`` is chunk position ``i`` of
  slot ``b``, the live positions of slot 0 first, then slot 1's, and so
  on; rows at or beyond the step's live total are padding.  Embedding,
  norms, projections, MLPs, routers and experts see that batch and
  nothing else.  A mixer that owns per-slot state (the K/V window and
  the attend, a recurrence, a convolution window) takes its operands
  :meth:`to_slots`, runs in the ``[slots, C, ...]`` layout its kernels
  are written for, and hands its result :meth:`to_flat`.  Both moves are
  gathers of whole rows (a scatter is a serial loop on a TPU): a dead
  chunk position reads some other row's values, which nothing reads
  after it, exactly as it held garbage before; a padding row is
  gathered by no position.  Who still moves rows so: ``kv_write`` (new K/V,
  latent and index rows ``to_slots``), ``slot_attn`` and ``slot_attn_kvwin``
  (queries in, result back), ``dsa_index`` (index queries and weights in,
  the scores ``to_flat`` for their thresholds), ``ssm_scan`` and the
  convolution's window.  Who does not: the selected and the windowed latent
  attends (``slot_attn_sel``, ``slot_attn_win``) and a plain latent leaf's
  ``slot_attn`` where it takes their grid (``plain_tile_form``), which
  where their kernel
  runs read their queries from the flat batch at ``dst``'s first row a slot
  and write their result to the same rows (kernels/slot_attention.py, the
  tile forms; the layer's carry then holds both row-wise).

  ``src`` int32 ``[T]`` — the ``slot * C + i`` each flat row reads;
  ``dst`` int32 ``[slots * C]`` — the flat row each chunk position reads
  back; both ``None`` at full width (``T == slots x C``), where the map
  is a reshape.  ``live`` bool ``[T, 1]`` — rows that carry a live
  position (``None``: all; what a dropless expert layer routes).
  ``positions`` int32 ``[T, 1]`` — each row's absolute position,
  ``cursors[b] + i``.  ``head`` int32 ``[slots]`` or ``[slots, R]`` —
  the flat rows whose logits the caller asked for (``None``: every chunk
  position's).

  ``narrow`` (static; ``None``: one width) is a second, smaller row count
  the layers may run on, and ``fits`` (bool scalar) says whether this
  step's live positions fit it: live rows are contiguous from row 0, so
  a step that fits computes the first ``narrow`` rows and nothing else
  (:func:`slot_layers`).  The maps, the embedding, the head and the
  sampler are built at ``T`` rows, once.
  """
  slots: int
  chunk: int
  src: Any
  dst: Any
  live: Any
  positions: Any
  head: Any = None
  narrow: Optional[int] = None
  fits: Any = None

  def to_slots(self, flat):
    """``[T, ...]`` -> ``[slots, C, ...]``.  Rows are gathered whole,
    as ``[T, features]``: lane-dense whatever the trailing axes are."""
    tail = flat.shape[1:]
    if self.dst is not None:
      flat = jnp.take(flat.reshape(flat.shape[0], -1), self.dst, axis=0,
                      mode="clip")
    return flat.reshape(self.slots, self.chunk, *tail)

  def to_flat(self, x):
    """``[slots, C, ...]`` -> ``[T, ...]``, gathered as :meth:`to_slots`
    gathers."""
    tail = x.shape[2:]
    x = x.reshape(self.slots * self.chunk, -1)
    if self.src is not None:
      x = jnp.take(x, self.src, axis=0, mode="clip")
    return x.reshape(x.shape[0], *tail)

  def first(self, n: int) -> "SlotRows":
    """The map of the batch's first ``n`` rows: what the layers see when
    they run on those alone.  :meth:`to_slots` then gathers from ``n``
    rows (a live position's row lies among them, a dead one's index is
    clipped), :meth:`to_flat` gathers ``n``."""
    return dataclasses.replace(
        self, src=self.src[:n], live=self.live[:n],
        positions=self.positions[:n], head=None, narrow=None, fits=None)

  def head_rows(self, x):
    """The rows of ``x`` ``[T, 1, D]`` the head runs on: ``[slots, D]``
    or ``[slots, R, D]`` as ``head`` asks, ``[slots, C, D]`` without."""
    if self.head is None:
      return self.to_slots(x[:, 0])
    return jnp.take(x[:, 0], self.head, axis=0, mode="clip")


def child_of(make):
  """``get(parent) -> make(parent)``, made once a parent: flax builds a
  named submodule once under a parent, and :func:`slot_layers` calls a
  :class:`SplitLayer`'s parts under the model itself and under the copies
  of it a conditional's sides run on."""
  made = {}

  def get(parent):
    if id(parent) not in made:
      made[id(parent)] = (parent, make(parent))   # the parent kept: its id
    return made[id(parent)][1]
  return get


@dataclasses.dataclass
class SplitLayer:
  """A layer of :func:`slot_layers` whose mixer owns a cache leaf that
  grows with the context (a K/V pair, a latent, an index), in three parts
  so that the leaf's write and its attend stand OUTSIDE the conditional of
  a two-width step and in the program once.  ``call(mdl, rows, x)`` is the
  whole layer; with ``part=`` one of

  * ``"pre"``: ``x`` -> ``carry``: the norm and the projections before
    the mixer, to the mixer's operands;
  * ``"mix"`` (``x`` is ``None``): ``carry`` -> ``carry``: the window
    write and the attend, on ``[slots, C, ..]`` whatever rows the other
    two ran on;
  * ``"post"``: ``x``, ``carry`` -> ``x``: the output projection, the
    residual and everything after it.

  ``carry`` is ``(rowwise, whole)``: a tuple of arrays with the flat
  batch's rows in front (or ``None``) and a tree of anything else.  Which
  layers a stack splits is said where the stack is built: each split
  layer costs the step a conditional (55 to 150 us on a v5e), each such
  leaf left inside one rests on the compiler writing it in place there
  (:class:`GPT` leaves its blocks whole and says why)."""
  call: Any

  def __call__(self, mdl, rows, x, **part):
    return self.call(mdl, rows, x, **part)


def slot_layers(model, rows: SlotRows, x, layers):
  """A slot-mode model's stack of layers on the token-flat batch ``x``
  ``[T, 1, D]``: ``layers[i](mdl, rows, x) -> x`` runs layer ``i`` as a
  submodule of ``mdl`` on the rows ``rows`` maps.

  With one width (and outside slot mode, ``rows`` ``None``) this is the
  loop.  With a ``narrow`` one, what is position-wise stands in
  conditionals on ``rows.fits`` inside the ONE compiled program: the
  narrow side runs it on ``x``'s first ``narrow`` rows under
  :meth:`SlotRows.first`'s map, where every live position lies, and
  fills its results up to ``T`` rows with zeros, which no live position
  reads; the wide side runs it as it is.  A plain layer stands there
  whole (its kernels take ``[slots, C, ..]`` operands on either side): so
  a run of them is ONE conditional.  A :class:`SplitLayer` ends the run
  after its ``"pre"`` part, has its ``"mix"`` part outside, and starts the
  next run with its ``"post"`` part: a cache leaf it owns is no operand
  that a conditional changes, so nothing rests on the compiler proving an
  in-place write safe inside one (it copied such leaves whole, 100 to 540
  MB each, in four of the six serving cells: PERF.md, PR 41).  What is
  outside — the maps, the embedding, the mixers of split layers, the last
  norm, the head on ``[slots, ..]`` rows, the sampler — is in the program
  once."""
  if rows is None or rows.narrow is None:
    for layer in layers:
      x = layer(model, rows, x)
    return x
  n, T = rows.narrow, x.shape[0]
  cut = lambda tree: jax.tree_util.tree_map(lambda y: y[:n], tree)
  fill = lambda tree: jax.tree_util.tree_map(
      lambda y: jnp.pad(y, ((0, T - n),) + ((0, 0),) * (y.ndim - 1)), tree)

  def conditional(steps, x, carry):
    def run(rows):
      def fn(mdl, x, carry):
        for step in steps:
          x, carry = step(mdl, rows, x, carry)
        return x, carry
      return fn

    def narrow(mdl, x, carry):
      x, (rowwise, whole) = run(rows.first(n))(
          mdl, x[:n], (cut(carry[0]), carry[1]))
      return fill(x), (fill(rowwise), whole)

    return nn.cond(rows.fits, narrow, run(rows), model, x, carry)

  steps, carry = [], ((), ())
  for layer in layers:
    if isinstance(layer, SplitLayer):
      steps.append(lambda mdl, rows, x, carry, layer=layer: (
          x, layer(mdl, rows, x, part="pre")))
      x, carry = conditional(steps, x, carry)
      carry = layer(model, rows, None, part="mix", carry=carry)
      steps = [lambda mdl, rows, x, carry, layer=layer: (
          layer(mdl, rows, x, part="post", carry=carry), ((), ()))]
    else:
      steps.append(lambda mdl, rows, x, carry, layer=layer: (
          layer(mdl, rows, x), carry))
  return conditional(steps, x, carry)[0]


def slot_rows(cursors, num_valid, slots: int, chunk: int,
              width: Optional[int] = None, head_pos=None,
              narrow: Optional[int] = None) -> SlotRows:
  """The step's :class:`SlotRows`.  ``width`` is the flat batch's static
  row count ``T`` (``None`` or ``slots x chunk``: full width, the map a
  reshape); under a narrower one no step may hold more than ``T`` live
  positions (the engine derives ``T`` and hands it to the scheduler as
  its plans' ceiling, serving/engine.py:flat_width).  From ``num_valid`` alone: an
  exclusive cumulative sum gives slot ``b``'s live positions the rows
  ``[start_b, start_b + num_valid_b)``; the inverse, which slot a row
  belongs to, is one compare-and-count (a binary search would be a
  serial loop of scalar steps on a TPU).  ``head_pos`` int32 ``[slots]``
  or ``[slots, R]`` names chunk positions whose logits are wanted.
  ``narrow`` (``None``, or at least ``width``: none) is the second row
  count a step whose live positions fit it computes
  (:func:`slot_layers`; serving/engine.py:narrow_width)."""
  N, C = slots, chunk
  i32 = jnp.int32
  cursors = cursors.astype(i32)
  # [slots] against ``head_pos``, which is [slots] or [slots, R]
  per_slot = lambda v: v.reshape((N,) + (1,) * (head_pos.ndim - 1))
  if width is None or width >= N * C:
    positions = cursors[:, None] + jnp.arange(C, dtype=i32)[None]
    live = None if num_valid is None else (
        jnp.arange(C)[None] < num_valid[:, None]).reshape(N * C, 1)
    head = None if head_pos is None else (
        per_slot(jnp.arange(N, dtype=i32)) * C + head_pos)
    return SlotRows(N, C, None, None, live, positions.reshape(N * C, 1),
                    head)
  T = width
  ends = jnp.cumsum(num_valid.astype(i32))
  starts = ends - num_valid
  row = jnp.arange(T, dtype=i32)
  slot = jnp.minimum(
      jnp.sum(ends[None, :] <= row[:, None], axis=1, dtype=i32), N - 1)
  i = row - jnp.take(starts, slot)
  # Indices beyond either side (a padding row's, a dead chunk position's)
  # are clipped where they are used (``SlotRows``' gathers).
  dst = (starts[:, None] + jnp.arange(C, dtype=i32)[None]).reshape(N * C)
  head = None if head_pos is None else per_slot(starts) + head_pos
  if narrow is not None and narrow >= T:
    narrow = None
  return SlotRows(N, C, slot * C + i, dst, (row < ends[-1])[:, None],
                  (jnp.take(cursors, slot) + i)[:, None], head, narrow,
                  None if narrow is None else ends[-1] <= narrow)


def flat_ids(ids, slot_cursors, num_valid, rows=None):
  """A slot-mode call's map and its token ids as the flat batch takes
  them: ``(rows, ids [T, 1])``.  A caller that hands no map in
  (``model.apply(..., decode=True, slot_cursors=...)`` directly) gets the
  full-width one, every position of every slot."""
  if rows is None:
    rows = slot_rows(slot_cursors, num_valid, *ids.shape)
  return rows, rows.to_flat(ids)[:, None]


def slot_step_logits(model, params, kv, tokens, cursors,
                     kv_write_impl=None, slot_attn_impl=None,
                     num_valid=None, stats: bool = False,
                     width: Optional[int] = None, head_pos=None,
                     narrow: Optional[int] = None, **state_args):
  """Multi-token scoring on the shared slot-cache core — THE device entry
  every serving component steps through.

  One call scores ``tokens`` (int32 ``[num_slots, C]``, any chunk width
  C >= 1) against the slot KV cache: token ``i`` of slot ``b`` lands at
  absolute position ``cursors[b] + i``, attends its own causal prefix
  (:func:`slot_cache_attend`), and position ``i``'s logits are the
  model's distribution for the token at ``cursors[b] + i + 1``.  That
  makes the call serve three roles with identical numerics:

  * chunked **prefill** (C prompt tokens per slot),
  * one-token **decode** (C == 1, or one valid token in a wider chunk),
  * batched **verification** of speculative drafts — k drafted tokens
    ride the chunk positions plain decode wastes, and their k+1 target
    distributions come back in the same call
    (serving/speculative/verify.py).

  ``kv_write_impl`` and ``slot_attn_impl`` are the resolved lowerings of
  the cache write and of the attend (kernels/kv_write.py,
  kernels/slot_attention.py; ``None`` resolves each from the shapes).
  ``num_valid`` (int32 ``[num_slots]``; ``None`` = every position of
  every slot is real) says how many of the chunk's positions each slot
  feeds: the attend reads no cache row at or beyond ``cursors +
  num_valid`` and none at all of an idle slot (``num_valid == 0``), and
  a recurrence advances by exactly that many, and a dropless expert
  layer routes exactly those positions.  A model with positional
  arithmetic of its own (models/glm_moe.py, models/lfm2_moe.py: rotary)
  takes token ``i``'s position from the same ``cursors[b] + i``.
  ``state_args`` go to a model that asks for more (models/jamba.py:
  ``reset``, ``ssm_scan_impl``; models/glm_moe.py: ``moe_gmm_impl``;
  models/lfm2_moe.py: ``reset`` AND ``moe_gmm_impl``; models/gigachat.py:
  ``reset``, ``gdn_scan_impl`` and ``moe_gmm_impl``; ``expert_axis``,
  the mesh axis a divided engine's step is mapped over, to a model whose
  expert layers exchange rows over it); a GPT takes none.  ``stats`` also
  returns what the model sowed into its ``stats``
  collection (an expert layer's load).

  The position-wise layers run on a token-flat batch (:class:`SlotRows`)
  of ``width`` rows, which must hold the step's live positions:
  ``None`` is ``num_slots x C``, every position of every slot, through
  the same model code.  ``head_pos`` (int32 ``[num_slots]`` or
  ``[num_slots, R]``: chunk positions) gathers the rows the head runs on
  BEFORE the head: the one a slot samples from, or a speculating step's
  ``K + 1``.  ``narrow`` is a second, smaller width in the same program:
  a step whose live positions fit it runs its layers on that many rows
  (:func:`slot_layers`), its head as at ``width``.

  Returns ``(logits, new_kv)`` — ``logits`` ``[num_slots, C, vocab]``,
  or ``[num_slots, vocab]`` / ``[num_slots, R, vocab]`` as ``head_pos``
  asks — and, with ``stats``, the sown tree; the caller owns cursor
  advancement (and, for speculation, rollback to the last accepted
  position).
  """
  rows = slot_rows(cursors, num_valid, *tokens.shape, width=width,
                   head_pos=head_pos, narrow=narrow)
  axis = state_args.get("expert_axis")
  if axis is not None and rows.fits is not None:
    # Inside a ``shard_map`` over ``axis`` the layers exchange rows between
    # the chips, so every chip must take the same side of the width's
    # conditionals: the narrow one only where every chip's positions fit.
    rows.fits = jax.lax.pmin(rows.fits.astype(jnp.int32), axis) > 0
  logits, mut = model.apply(
      {"params": params, "cache": kv}, tokens, decode=True,
      slot_cursors=cursors, num_valid=num_valid, rows=rows,
      kv_write_impl=kv_write_impl, slot_attn_impl=slot_attn_impl,
      mutable=["cache", "stats"] if stats else ["cache"], **state_args)
  if stats:
    return logits, mut["cache"], mut.get("stats", {})
  return logits, mut["cache"]


def missing_slot_cache():
  raise ValueError(
      "slot-mode decode (slot_cursors=...) needs an externally allocated "
      "slot KV cache passed in the 'cache' collection; build one with "
      "serving.kv_cache.allocate_kv_cache(cfg, num_slots, chunk)")
