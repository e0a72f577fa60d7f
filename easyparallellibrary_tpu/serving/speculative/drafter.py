"""Drafters: cheap token proposers for speculative decoding.

A drafter fills the chunk positions the fused serving step wastes on
plain decode with *guesses* at the next ``k`` tokens of each slot; the
step then scores all of them in its one model call and the verifier
(verify.py) keeps the accepted prefix.  Two designs ship:

* :class:`NgramDrafter` — prompt-lookup decoding: propose the
  continuation of the most recent earlier occurrence of the request's
  own trailing n-gram.  Pure host work, no weights, no device state —
  the zero-cost drafter for repetitive text (code, retrieval, chat
  templates).
* :class:`DraftModelDrafter` — a small GPT (same vocabulary, any
  depth/width) greedily rolled ``k`` tokens ahead per slot in ONE jitted
  call against its own slot KV cache.  The draft cache mirrors the
  target's admission/prefill/rollback life exactly: it consumes the same
  step plan the engine does, and after verification its cursors are
  overwritten with the engine's rolled-back cursors — cursor values are
  "committed tokens resident in cache", identical on both sides, so no
  cache rewrite is ever needed.

Both propose deterministically (a point-mass proposal); verify.py's
rejection-sampling acceptance stays exactly distribution-preserving for
that case (accept with prob ``p(d)``, residual excludes ``d``).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from easyparallellibrary_tpu.models.slot_core import (
    paged_step_logits, slot_step_logits)
from easyparallellibrary_tpu.serving._capabilities import (
    check_draft_compatible)


def ngram_propose(history: np.ndarray, k: int, ngram_max: int,
                  ngram_min: int) -> np.ndarray:
  """Prompt-lookup proposal: up to ``k`` continuation tokens of the most
  recent earlier occurrence of ``history``'s trailing n-gram.

  Longest suffix first (``ngram_max`` down to ``ngram_min``): a longer
  match is stronger evidence the continuation repeats.  Among equal-n
  matches the most recent wins (locally repetitive text beats a stale
  early match).  Returns an empty array when nothing matches — the slot
  simply decodes non-speculatively this step.
  """
  history = np.asarray(history).reshape(-1)
  L = len(history)
  for n in range(min(ngram_max, L - 1), ngram_min - 1, -1):
    suffix = history[L - n:]
    # Windows over history[:-1]: every match start i <= L-1-n has at
    # least one continuation token, and the suffix's own occurrence at
    # L-n is excluded.
    windows = np.lib.stride_tricks.sliding_window_view(history[:L - 1], n)
    hits = np.nonzero((windows == suffix).all(axis=1))[0]
    if hits.size:
      start = int(hits[-1]) + n
      return history[start:start + k].astype(np.int32)
  return np.zeros((0,), np.int32)


class Drafter:
  """Interface the engine drives (serving/engine.py).

  ``k`` is the maximum drafts per slot per step; the engine validates
  ``k + 1 <= prefill_chunk`` at bind time.  Lifecycle per engine
  iteration: ``propose(plan, histories)`` BEFORE the fused step (the
  plan's token block is still draft-free), then ``observe_commit(
  new_cursors)`` after it (the engine's verified, rolled-back cursor
  vector — the only rollback a drafter with device state needs).
  """

  k: int = 0

  def bind(self, engine) -> None:
    """Called once from the engine's constructor with the engine itself;
    drafters with device state allocate against the engine's slot/chunk
    geometry and mesh here."""

  def propose(self, plan, histories: Dict[int, np.ndarray]
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Return ``(draft_tokens [N, k] int32, num_draft [N] int32)`` for
    the step described by ``plan`` (num_draft[slot] <=
    plan.draft_cap[slot]).  ``histories`` maps each draft-eligible slot
    to its committed tokens (prompt + generated)."""
    raise NotImplementedError

  def observe_commit(self, new_cursors) -> None:
    """Engine hook after verification; ``new_cursors`` is the engine's
    post-rollback cursor vector (committed cache-resident tokens per
    slot)."""

  def observe_skip(self, plan) -> None:
    """Engine hook when a step runs WITHOUT drafting (the resilience
    degradation ladder's spec_off level skips draft compute outright —
    serving/resilience.py).  Stateless drafters ignore it; drafters
    with device state may mark their mirror stale.  Skipping can only
    cost acceptance rate after recovery, never correctness: the
    verifier judges every later draft against the target's own
    distribution."""


class NgramDrafter(Drafter):
  """Model-free prompt-lookup drafter (:func:`ngram_propose` per slot).

  ``lookback`` bounds the history scanned per step (the trailing window
  most likely to repeat): without it, a long-context request would pay
  an O(history) host-side scan per decode step on the serving hot path.
  0 = unbounded.
  """

  def __init__(self, k: int = 4, ngram_max: int = 4, ngram_min: int = 1,
               lookback: int = 512):
    if not 1 <= ngram_min <= ngram_max:
      raise ValueError(f"need 1 <= ngram_min <= ngram_max; got "
                       f"ngram_min={ngram_min}, ngram_max={ngram_max}")
    if lookback < 0:
      raise ValueError(f"lookback must be >= 0 (0 = unbounded): "
                       f"{lookback}")
    self.k = int(k)
    self.ngram_max = int(ngram_max)
    self.ngram_min = int(ngram_min)
    self.lookback = int(lookback)

  def propose(self, plan, histories):
    from easyparallellibrary_tpu.observability import trace as trace_lib
    # draft_cap is per-SLOT in both plan kinds (the paged plan's tokens
    # are a flat [token_budget] batch, so tokens.shape[0] is not N).
    N = plan.draft_cap.shape[0]
    toks = np.zeros((N, self.k), np.int32)
    counts = np.zeros((N,), np.int32)
    with trace_lib.get_tracer().span("ngram_propose", cat="serving",
                                     track="serving"):
      for slot, hist in histories.items():
        cap = int(plan.draft_cap[slot])
        if cap <= 0:
          continue
        if self.lookback:
          hist = hist[-self.lookback:]
        cont = ngram_propose(hist, min(cap, self.k), self.ngram_max,
                             self.ngram_min)
        counts[slot] = len(cont)
        toks[slot, :len(cont)] = cont
    return toks, counts


class DraftModelDrafter(Drafter):
  """Greedy draft-model drafter with its own slot KV cache.

  ``model``/``params`` are a small GPT sharing the target's vocabulary
  (checked at bind via ``_capabilities.check_draft_compatible``).  One
  jitted call per engine iteration first MIRRORS the step plan through
  the draft model (the same ``[num_slots, chunk]`` block the target
  sees: prefill chunks keep the draft cache in lockstep, decode slots'
  last committed token seeds the rollout), then greedily rolls ``k``
  tokens ahead per slot.  The draft cache buffer is donated, so the
  drafter's steady-state footprint is exactly one (small) cache.
  """

  def __init__(self, model, params, k: int = 4, mesh=None):
    self.k = int(k)
    self.model = model
    self.params = params
    self.mesh = mesh
    self._kv = None
    self._cursors = None
    self._fn = None
    # Paged-engine mirror (set at bind): the draft model keeps its OWN
    # paged pools but reads the ENGINE's block tables — block indices
    # depend only on positions, which are identical on both sides, so
    # one host allocation serves both caches.
    self._paged = False
    # Set at bind (observability/device.py cost-card capture).  The
    # attempt flag is one-shot: a FAILED capture must not re-pay the
    # AOT lower+compile on every subsequent propose() (capture_twin
    # stores no card on failure — it logs once and degrades).
    self._introspector = None
    self._twin_label = "serving/drafter"
    self._card_attempted = False

  @classmethod
  def from_checkpoint(cls, directory: str, model, *, k: int = 4,
                      target=None, shardings=None, mesh=None):
    """Restore draft params off the PR-2 checksum-validated fallback
    chain (``runtime.saver.restore_params``) and wrap them as a drafter.

    The checkpoint's embedding shape is validated against ``model.cfg``
    from the index alone (``saver.peek_leaf_shapes``) BEFORE any shard
    is read, so a wrong-vocabulary draft checkpoint fails in
    milliseconds with an actionable message instead of a tree-structure
    error mid-restore.  Without ``target`` a template is built by
    ``model.init`` (cheap for a drafter-sized GPT).
    """
    from easyparallellibrary_tpu.runtime import saver
    leaves, _ = saver.peek_leaf_shapes(directory)
    for path, shape in leaves.items():
      name = path[len("params/"):] if path.startswith("params/") else path
      if name == "wte/embedding" and shape and \
          shape[0] != model.cfg.vocab_size:
        raise ValueError(
            f"draft checkpoint {directory!r} holds a vocab-{shape[0]} "
            f"embedding but the draft config says vocab_size="
            f"{model.cfg.vocab_size}; speculative verification needs the "
            f"target's vocabulary — restore a checkpoint trained on the "
            f"same tokenizer")
    if target is None:
      target = model.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    params, _ = saver.restore_params(directory, target=target,
                                     shardings=shardings)
    return cls(model, params, k=k, mesh=mesh)

  def bind(self, engine):
    from easyparallellibrary_tpu.observability import device as device_lib
    from easyparallellibrary_tpu.serving import kv_cache as kv_lib
    check_draft_compatible(engine.model.cfg, self.model.cfg)
    # Device-truth introspection (observability/device.py): the draft
    # rollout is a compiled twin like the fused step — its cost card is
    # captured at the first propose() with that call's abstract specs.
    self._introspector = device_lib.get_introspector()
    self._twin_label = f"{engine._track_prefix}/drafter"
    mesh = self.mesh if self.mesh is not None else engine.mesh
    self._paged = bool(getattr(engine, "paged", False))
    if self._paged:
      import dataclasses
      # The mirror pool is addressed exclusively through the ENGINE's
      # block tables (target max_seq_len / block_size wide), so its
      # capacity/geometry validation must use the TARGET's sequence
      # length — a draft model legitimately padded LONGER than the
      # target (check_draft_compatible permits and even advises it)
      # must not inflate the blocks-per-slot requirement.  Only the
      # draft's head geometry shapes the pool.
      mirror_cfg = dataclasses.replace(
          self.model.cfg, max_seq_len=engine.model.cfg.max_seq_len)
      self._kv = kv_lib.allocate_paged_kv_cache(
          mirror_cfg, engine.num_blocks, engine.block_size, mesh)
      self._fn = self._build_paged_draft_fn(engine)
    else:
      self._kv, self._cursors = kv_lib.allocate_kv_cache(
          self.model.cfg, engine.num_slots, engine.chunk, mesh)
      self._fn = self._build_draft_fn(
          engine.chunk, engine.flat_width,
          kv_lib.resolved(kv_lib.step_lowerings(
              self.model.cfg, engine.num_slots, engine.chunk, mesh)))

  def _build_draft_fn(self, chunk: int, width: int, lowerings):
    model, K, C = self.model, self.k, chunk
    # One resolved lowering of the cache write and one of the attend (a
    # draft model keeps K/V pairs and nothing else: the record's other
    # entries are None) for the chunk-wide and the one-token calls alike
    # (what fits a chunk fits one token).
    score = functools.partial(slot_step_logits, **lowerings)

    def draft(params, kv, cursors, tokens, num_valid, reset):
      cursors = jnp.where(reset, 0, cursors)
      # Mirror the engine's chunk: writes the same prefill K/V the
      # target wrote, and scores decode slots' last committed token.
      last, kv = score(model, params, kv, tokens, cursors,
                       num_valid=num_valid, width=width,
                       head_pos=jnp.clip(num_valid - 1, 0, C - 1))
      toks = [jnp.argmax(last, axis=-1).astype(jnp.int32)]
      cur = cursors + num_valid
      # The roll-out feeds one token to every slot the chunk fed; an
      # idle slot's cache is not read.
      fed = (num_valid > 0).astype(jnp.int32)
      for _ in range(1, K):
        lg, kv = score(model, params, kv, toks[-1][:, None], cur,
                       num_valid=fed)
        toks.append(jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32))
        cur = cur + 1
      # Write-only feed of the final draft: its K/V must be cache-
      # resident too — if every draft is accepted the rolled-back cursor
      # covers its position, and a later step would attend garbage
      # there (the logits of this call are dead code XLA prunes).
      _, kv = score(model, params, kv, toks[-1][:, None], cur,
                    num_valid=fed)
      return jnp.stack(toks, axis=1), kv

    return jax.jit(draft, donate_argnums=(1,))

  def _build_paged_draft_fn(self, engine):
    """Paged twin of :meth:`_build_draft_fn`: mirror the engine's FLAT
    plan through the draft model (same tokens, slots, positions and
    block tables — prefill chunks keep the mirror pools in lockstep),
    then greedily roll ``k`` tokens ahead per drafting slot with
    one-token-per-slot flat batches at consecutive positions.  Rollout
    positions past the virtual length clamp to the null block inside
    ``paged_step_logits``, so overshoot (a slot near its budget) costs
    acceptance, never correctness.  No cursors anywhere: rollback is
    implicit in next step's host-planned positions."""
    model, K = self.model, self.k
    N = engine.num_slots
    T = engine.token_budget
    impl = engine._paged_impl

    def draft(params, kv, tokens, slot_ids, positions, valid, tables,
              last_idx, drafting):
      li = jnp.clip(last_idx, 0, T - 1)
      logits, kv = paged_step_logits(model, params, kv, tokens, slot_ids,
                                     positions, valid, tables, impl=impl)
      last = jnp.take(logits, li, axis=0)                 # [N, V]
      toks = [jnp.argmax(last, axis=-1).astype(jnp.int32)]
      sid = jnp.arange(N, dtype=jnp.int32)
      pos0 = jnp.take(positions, li, axis=0) + 1          # first draft pos
      for j in range(1, K):
        lg, kv = paged_step_logits(model, params, kv, toks[-1], sid,
                                   pos0 + (j - 1), drafting, tables,
                                   impl=impl)
        toks.append(jnp.argmax(lg, axis=-1).astype(jnp.int32))
      # Write-only feed of the final draft (same contract as the slot
      # layout: full acceptance must leave no K/V hole).
      _, kv = paged_step_logits(model, params, kv, toks[-1], sid,
                                pos0 + (K - 1), drafting, tables,
                                impl=impl)
      return jnp.stack(toks, axis=1), kv

    return jax.jit(draft, donate_argnums=(1,))

  def propose(self, plan, histories):
    from easyparallellibrary_tpu.observability import trace as trace_lib
    if self._fn is None:
      raise RuntimeError("DraftModelDrafter.propose before bind(): the "
                         "engine binds drafters in its constructor")
    with trace_lib.get_tracer().span("draft_model_forward", cat="serving",
                                     track="serving"):
      if self._paged:
        last_idx = (plan.base_idx + plan.num_valid - 1).astype(np.int32)
        draft_args = (
            self.params, self._kv, plan.tokens, plan.slot_ids,
            plan.positions, plan.valid, plan.block_tables, last_idx,
            plan.draft_cap > 0)
      else:
        draft_args = (self.params, self._kv, self._cursors,
                      plan.tokens, plan.num_valid, plan.reset)
      if self._introspector is not None and not self._card_attempted:
        from easyparallellibrary_tpu.observability import (
            device as device_lib)
        # Capture BEFORE the call: the cache buffer is donated, and the
        # specs must describe arguments that still exist (abstract
        # shapes only — nothing is read or transferred).  Exactly one
        # attempt, success or not (the engine/fit captures follow the
        # same one-shot rule).
        self._card_attempted = True
        self._introspector.capture_twin(
            self._twin_label, self._fn, device_lib.specs_of(draft_args),
            compile_count=1, meta={"k": self.k})
      toks, self._kv = self._fn(*draft_args)
      # The drafter's one designated fetch — explicit, like the
      # engine's token fetch, so the serving loop stays legal under
      # jax.transfer_guard_device_to_host("disallow").
      toks = jax.device_get(toks)
    counts = np.minimum(plan.draft_cap, self.k).astype(np.int32)
    return toks, counts

  def observe_commit(self, new_cursors):
    # Cursor values are "committed tokens resident in cache" — identical
    # for draft and target caches, so adopting the engine's rolled-back
    # vector IS the draft-side rollback (rejected-draft K/V beyond it is
    # masked, then overwritten, exactly like chunked-prefill garbage).
    # Paged mirror: there are no cursors — next step's host-planned
    # positions ARE the rollback — so there is nothing to adopt.
    if not self._paged:
      self._cursors = new_cursors

  def observe_skip(self, plan):
    # A skipped step (resilience spec_off window) means the mirror cache
    # missed this step's K/V writes: positions the engine committed
    # during the window hold garbage on the draft side until the slot is
    # reused.  That can only depress acceptance after recovery — the
    # target's verification still judges every draft — so no repair pass
    # is attempted on the serving hot path.
    pass
