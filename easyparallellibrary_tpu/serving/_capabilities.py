"""Unified serving capability guards.

One site answers "can this config serve?" so the engine, the drafters
and any future serving component reject an unsupported composition with
the SAME actionable message — each pointing at the ROADMAP open item
that will lift the limit, instead of three slightly different inline
raises that drift apart (the PR-3 engine carried two of these inline;
speculative decoding would have added a third family).

Everything here is a pure check: no imports of the engine, no device
work, safe to call before any allocation.
"""

from __future__ import annotations

ROADMAP_PP_SERVING = (
    "pipeline-parallel serving is ROADMAP item R9 ('Multi-chip serving'; "
    "docs/serving.md 'Current limits')")
ROADMAP_MOE_SERVING = (
    "the GPT block's capacity-bounded experts (models/moe.py MoEMLP) are "
    "a training layer: dropless expert decode inside the fused step is "
    "models/glm_moe.py's; what is still missing is ROADMAP item R4 "
    "('Sparse experts that serve'; docs/serving.md 'Current limits')")
ROADMAP_DRAFT_DISTILL = (
    "a drafter trained on the target's tokenizer is not queued; ROADMAP "
    "item R2's speculative cell decides whether the draft-model path "
    "stays (docs/serving.md 'Speculative decoding')")
ROADMAP_RECURRENT_STATE = (
    "a recurrence cannot be rolled back by moving a cursor: snapshots of "
    "recurrent state (prefix caching, paged and speculative rollback, the "
    "guarded retry) are ROADMAP item R7 ('Recurrent state beside KV'; "
    "docs/serving.md 'Current limits'); serve it through the contiguous "
    "cache with those features off")
ROADMAP_LATENT_CACHE = (
    "the latent cache of multi-head latent attention is one leaf a layer "
    "in the contiguous slot cache only: a paged pool of latent rows, their "
    "prefix cache, and the rollback speculation and the guarded retry need "
    "are ROADMAP item R5 ('A latent cache'; docs/serving.md 'Current "
    "limits'); serve it through the contiguous cache with those features "
    "off")
ROADMAP_SPARSE_LATENT = (
    "a latent attention that selects its rows keeps the indexer's keys "
    "beside the latent leaf in the contiguous slot cache only, and its "
    "selection is found over a slot's contiguous rows: a paged pool of "
    "latent and index rows is ROADMAP item R5 ('A latent cache'), "
    "selection inside the paged kernel ROADMAP item R10 ('Attention that "
    "selects what it reads'; docs/serving.md 'Current limits'); serve it "
    "through the contiguous cache with those features off")
ROADMAP_WINDOW_LATENT = (
    "a latent attention behind a window keeps a ring of window + chunk "
    "rows a slot, which overwrites what a cursor moved back would need "
    "again and has no paged form: a paged pool per layer kind that frees "
    "the blocks a window has left is ROADMAP item R6 ('Window and full "
    "layers in one cache'), the latent rows' pool ROADMAP item R5 ('A "
    "latent cache'; docs/serving.md 'Current limits'); serve it through "
    "the contiguous cache with those features off")
ROADMAP_WINDOW_KV = (
    "an attention layer behind a window keeps its K/V pair as a ring of "
    "window - 1 + chunk rows a slot, which overwrites what a cursor moved "
    "back would need again (a rejected draft's rows, a retried step's, a "
    "shared prefix's) and has no paged form: a paged pool per layer kind "
    "that frees the blocks a window has left, and what a ring answers to a "
    "cursor moved back, are ROADMAP item R6 ('Window and full layers in "
    "one cache'; docs/serving.md 'Current limits'); serve it through the "
    "contiguous cache with those features off")
ROADMAP_DIVIDED = (
    "an engine on a mesh whose 'expert' axis is larger than one DIVIDES "
    "its slots and its held experts over that axis: the plain fused step "
    "of the contiguous cache runs a chip's slots on each chip inside one "
    "shard_map and exchanges expert rows between them (models/moe.py "
    "exchanged_experts).  Nothing else is built across chips: experts "
    "over more chips than hold the slots, a pipeline's further stages, a "
    "K/V pair divided by heads inside the shard_map, the paged, "
    "speculative and guarded twins on such a mesh are ROADMAP item R9 "
    "('Multi-chip serving'; docs/serving.md 'The divided engine')")
ROADMAP_PREEMPTION = (
    "priority reorders ADMISSION, and on the paged engine "
    "(serving.paged.enabled) a RUNNING throughput-class slot is "
    "preempted (reason 'preempted') both on block-pool exhaustion and "
    "EAGERLY when a latency-class arrival would otherwise queue "
    "(serving/proactive_preemptions; docs/robustness.md)")

# Finish-reason glossary (docs/robustness.md "Serving resilience"):
#   length      — max_new_tokens reached
#   stop_token  — the request's stop token was generated
#   deadline    — Request.deadline_s / ttft_budget_s expired
#   cancelled   — client cancellation (scheduler/engine .cancel(uid))
#   shed        — rejected at submit by admission control (overload)
#   failed      — quarantined more than serving.resilience.max_requeues
#                 times (persistent bad steps implicating this request)
#   preempted   — paged out mid-flight because the KV block pool ran dry
#                 (paged engine; rides the requeue prefix-replay path, so
#                 unlike the others it names a REQUEUE, not a final
#                 resolution — the request finishes later under one of
#                 the reasons above with its output bit-intact)
FINISH_REASONS = ("length", "stop_token", "deadline", "cancelled",
                  "shed", "failed", "preempted")

# Admission classes: "latency" jumps the FCFS queue, "throughput" rides
# it.  (True preemption of running requests: ROADMAP_PREEMPTION.)
PRIORITIES = ("latency", "throughput")


def step_overlap(*, paged: bool, speculative: bool, resilient: bool) -> str:
  """Whether an engine may launch step k+1 before it has fetched step k's
  tokens (serving/engine.py ``step``): ``"on"``, or ``"off: <reason>"``
  for the engines whose NEXT plan needs this step's verdict.  Decided
  once, at construction, from what the engine is; the plain step of the
  contiguous cache needs nothing but the sampled token, which it takes
  on the device."""
  needs = [why for on, why in (
      (paged, "the paged cache's block tables follow commits and "
              "preemptions"),
      (speculative, "the host drafts from committed tokens"),
      (resilient, "the guarded step's verdict decides whether a cursor "
                  "moved"),
  ) if on]
  if not needs:
    return "on"
  return ("off: the next plan needs this step's commit ("
          + "; ".join(needs) + ")")


def check_request_fields(req) -> None:
  """Validate a Request's lifecycle-control fields at submit time, so a
  typo'd priority class or negative deadline fails loudly instead of
  silently never expiring."""
  if req.priority not in PRIORITIES:
    raise ValueError(
        f"request priority must be one of {PRIORITIES}; got "
        f"{req.priority!r} — {ROADMAP_PREEMPTION}")
  if req.deadline_s < 0:
    raise ValueError(f"deadline_s must be >= 0 (0 = none): "
                     f"{req.deadline_s}")
  if req.ttft_budget_s < 0:
    raise ValueError(f"ttft_budget_s must be >= 0 (0 = none): "
                     f"{req.ttft_budget_s}")
  if (req.deadline_s > 0 and req.ttft_budget_s > 0
      and req.ttft_budget_s > req.deadline_s):
    raise ValueError(
        f"ttft_budget_s {req.ttft_budget_s} exceeds deadline_s "
        f"{req.deadline_s}: the first token can never beat a budget "
        f"that outlives the whole request")


def check_servable(cfg, role: str = "the serving engine") -> None:
  """Reject model configs the serving stack cannot run.

  ``cfg`` is a :class:`models.gpt.GPTConfig`, a
  :class:`models.jamba.JambaConfig`, a
  :class:`models.glm_moe.GlmMoeConfig`, a
  :class:`models.lfm2_moe.Lfm2MoeConfig`, a
  :class:`models.dots3_note.Dots3NoteConfig` or a
  :class:`models.smallthinker.SmallThinkerConfig` (a config without
  ``pipeline_stages`` / ``num_experts`` has neither); ``role`` names the
  component doing the rejecting so a draft-model failure reads
  differently from a target-model one.  Refused are the GPT block's
  experts (``num_experts``: MoEMLP drops what overflows a capacity and
  has no slot mode); the dropless experts of models/glm_moe.py
  and models/lfm2_moe.py (``n_routed_experts``) are served.
  """
  stages = getattr(cfg, "pipeline_stages", 1)
  if stages > 1:
    raise ValueError(
        f"{role} is single-program (pipeline_stages=1) but got "
        f"pipeline_stages={stages}; restore the checkpoint "
        f"into a non-pipelined config (runtime.saver.restore_params) — "
        f"{ROADMAP_PP_SERVING}")
  experts = getattr(cfg, "num_experts", 0)
  if experts > 0:
    raise ValueError(
        f"{role} does not support MoE checkpoints yet "
        f"(num_experts={experts}); restore a dense checkpoint — "
        f"{ROADMAP_MOE_SERVING}")


def check_recurrent_state(cfg, feature: str) -> None:
  """Reject ``feature`` (the paged cache, prefix caching, speculative
  decoding, the guarded retry, a draft model) for a model some of whose
  layers keep recurrent state (``cfg.layer_kinds()``: models/jamba.py's
  ``mamba``, models/lfm2_moe.py's ``conv``, models/gigachat.py's
  ``gated_delta``): each of them takes a request back to an earlier position by moving a
  cursor or dropping blocks, and stale recurrent state is masked by
  nothing.  ONE message for every such composition.  Preemption by
  replay (scheduler.requeue_slot) is not among them: a replay starts
  from ``reset``."""
  from easyparallellibrary_tpu.serving.kv_cache import recurrent_kinds
  kinds = recurrent_kinds(cfg)
  if kinds:
    raise ValueError(
        f"{feature} is not available for a model with recurrent-state "
        f"layers ({type(cfg).__name__}) of kind {' and '.join(kinds)} — "
        f"{ROADMAP_RECURRENT_STATE}")


def check_latent_cache(cfg, feature: str) -> None:
  """Reject ``feature`` (the paged cache, prefix caching, speculative
  decoding, the guarded retry, a draft model) for a model whose layers
  keep a latent leaf in place of a K/V pair (``cfg.layer_kinds()``,
  models/glm_moe.py), that leaf and an indexer's keys, or a ring behind a
  window (models/dots3_note.py): each of them is built for ``cached_key``
  / ``cached_value`` pairs (the block pool, its radix tree, the rows a
  rejected draft or a retried step leaves behind).  ONE message for
  every such composition and kind."""
  from easyparallellibrary_tpu.serving.kv_cache import (
      LATENT_KINDS, latent_kinds)
  why = dict(zip(LATENT_KINDS, (
      ("a latent cache", ROADMAP_LATENT_CACHE),
      ("latent attention that selects its rows (sparse_latent layers)",
       ROADMAP_SPARSE_LATENT),
      ("latent attention behind a window (window_latent layers)",
       ROADMAP_WINDOW_LATENT))))
  kinds = latent_kinds(cfg)
  if kinds:
    # One message a kind, all of a model's kinds in one refusal.
    raise ValueError(
        f"{feature} is not available for a model with "
        f"{' and '.join(why[k][0] for k in kinds)} "
        f"({type(cfg).__name__}) — {'; '.join(why[k][1] for k in kinds)}")


def check_kv_window(cfg, feature: str) -> None:
  """Reject ``feature`` (the paged cache, prefix caching, speculative
  decoding, the guarded retry, a draft model) for a model some of whose
  attention layers keep their K/V pair as a ring behind a window
  (``cfg.layer_kinds()``: models/smallthinker.py's ``window_kv``): each of
  them moves a cursor back or shares rows by position, and the ring has
  overwritten them.  ONE message for every such composition."""
  from easyparallellibrary_tpu.serving.kv_cache import has_kv_window
  if has_kv_window(cfg):
    raise ValueError(
        f"{feature} is not available for a model with attention layers "
        f"behind a window over K/V pairs ({type(cfg).__name__}, window_kv "
        f"layers) — {ROADMAP_WINDOW_KV}")


def check_divided(model, mesh, num_slots: int, *, paged: bool,
                  prefix_cache: bool, speculative: bool,
                  resilient: bool) -> None:
  """Reject what an engine whose slots are divided over the mesh's
  ``expert`` axis (serving/kv_cache.py ``slot_axis``) cannot be: ONE
  message for every such composition.  It serves a model with held
  experts whose ``__call__`` takes ``expert_axis`` (models/glm_moe.py),
  through the plain step of the contiguous cache, on a mesh whose other
  axes are of size one, with a whole number of slots and of held experts
  a chip."""
  import inspect
  from easyparallellibrary_tpu.serving.kv_cache import slot_axis
  axis = slot_axis(mesh)
  if axis is None:
    return
  name, chips = axis
  cfg = model.cfg
  other = {a: n for a, n in zip(mesh.axis_names, mesh.devices.shape)
           if a != name and n > 1}
  held = getattr(cfg, "experts_held", None)
  experts = held[1] if held is not None else getattr(
      cfg, "n_routed_experts", 0)
  wrong = [what for what, bad in (
      (f"mesh axes {other} beside {name!r}", bool(other)),
      (f"a model whose call takes no expert_axis "
       f"({type(model).__name__})",
       "expert_axis" not in inspect.signature(
           type(model).__call__).parameters),
      (f"{experts} held experts over {chips} chips",
       not experts or experts % chips != 0),
      (f"{num_slots} slots over {chips} chips", num_slots % chips != 0),
      ("the paged cache (serving.paged)", paged),
      ("prefix caching (serving.prefix_cache)", prefix_cache),
      ("speculative decoding (serving.speculative)", speculative),
      ("the guarded step (serving.resilience)", resilient)) if bad]
  if wrong:
    raise ValueError(
        f"an engine divided over mesh axis {name!r} ({chips} chips) is "
        f"not available with {' and '.join(wrong)} — {ROADMAP_DIVIDED}")


def check_draft_compatible(target_cfg, draft_cfg) -> None:
  """Reject draft models whose shapes cannot verify against the target.

  The verify step compares token ids, so the two models must share one
  vocabulary; the draft slot cache must cover every committed position a
  request can reach, so the draft ``max_seq_len`` must be at least the
  target's.  Everything else (depth, width, heads) is free to differ —
  that asymmetry is the whole point of a drafter.
  """
  check_servable(draft_cfg, role="a draft model")
  check_recurrent_state(draft_cfg, "a draft model (its rejected drafts "
                        "roll back)")
  check_latent_cache(draft_cfg, "a draft model")
  check_kv_window(draft_cfg, "a draft model")
  if draft_cfg.vocab_size != target_cfg.vocab_size:
    raise ValueError(
        f"draft model vocab_size {draft_cfg.vocab_size} != target "
        f"vocab_size {target_cfg.vocab_size}: speculative verification "
        f"compares token ids under one vocabulary; use a drafter trained "
        f"on the target tokenizer — {ROADMAP_DRAFT_DISTILL}")
  if draft_cfg.max_seq_len < target_cfg.max_seq_len:
    raise ValueError(
        f"draft model max_seq_len {draft_cfg.max_seq_len} < target "
        f"max_seq_len {target_cfg.max_seq_len}: the draft slot cache "
        f"must cover every position a request can commit (requests are "
        f"admitted against the target's max_seq_len); pad the draft "
        f"config's max_seq_len up to the target's")


def check_draft_fits_chunk(k: int, chunk: int) -> None:
  """The fused step carries each decode slot's last committed token plus
  its ``k`` drafts in one ``chunk``-wide block; reject a drafter the
  step could never schedule."""
  if k < 1:
    raise ValueError(f"speculative draft length k must be >= 1; got {k}")
  if k + 1 > chunk:
    raise ValueError(
        f"speculative draft length k={k} needs prefill_chunk >= k + 1 "
        f"(one chunk holds the last committed token plus the drafts); "
        f"got prefill_chunk {chunk} — raise serving.prefill_chunk or "
        f"lower serving.speculative.k")
