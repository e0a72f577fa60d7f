"""Pipeline schedule policies.

The reference encodes schedules as control-dependency edges between
per-(stage, micro-batch) entrance/exit op sets
(epl/strategies/scheduler.py:36-116): ``PreferForward`` is GPipe-like,
``PreferBackward`` is 1F1B-like (bounds live activations), and
``PreferBackwardOptimizer`` additionally interleaves the optimizer apply.

Here the policies select between two genuinely different programs:

  * PreferForward          — GPipe: autodiff through the SPMD pipeline
                             (parallel/pipeline.py); all micro-batch
                             activations live at the fwd/bwd boundary.
  * PreferBackward         — TRUE 1F1B: the manual
                             fwd/bwd-wavefront scan in
                             parallel/schedule_1f1b.py, whose residual
                             ring structurally bounds live stage inputs to
                             min(M, 2S-1) per stage instead of M, with
                             per-stage recompute (matching the reference's
                             free-and-recompute behavior).  Dispatched by
                             models.gpt.make_gpt_train_step.
  * PreferBackwardOptimizer— PreferBackward + grouped optimizer apply
                             (see runtime/optimizer_helper.py).

``remat_stage`` is also consulted by forward-only Pipeline module uses
(eval), where it toggles per-stage checkpointing.

Megatron-style interleaved (virtual-stage) 1F1B: impossible on the
LOCKSTEP engines (a masked chunk costs the same as a live one, so K-way
chunk interleaving has ramp 2(S - 1/K) device-ticks — never better than
plain 1F1B's 2(S-1); requesting it with 1F1B on the vmapped engines
falls back with a warning, and interleave stays the reference's
circular weight placement there).  The per-rank formulation CAN express
it: ``pipeline.engine="smap"`` with ``pipeline_interleave=K > 1``
dispatches the table-driven interleaved engine
(parallel/pipeline_interleaved.py) whose real branches shrink the ramp
to 2(S-1) + (K-1)S one-chunk ticks.
"""

from __future__ import annotations

import dataclasses

from easyparallellibrary_tpu import constants


@dataclasses.dataclass(frozen=True)
class Schedule:
  name: str
  remat_stage: bool
  grouped_apply: bool


_SCHEDULES = {
    constants.SCHEDULE_PREFER_FORWARD: Schedule(
        constants.SCHEDULE_PREFER_FORWARD, remat_stage=False,
        grouped_apply=False),
    constants.SCHEDULE_PREFER_BACKWARD: Schedule(
        constants.SCHEDULE_PREFER_BACKWARD, remat_stage=True,
        grouped_apply=False),
    constants.SCHEDULE_PREFER_BACKWARD_OPT: Schedule(
        constants.SCHEDULE_PREFER_BACKWARD_OPT, remat_stage=True,
        grouped_apply=True),
}


def get_scheduler(name: str) -> Schedule:
  """Reference: get_scheduler registry (epl/strategies/scheduler.py:126)."""
  if name not in _SCHEDULES:
    raise ValueError(f"Unknown pipeline schedule {name!r}; "
                     f"one of {sorted(_SCHEDULES)}")
  return _SCHEDULES[name]
