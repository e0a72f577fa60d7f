"""Device time a step and chip inside the expert layers' exchange: the
all-to-all operations of the fused step, which nothing but
``models/moe.py:exchanged_experts`` issues (one under the named scope
``moe_dispatch`` and one under ``moe_combine`` a round and expert layer;
the scopes are in the program's HLO metadata, which the reduced trace
block does not carry, so the operations are taken by their kind: on the
device trace an instruction carries the name of the primitive that made
it, ``all_to_all.<n>``; XLA's own spelling ``all-to-all`` is taken too).
The all-gather of the counts ahead of a layer's first round, where a chip
also waits for the slowest of the four, is NOT in it
(``comm.exposed_pct.backlog`` holds both).  Averaged over the chips, over
the steps of the traced window (``harness/kernel_time.py``'s count of
them).  ``None`` on a program without such operations (one chip, a parent
commit)."""

import re

from perfbench.harness import kernel_time, loop_spans, stats
from perfbench.harness.result import say

ALL_TO_ALL = re.compile(r"^all[-_]to[-_]all")


def read(ctx):
  block = ctx.get("trace")
  periods = loop_spans.step_periods_ms(ctx.get("spans", ()))
  if not block or not periods:
    return None
  seconds = sum(t for name, t in block.get("op_seconds", {}).items()
                if ALL_TO_ALL.match(name))
  if seconds <= 0:
    return None
  closing = sum(s for name, s in block.get("idle_gaps", ())
                if name == kernel_time.NO_SPAN)
  steps = (block["window_s"] - closing) * 1e3 / stats.median(periods)
  if steps <= 0:
    return None
  say(f"exchange: {1e3 * seconds:.1f} ms of all-to-all a chip in "
      f"{steps:.2f} steps")
  return 1e3 * seconds / steps
