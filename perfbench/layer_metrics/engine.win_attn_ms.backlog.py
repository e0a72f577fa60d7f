"""Device time a step of the ``slot_attn_win`` kernel (one call a window
layer: every live query attends the rows of its slot's ring that its window
covers) where it moves ``serve_tokens_per_s``; the arithmetic is
``harness/kernel_time.py``'s.  ``None`` where the step was built with the
reference lowering or the program has no such kernel (a parent commit)."""

from perfbench.harness import dsa_cost, kernel_time


def read(ctx):
  return kernel_time.ms_per_step(ctx, dsa_cost.WIN_ATTN)
