"""Device time a step of the ``gdn_scan`` kernel (one call a linear-attention
layer: every slot's matrix state advanced by its chunk's live positions, one
position's form or the chunk's) where it moves ``serve_tokens_per_s``; the
arithmetic is ``harness/kernel_time.py``'s.  ``None`` where the step was
built with the reference scan or the program has no such kernel (a parent
commit)."""

from perfbench.harness import gdn_cost, kernel_time


def read(ctx):
  return kernel_time.ms_per_step(ctx, gdn_cost.KERNEL)
