"""Device time a step of the ``dsa_index`` kernel (one call a full layer: the
index scores of every live query against the index rows of its slot under
its bound, the heads folded inside the kernel) where it moves
``serve_tokens_per_s``; the arithmetic is ``harness/kernel_time.py``'s.
``None`` where the step was built with the reference lowering or the
program has no such kernel (a parent commit)."""

from perfbench.harness import dsa_cost, kernel_time


def read(ctx):
  return kernel_time.ms_per_step(ctx, dsa_cost.DSA_INDEX)
