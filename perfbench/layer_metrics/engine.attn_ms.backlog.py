"""Device time a step of the ``slot_attn`` kernel (one call an attention
layer: every slot's chunk attends the rows of its own cache under its
bound) where it moves ``serve_tokens_per_s``; the arithmetic is
``harness/kernel_time.py``'s.  ``None`` where the step was built with the
reference attend or the program has no such kernel (a parent commit)."""

from perfbench.harness import kernel_time

KERNEL = "slot_attn"


def read(ctx):
  return kernel_time.ms_per_step(ctx, KERNEL)
