"""Device time a step of the ``slot_attn_kvwin`` kernel (two launches a
window layer over K/V pairs: the decoding slots on their one position, then
the prefilling slots on their chunk; every live query attends the rows of
its slot's K and V rings that its window covers) where it moves
``serve_tokens_per_s``; the arithmetic is ``harness/kernel_time.py``'s.
``None`` where the step was built with the reference lowering or the
program has no such kernel (a parent commit)."""

from perfbench.harness import kernel_time, kv_attn_cost


def read(ctx):
  return kernel_time.ms_per_step(ctx, kv_attn_cost.KERNEL)
