"""Device time a step of the ``moe_gmm`` kernel (two calls an expert
layer: the step's sorted assignments times their experts' gate-and-up
matrices, then their down matrices) where it moves ``itl_p95_ms``; the
arithmetic is ``harness/kernel_time.py``'s.  ``None`` where the step was
built with the reference lowering or the program has no such kernel (a
parent commit)."""

from perfbench.harness import kernel_time, moe_cost


def read(ctx):
  return kernel_time.ms_per_step(ctx, moe_cost.KERNEL)
