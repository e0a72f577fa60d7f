"""Device time a step of the ``ssm_scan`` kernel (one call a Mamba layer:
every slot's recurrent state advanced by its chunk's live positions) where
it moves ``serve_tokens_per_s``; the arithmetic is
``harness/kernel_time.py``'s.  ``None`` where the step was built with the
reference scan or the program has no such kernel (a parent commit)."""

from perfbench.harness import kernel_time, ssm_cost


def read(ctx):
  return kernel_time.ms_per_step(ctx, ssm_cost.KERNEL)
