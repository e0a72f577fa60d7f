"""The grouped-matmul kernel's share of its roofline.

Required work a step (``harness/moe_cost.py``): in every expert layer all
held experts' three matrices read once, and the rows of each live
assignment in and out (the slots that were live each feeding at least one
position: a floor).  Time: the ``moe_gmm`` custom calls' device time a step
(``harness/kernel_time.py``).  Which bound holds is printed.  ``None``
where the kernel's name is absent from the trace or the run handed over no
configuration with routed experts.
"""

from perfbench.harness import flops, kernel_time, moe_cost
from perfbench.harness.result import say


def read(ctx):
  config, peaks = ctx.get("config"), ctx.get("peaks")
  active = ctx.get("active_slots")
  if not (config and peaks and active and "n_routed_experts" in config):
    return None
  ms = kernel_time.ms_per_step(ctx, moe_cost.KERNEL)
  if ms is None:
    return None
  live = sum(active) / len(active)
  f, b = moe_cost.step_cost(config, ctx.get("model", {}), live)
  pct, bound = flops.roofline_pct(f, b, ms / 1e3, peaks["bf16_flops_per_s"],
                                  peaks["hbm_bytes_per_s"])
  say(f"moe_gmm: {ms:.3f} ms a step against {b / 1e9:.3f} GB and "
      f"{f / 1e9:.2f} GFLOP required for {live:.1f} live slots, "
      f"{bound}-bound")
  return pct
