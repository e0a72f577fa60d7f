"""The selective-scan kernel's share of its roofline.

Required work a step (``harness/ssm_cost.py``): every Mamba layer's scan
once, the slots that were live each advancing by at least one position:
their float32 state in and out, one position's activations.  Time: the
``ssm_scan`` custom calls' device time a step (``harness/kernel_time.py``).
Which bound holds is printed.  ``None`` where the kernel's name is absent
from the trace or the run handed over no model configuration.
"""

from perfbench.harness import flops, kernel_time, ssm_cost
from perfbench.harness.result import say


def read(ctx):
  config, peaks = ctx.get("config"), ctx.get("peaks")
  active = ctx.get("active_slots")
  if not (config and peaks and active and "mamba_d_state" in config):
    return None
  ms = kernel_time.ms_per_step(ctx, ssm_cost.KERNEL)
  if ms is None:
    return None
  live = sum(active) / len(active)
  f, b = ssm_cost.step_cost(config, ctx.get("model", {}), live)
  pct, bound = flops.roofline_pct(f, b, ms / 1e3, peaks["bf16_flops_per_s"],
                                  peaks["hbm_bytes_per_s"])
  say(f"ssm_scan: {ms:.3f} ms a step against {b / 1e9:.3f} GB and "
      f"{f / 1e9:.2f} GFLOP required for {live:.1f} live slots, "
      f"{bound}-bound")
  return pct
