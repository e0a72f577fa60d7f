"""The flash-attention kernels' share of their roofline, forward and
backward together.

The three Mosaic kernels (forward, dK/dV, dQ) all appear in the device
trace as custom calls named after the scope that calls them (``attn``):
they cannot be told apart by a stable name yet, so they are judged
together.  Required work per step and layer: one causal forward and one
backward (``harness/flops.py``; the remat policy saves the forward's
outputs, so it runs once).  Time: the ``attn`` custom calls' device time
per step.  Which bound holds is printed.

Not listed in ``BENCHMARK.json`` at present: the one-chip train cell that
it was read in (16.2% there) was taken out (PERF.md, section 6), and on
four chips the kernels carry the name of the ``shard_map`` around them,
which is no kernel's name.  A cell that brings it back adds an entry
only; ``tests/`` read it on the trace recorded on one chip.
"""

from perfbench.harness import flops, stats
from perfbench.harness.result import say

SCOPE = "attn"


def read(ctx):
  block, att, peaks = ctx.get("trace"), ctx.get("attention"), ctx.get("peaks")
  if not (block and att and peaks and ctx.get("step_done_gaps_ms")):
    return None
  calls, seconds = block["custom_calls"].get(SCOPE, (0, 0.0))
  if seconds <= 0:
    return None
  step_s = stats.median(ctx["step_done_gaps_ms"]) / 1e3
  steps = block["window_s"] / step_s
  shape = (att["batch_per_chip"], att["heads"], att["seq"], att["head_dim"])
  f_fwd, b_fwd = flops.flash_fwd_cost(*shape)
  f_bwd, b_bwd = flops.flash_bwd_cost(*shape)
  pct, bound = flops.roofline_pct(
      att["layers"] * (f_fwd + f_bwd), att["layers"] * (b_fwd + b_bwd),
      seconds / steps, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
  say(f"flash kernels: {calls / steps:.1f} calls a step, "
      f"{1e3 * seconds / steps:.2f} ms a step, {bound}-bound")
  return pct
