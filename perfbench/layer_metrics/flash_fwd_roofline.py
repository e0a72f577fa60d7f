"""The flash-attention forward kernel's share of its roofline.

The program names its three Mosaic kernels (``kernels/flash_attention.py``:
``flash_fwd``, ``flash_dkv``, ``flash_dq``), and a kernel's name is the name
of its custom-call instruction on the device trace's ``XLA Ops`` line, on
one chip and inside a ``shard_map`` alike.  Required work per step and layer:
one causal forward (``harness/flops.py``; the remat policy saves the
forward's outputs, so it runs once).  Time: the named custom calls' device
time per step and chip, steps counted as ``flash_attn_roofline.py`` counts
them.  Which bound holds is printed.  ``None`` where a name is absent: a
program that does not name its kernels, the trace recorded before it did.
"""

from perfbench.harness import flops, stats
from perfbench.harness.result import say

KERNELS = ("flash_fwd",)


def share(ctx, kernels, cost_fn, label):
  """Roofline share in percent of ``kernels`` together against
  ``cost_fn(batch, heads, seq, head_dim)`` x layers a step."""
  block, att, peaks = ctx.get("trace"), ctx.get("attention"), ctx.get("peaks")
  if not (block and att and peaks and ctx.get("step_done_gaps_ms")):
    return None
  found = [block["custom_calls"].get(k) for k in kernels]
  if not all(found):
    return None
  calls = sum(c for c, _ in found)
  seconds = sum(s for _, s in found)
  if seconds <= 0:
    return None
  step_s = stats.median(ctx["step_done_gaps_ms"]) / 1e3
  steps = block["window_s"] / step_s
  f, b = cost_fn(att["batch_per_chip"], att["heads"], att["seq"],
                 att["head_dim"])
  pct, bound = flops.roofline_pct(
      att["layers"] * f, att["layers"] * b, seconds / steps,
      peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
  say(f"{label} ({' + '.join(kernels)}): {calls / steps:.1f} calls a step, "
      f"{1e3 * seconds / steps:.2f} ms a step, {bound}-bound")
  return pct


def read(ctx):
  return share(ctx, KERNELS, flops.flash_fwd_cost, "flash forward")
