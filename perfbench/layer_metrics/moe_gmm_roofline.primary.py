"""The grouped-matmul kernel's share of its roofline in a configuration
whose experts are named ``moe_num_primary_experts`` of
``moe_ffn_hidden_size``, ``moe_num_active_primary_experts`` a token
(``model_type: smallthinker``: every layer an expert layer, no dense one).

As ``moe_gmm_roofline`` (``harness/moe_cost.py:layer_cost``: all the
experts' three matrices read once a layer and step, 8 x 64 x 11.80 MB =
6.04 GB, and the rows of each live assignment in and out, the slots that
were live each feeding at least one position: a floor), the counts taken
from this configuration's own keys.  Time: the ``moe_gmm`` custom calls'
device time a step (``harness/kernel_time.py``).  Which bound holds is
printed.  ``None`` where the kernel's name is absent from the trace or the
run handed over no such configuration.
"""

from perfbench.harness import flops, kernel_time, moe_cost
from perfbench.harness.result import say


def step_cost(config: dict, model_opts: dict, live_slots: float):
  """(flops, bytes) of one serving step's grouped matmuls: every layer
  once."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  f, b = moe_cost.layer_cost(
      live_slots * config["moe_num_active_primary_experts"],
      config["moe_num_primary_experts"], config["hidden_size"],
      config["moe_ffn_hidden_size"], act)
  n = config["num_hidden_layers"]
  return n * f, n * b


def read(ctx):
  config, peaks = ctx.get("config"), ctx.get("peaks")
  active = ctx.get("active_slots")
  if not (config and peaks and active
          and "moe_num_primary_experts" in config):
    return None
  ms = kernel_time.ms_per_step(ctx, moe_cost.KERNEL)
  if ms is None:
    return None
  live = sum(active) / len(active)
  f, b = step_cost(config, ctx.get("model", {}), live)
  pct, bound = flops.roofline_pct(f, b, ms / 1e3, peaks["bf16_flops_per_s"],
                                  peaks["hbm_bytes_per_s"])
  say(f"moe_gmm: {ms:.3f} ms a step against {b / 1e9:.3f} GB and "
      f"{f / 1e9:.2f} GFLOP required for {live:.1f} live slots on "
      f"{config['moe_num_primary_experts']} experts a layer, {bound}-bound")
  return pct
