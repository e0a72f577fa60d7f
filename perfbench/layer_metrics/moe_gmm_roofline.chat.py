"""The grouped-matmul kernel's share of its roofline in a cell whose
configuration names its experts ``num_experts`` and its dense layers
``num_dense_layers`` (``model_type: lfm2_moe``).

Required work a step (``harness/moe_cost.layer_cost`` a layer, times the
expert layers): all held experts' three matrices read once, and the rows of
each live assignment in and out, the slots that were live each feeding at
least one position (a floor).  Time: the ``moe_gmm`` custom calls' device
time a step (``harness/kernel_time.py``).  Which bound holds is printed.

The count assumes EVERY expert is touched in every layer of every step.
With ``a`` live assignments a layer over ``E`` experts one goes untouched
with probability about ``exp(-a / E)``, so the reader returns ``None`` where
the window's mean live slots times ``num_experts_per_tok`` is under ``10 x
E``: below that the requirement would be overstated (the program's counter
``serving/experts_touched_min`` says what was touched; the runners do not
hand it over yet).  ``None`` too where the kernel's name is absent from the
trace or the run handed over no such configuration.
"""

from perfbench.harness import flops, kernel_time, moe_cost
from perfbench.harness.result import say

# Live assignments a layer, per expert, under which the floor is not one.
MIN_ASSIGNMENTS_PER_EXPERT = 10


def expert_layers(config: dict) -> int:
  """How many layers of the configuration are expert layers."""
  return config["num_hidden_layers"] - config["num_dense_layers"]


def step_cost(config: dict, model_opts: dict, live_slots: float):
  """(flops, bytes) of one serving step's grouped matmuls: every expert
  layer once, ``live_slots`` slots feeding at least one position each,
  each position going to ``num_experts_per_tok`` experts."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  f, b = moe_cost.layer_cost(
      live_slots * config["num_experts_per_tok"], config["num_experts"],
      config["hidden_size"], config["moe_intermediate_size"], act)
  n = expert_layers(config)
  return n * f, n * b


def read(ctx):
  config, peaks = ctx.get("config"), ctx.get("peaks")
  active = ctx.get("active_slots")
  if not (config and peaks and active and "num_experts" in config
          and "num_dense_layers" in config):
    return None
  live = sum(active) / len(active)
  if (live * config["num_experts_per_tok"]
      < MIN_ASSIGNMENTS_PER_EXPERT * config["num_experts"]):
    say(f"moe_gmm: {live:.1f} live slots a step are too few for every one "
        f"of {config['num_experts']} experts to be touched; no roofline")
    return None
  ms = kernel_time.ms_per_step(ctx, moe_cost.KERNEL)
  if ms is None:
    return None
  f, b = step_cost(config, ctx.get("model", {}), live)
  pct, bound = flops.roofline_pct(f, b, ms / 1e3, peaks["bf16_flops_per_s"],
                                  peaks["hbm_bytes_per_s"])
  say(f"moe_gmm: {ms:.3f} ms a step against {b / 1e9:.3f} GB and "
      f"{f / 1e9:.2f} GFLOP required for {live:.1f} live slots, "
      f"{bound}-bound")
  return pct
