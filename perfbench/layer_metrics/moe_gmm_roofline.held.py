"""The grouped-matmul kernel's share of its roofline in a cell whose chip
holds a SHARE of each layer's routed experts (``n_routed_experts`` of
``n_routed_experts_published`` in the configuration's file).

As ``moe_gmm_roofline`` (``harness/moe_cost.py``: all HELD experts' three
matrices read once a layer and step, the rows of each live assignment in
and out, the slots that were live each feeding at least one position: a
floor), with the assignments that fall on held experts: of a live
position's ``num_experts_per_tok`` choices over the router's published
width, ``held / published`` land here on average.  Time: the ``moe_gmm``
custom calls' device time a step (``harness/kernel_time.py``).  Which
bound holds is printed.  ``None`` where the kernel's name is absent from
the trace or the configuration holds every expert.
"""

from perfbench.harness import flops, kernel_time, moe_cost
from perfbench.harness.result import say


def step_cost(config: dict, model_opts: dict, live_slots: float):
  """(flops, bytes) of one serving step's grouped matmuls over the held
  experts: every expert layer once."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  held, published = (config["n_routed_experts"],
                     config["n_routed_experts_published"])
  f, b = moe_cost.layer_cost(
      live_slots * config["num_experts_per_tok"] * held / published, held,
      config["hidden_size"], config["moe_intermediate_size"], act)
  n = moe_cost.expert_layers(config)
  return n * f, n * b


def read(ctx):
  config, peaks = ctx.get("config"), ctx.get("peaks")
  active = ctx.get("active_slots")
  if not (config and peaks and active
          and "n_routed_experts_published" in config):
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
      f"{config['n_routed_experts']} of "
      f"{config['n_routed_experts_published']} experts, {bound}-bound")
  return pct
