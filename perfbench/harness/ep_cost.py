"""Required work of a cell whose engine is DIVIDED over the chips of one
host (``chips`` 4: the slots and the held experts divided over a mesh
axis), A CHIP, from shapes, the mix and the program's counters.

The device trace's kernel times are averaged over the chips
(``xplane.custom_calls``), so a kernel's roofline in such a cell sets a
chip's time against a chip's work:

* the grouped matmul (``moe_gmm``): a chip streams ITS held experts' three
  matrices once an expert layer and step (``n_routed_experts / chips`` of
  the host's) and moves the rows of the assignments that ARRIVED at its
  experts from all chips, in and out of both products.  The arrivals are
  the program's own count (``serving/held_assignments``: live assignments
  that fell on the host's held experts, all expert layers and chips, a
  step), handed over by ``runners/serve_family_even_counters.py``;
* the index scores and the selected attend (``dsa_index``,
  ``slot_attn_sel``): ``harness/dsa_cost.py``'s requirement a request of
  the mix, EVERY layer a selecting one, at a chip's share of the host's
  completed requests a second.
"""

from __future__ import annotations

from perfbench.harness import dsa_cost, flops as flops_lib
from perfbench.harness import kernel_time, loop_spans, moe_cost, stats
from perfbench.harness.result import say

HELD = "serving/held_assignments"


def moe_step_cost(config: dict, model_opts: dict, chips: int,
                  held_assignments: float):
  """(flops, bytes) a CHIP's grouped matmuls require in one step: every
  expert layer's ``n_routed_experts / chips`` experts read once, and a
  chip's share of the step's ``held_assignments`` (all expert layers, all
  chips) as rows in and out."""
  act = 2 if model_opts.get("dtype", "bfloat16") == "bfloat16" else 4
  layers = moe_cost.expert_layers(config)
  f, b = moe_cost.layer_cost(
      held_assignments / layers / chips, config["n_routed_experts"] // chips,
      config["hidden_size"], config["moe_intermediate_size"], act)
  return layers * f, layers * b


def moe_roofline(ctx):
  """Reader of ``moe_gmm_roofline.ep``; ``None`` on one chip, without the
  counter or where the kernel's name is absent from the trace."""
  config, peaks = ctx.get("config"), ctx.get("peaks")
  held = (ctx.get("counters") or {}).get(HELD)
  chips = ctx.get("chips", 1)
  if not (config and peaks and held and chips > 1):
    return None
  ms = kernel_time.ms_per_step(ctx, moe_cost.KERNEL)
  if ms is None:
    return None
  mean_held = sum(held) / len(held)
  f, b = moe_step_cost(config, ctx.get("model", {}), chips, mean_held)
  pct, bound = flops_lib.roofline_pct(f, b, ms / 1e3,
                                      peaks["bf16_flops_per_s"],
                                      peaks["hbm_bytes_per_s"])
  say(f"moe_gmm: {ms:.3f} ms a step and chip against {b / 1e9:.3f} GB and "
      f"{f / 1e9:.2f} GFLOP required for {mean_held / chips:.1f} arriving "
      f"assignments on {config['n_routed_experts'] // chips} experts a "
      f"layer, {bound}-bound")
  return pct


def all_selecting(config: dict) -> dict:
  """``config`` in ``dsa_cost.sizes``' terms for a model EVERY layer of
  which selects: ``layer_types`` all full, a window layer of no width (it
  has none, and ``dsa_cost`` then counts nothing for one)."""
  return dict(config,
              layer_types=[dsa_cost.FULL] * config["num_hidden_layers"],
              swa_kv_lora_rank=0, swa_qk_rope_head_dim=0,
              swa_num_attention_heads=0, sliding_window_size=0)


def selecting_roofline(ctx, kernel: str, metric: str):
  """Reader of ``dsa_index_roofline.ep`` / ``sel_attn_roofline.ep``: as
  ``dsa_cost.roofline`` (a steady-state ESTIMATE: the work from the
  window's throughput and the mix's lengths, the time from the device
  trace), A CHIP: the host's completed requests a second over its chips
  against the kernel's busy share of the step period, which the trace
  gives a chip."""
  config, peaks = ctx.get("config"), ctx.get("peaks")
  rate, chips = ctx.get("tokens_per_s"), ctx.get("chips", 1)
  if not (config and peaks and rate and chips > 1
          and "index_topk" in config):
    return None
  ms = kernel_time.ms_per_step(ctx, kernel)
  periods = loop_spans.step_periods_ms(ctx.get("spans", ()))
  if ms is None or not periods:
    return None
  found = dsa_cost.cell_of(metric, ctx)
  if found is None:
    return None
  cell_file, mix = found
  act = 2 if ctx.get("model", {}).get("dtype", "bfloat16") == "bfloat16" else 4
  work, mean_out = dsa_cost.mix_mean_work(
      all_selecting(config), mix, cell_file["engine"]["prefill_chunk"], act)
  f, b = (rate / chips / mean_out * x for x in work[kernel])
  busy = ms / stats.median(periods)
  pct, bound = flops_lib.roofline_pct(
      f, b, busy, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
  say(f"{kernel}: busy {100 * busy:.1f}% of the step period against "
      f"{f / 1e12:.3f} TFLOP and {b / 1e9:.3f} GB required a second and "
      f"chip at {rate / chips / mean_out:.3f} requests/s a chip, "
      f"{bound}-bound (steady-state estimate)")
  return pct
