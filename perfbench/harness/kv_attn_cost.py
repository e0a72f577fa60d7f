"""Operations and bytes that attention behind a WINDOW over a ring of K/V
PAIRS requires, from shapes and the mix's own lengths
(``model_type: smallthinker``).

The kernel (``slot_attn_kvwin``, ``easyparallellibrary_tpu/kernels/
slot_attention.py``): in every window layer, each live slot's queries
attend the rows of the slot's K and V rings that their windows cover.
Counted is what ANY implementation must do, in ``harness/dsa_cost.py``'s
terms (:func:`kernel` is one of its kernel records, so its
``request_work`` sums it over a request):

* bytes: a slot-step whose queries sit at ``[cursor, cursor + num_valid)``
  must read the rows ``[cursor - window + 1, cursor + num_valid)`` that
  exist, once, as keys and as values: ``min(cursor + num_valid, window - 1 +
  num_valid)`` rows of ``2 x num_key_value_heads x head_dim`` values (2,048
  B in bfloat16 at 4 heads of 128).  The queries and the output, a slot's
  few rows, are left out.  The program counts the same sum a step from the
  plan it holds, counter ``serving/kv_window_rows``
  (``serving/engine.py:_slot_rows``);
* flops: ``4 x num_attention_heads x head_dim`` a (query, row): the score
  and the value product of every head, ``min(t + 1, window)`` rows a query
  at ``t``.  A decoding slot's requirement is 7 flops a byte and a whole
  chunk's 222, both under the chip's ridge of 240: memory-bound.

:func:`roofline` turns a traced run into a share of the roofline as
``dsa_cost.roofline`` does: the window's completed requests a second times
the mix's mean requirement a request, over the kernel's busy share of the
step period.  A steady-state ESTIMATE, labelled ``host_clock`` in the
manifest: ``runners/serve_family.py`` hands a reader the spans and ONE
counter (``serving/active_slots``), not ``serving/kv_window_rows``, so the
traced steps' own rows cannot be counted here (ROADMAP R1; PERF.md section
7, item 12).
"""

from __future__ import annotations

import numpy as np

from perfbench.harness import dsa_cost, flops as flops_lib
from perfbench.harness import kernel_time, loop_spans, stats, traffic
from perfbench.harness.result import say

KERNEL = "slot_attn_kvwin"


def window_layers(config: dict) -> int:
  """How many layers of the configuration attend behind the window."""
  return sum(1 for w in config["sliding_window_layout"] if w)


def row_bytes(config: dict, act_bytes: int = 2) -> int:
  """One position's keys and values in one layer."""
  return (2 * config["num_key_value_heads"] * config["head_dim"]
          * act_bytes)


def kernel(config: dict, act_bytes: int = 2) -> dict:
  """The kernel as ``dsa_cost.request_work`` takes one: flops a (query,
  row), bytes a row a slot-step, the most rows a query reads, a slot-step
  reading the UNION of its queries' windows, and the layers that run it."""
  return dict(flops=4 * config["num_attention_heads"] * config["head_dim"],
              row_bytes=row_bytes(config, act_bytes),
              most=config["sliding_window_size"], union=True,
              layers=window_layers(config))


def mix_mean_work(config: dict, mix: dict, chunk: int, act_bytes: int = 2):
  """``((flops, bytes), mean output length)``: what a request of the mix
  requires on average in all the window layers, over ``dsa_cost.GRID``
  quantiles of each length, an output cut where the mix's total would be
  passed as the generator cuts it."""
  k = kernel(config, act_bytes)
  cap = mix.get("max_total_len")
  pairs = [(int(p), int(o if cap is None else max(1, min(o, cap - p))))
           for p in traffic.length_quantiles(mix["prompt_len"], dsa_cost.GRID)
           for o in traffic.length_quantiles(mix["output_len"],
                                             dsa_cost.GRID)]
  work = np.array([dsa_cost.request_work(k, p, o, chunk) for p, o in pairs],
                  float)
  return (tuple(k["layers"] * work.mean(axis=0)),
          float(np.mean([o for _, o in pairs])))


def roofline(ctx, metric: str):
  """Reader of ``kv_win_attn_roofline`` (module docstring); ``None`` where
  the run handed over no such configuration or the kernel's name is absent
  from the trace (a parent commit, a step on the reference lowering)."""
  config, peaks = ctx.get("config"), ctx.get("peaks")
  rate = ctx.get("tokens_per_s")
  if not (config and peaks and rate and "sliding_window_layout" in config):
    return None
  ms = kernel_time.ms_per_step(ctx, KERNEL)
  periods = loop_spans.step_periods_ms(ctx.get("spans", ()))
  if ms is None or not periods:
    return None
  found = dsa_cost.cell_of(metric, ctx)
  if found is None:
    return None
  cell_file, mix = found
  act = 2 if ctx.get("model", {}).get("dtype", "bfloat16") == "bfloat16" else 4
  work, mean_out = mix_mean_work(config, mix,
                                 cell_file["engine"]["prefill_chunk"], act)
  f, b = (rate / mean_out * x for x in work)        # a second of wall
  busy = ms / stats.median(periods)
  pct, bound = flops_lib.roofline_pct(
      f, b, busy, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
  say(f"{KERNEL}: busy {100 * busy:.1f}% of the step period against "
      f"{f / 1e12:.3f} TFLOP and {b / 1e9:.3f} GB required a second at "
      f"{rate / mean_out:.3f} requests/s, {bound}-bound (steady-state "
      f"estimate)")
  return pct
