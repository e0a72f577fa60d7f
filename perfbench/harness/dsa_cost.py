"""Operations and bytes that latent attention which SELECTS its rows, and
latent attention behind a WINDOW, require, from shapes alone
(``model_type: dots3_note``).

Three kernels (``easyparallellibrary_tpu/kernels/dsa_index.py``,
``kernels/slot_attention.py``), counted for what ANY implementation must
do for a query at position ``t`` (0-based) of a request:

* ``dsa_index`` (a full layer): every row ``s <= t`` is scored by every
  index head: ``2 x index_n_heads x index_head_dim`` flops a (query, row);
  a slot-step reads the slot's index rows under its bound once, ``2 x
  index_head_dim`` bytes a row in bfloat16 (256 B);
* ``slot_attn_sel`` (a full layer): the query attends ``min(t + 1,
  index_topk)`` selected rows in the absorbed form: scores over the latent
  row's ``kv_lora_rank + qk_rope_head_dim`` values and the value product
  over its leading ``kv_lora_rank``, every head: ``2 x heads x (576 +
  512)`` flops a (query, selected row); a slot-step reads at least one
  query's selection, ``min(bound, index_topk)`` rows of ``2 x 576`` bytes
  (a floor: the union of a chunk's selections is larger);
* ``slot_attn_win`` (a window layer): ``min(t + 1, sliding_window_size)``
  rows, ``2 x swa_heads x (1088 + 1024)`` flops a (query, window row); a
  slot-step reads the rows its queries' windows cover.

A kernel that computes more (the selected attend scores every row under
the bound and masks the unselected; a one-token decode is padded to a tile
of positions) is charged for it: the requirement is the selection's.

:func:`request_work` sums a kernel's requirement over one request of
``prompt`` and ``output`` tokens served in chunks of ``chunk``;
:func:`mix_mean_work` averages it over a backlog mix's own length
quantiles (``harness/traffic.py`` permutes the prompts' and the outputs'
quantiles independently, so every pair is equally likely); and
:func:`roofline` turns a traced run into a share of the roofline:

    required work a second = ``tokens_per_s`` / mean output length x mean
    work a request (every request that completes brings its whole work),

over the kernel's busy share of the step period.  An ESTIMATE, and the
manifest says ``host_clock`` for it: the work comes from the whole window's
throughput on the host's clock and the mix's lengths, only the time from
the device trace, whose second or so holds whatever share of prefill the
moment has.  It holds when both see the population's mix of phases, which
a backlog cell's ramp is there to bring about; a window of a few dozen
requests can sit a few percent off it, a trace tens of percent.  Counting
the traced steps' own work needs the program's per-step counters
(``serving/index_rows``, ``serving/selected_rows``,
``serving/window_rows``), which the runner does not hand to a reader.
"""

from __future__ import annotations

import numpy as np

from perfbench.harness import flops as flops_lib
from perfbench.harness import kernel_time, loop_spans, manifest, stats, traffic
from perfbench.harness.result import say

DSA_INDEX, SEL_ATTN, WIN_ATTN = "dsa_index", "slot_attn_sel", "slot_attn_win"
FULL, SLIDING = "full_attention", "sliding_attention"
# Pairs of quantiles the mean runs over: GRID of each length.
GRID = 64


def sizes(config: dict, act_bytes: int = 2) -> dict:
  """Per kernel: flops a (query, row), bytes a row a slot-step, the most
  rows a query reads (None: every row under it), whether a slot-step must
  read the UNION of its queries' rows (a window: they are contiguous) or
  at least one query's (a selection: a floor), and the layers that run
  it."""
  c = config
  n = lambda kind: sum(1 for t in c["layer_types"] if t == kind)
  full_row = c["kv_lora_rank"] + c["qk_rope_head_dim"]
  swa_row = c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"]
  return {
      DSA_INDEX: dict(
          flops=2 * c["index_n_heads"] * c["index_head_dim"],
          row_bytes=c["index_head_dim"] * act_bytes, most=None, union=True,
          layers=n(FULL)),
      SEL_ATTN: dict(
          flops=2 * c["num_attention_heads"] * (full_row + c["kv_lora_rank"]),
          row_bytes=full_row * act_bytes, most=c["index_topk"], union=False,
          layers=n(FULL)),
      WIN_ATTN: dict(
          flops=2 * c["swa_num_attention_heads"]
          * (swa_row + c["swa_kv_lora_rank"]),
          row_bytes=swa_row * act_bytes, most=c["sliding_window_size"],
          union=True, layers=n(SLIDING)),
  }


def rows_up_to(first: int, count: int, most) -> int:
  """``sum of min(t + 1, most)`` over the ``count`` queries at ``t = first,
  first + 1, ...`` (``most`` None: ``t + 1``)."""
  t = np.arange(first, first + count, dtype=np.int64) + 1
  return int(np.sum(t if most is None else np.minimum(t, most)))


def step_rows(kernel: dict, start, end):
  """Rows a slot-step whose queries sit at ``[start, end)`` must read:
  every row under ``end`` that one of its queries reads (``union``), or
  what one query reads (a floor)."""
  most = kernel["most"]
  if most is None:
    return end
  reach = most + (end - start - 1 if kernel["union"] else 0)
  return np.minimum(end, reach)


def request_work(kernel: dict, prompt: int, output: int, chunk: int):
  """``(flops, bytes)`` one layer's kernel requires over a request: its
  ``prompt + output - 1`` fed positions (the last token generated is never
  fed), prefilled ``chunk`` at a time and decoded one a step."""
  fed = prompt + output - 1
  ends = np.concatenate([
      np.minimum(np.arange(chunk, prompt + chunk, chunk), prompt),
      prompt + np.arange(1, output)]).astype(np.int64)
  starts = np.concatenate([[0], ends[:-1]])
  return (kernel["flops"] * rows_up_to(0, fed, kernel["most"]),
          int(np.sum(step_rows(kernel, starts, ends))) * kernel["row_bytes"])


def mix_mean_work(config: dict, mix: dict, chunk: int, act_bytes: int = 2):
  """``({kernel: (flops, bytes)}, mean output length)``: what a request of
  the mix requires on average, all of the kernel's layers."""
  prompts = traffic.length_quantiles(mix["prompt_len"], GRID)
  outputs = traffic.length_quantiles(mix["output_len"], GRID)
  out = {}
  for name, kernel in sizes(config, act_bytes).items():
    work = np.array([request_work(kernel, int(p), int(o), chunk)
                     for p in prompts for o in outputs], float)
    out[name] = tuple(kernel["layers"] * work.mean(axis=0))
  return out, float(np.mean(outputs))


def cell_of(metric: str, ctx):
  """``(cell file, traffic mix)`` of the RUNNING cell, or ``None``: the one
  cell ``BENCHMARK.json`` lists under the per-layer metric ``metric``
  whose configuration, slots and kind of traffic are the run's.  The
  run's context names no cell and lacks the chunk and the mix's lengths;
  two listed cells it cannot tell apart get no number rather than the
  other's work."""
  man = manifest.Manifest()
  entry = next(m for m in man.doc["per_layer"] if m["name"] == metric)
  hits = []
  for name in entry["workloads"]:
    cell = man.workload(name)
    cell_file, mix = man.cell_file(name), man.traffic_file(cell["traffic"])
    if (man.config_file(cell["config"]) == ctx.get("config")
        and cell_file["engine"]["num_slots"] == ctx.get("num_slots")
        and mix["kind"] == ctx.get("kind")):
      hits.append((cell_file, mix))
  if len(hits) != 1:
    say(f"{metric}: {len(hits)} of the cells {entry['workloads']} match "
        "the run's configuration, slots and kind of traffic; no number")
    return None
  return hits[0]


def roofline(ctx, kernel: str, metric: str):
  """Reader of ``<kernel>_roofline`` (module docstring); ``None`` where
  the run handed over no such configuration or the kernel's name is absent
  from the trace (a parent commit, a step on the reference lowering)."""
  config, peaks = ctx.get("config"), ctx.get("peaks")
  rate = ctx.get("tokens_per_s")
  if not (config and peaks and rate and "index_topk" in config):
    return None
  ms = kernel_time.ms_per_step(ctx, kernel)
  periods = loop_spans.step_periods_ms(ctx.get("spans", ()))
  if ms is None or not periods:
    return None
  found = cell_of(metric, ctx)
  if found is None:
    return None
  cell_file, mix = found
  act = 2 if ctx.get("model", {}).get("dtype", "bfloat16") == "bfloat16" else 4
  work, mean_out = mix_mean_work(config, mix,
                                 cell_file["engine"]["prefill_chunk"], act)
  f, b = (rate / mean_out * x for x in work[kernel])   # a second of wall
  busy = ms / stats.median(periods)
  pct, bound = flops_lib.roofline_pct(
      f, b, busy, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
  say(f"{kernel}: busy {100 * busy:.1f}% of the step period against "
      f"{f / 1e12:.3f} TFLOP and {b / 1e9:.3f} GB required a second at "
      f"{rate / mean_out:.3f} requests/s, {bound}-bound (steady-state "
      f"estimate)")
  return pct
