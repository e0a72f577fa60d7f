"""Median time between the completions of successive steps, while the
window's own pipelined loop runs (host clock, ``block_until_ready``)."""

from perfbench.harness import stats


def read(ctx):
  gaps = ctx.get("step_done_gaps_ms")
  return stats.median(gaps) if gaps else None
