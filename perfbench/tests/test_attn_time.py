"""The ``slot_attn`` readers PR 29 added, on hand-made ``ctx``: the
kernel's device time over the steps the traced window held
(``harness/kernel_time.py``), and ``None`` wherever there is nothing to
read (a parent commit, a step built with the reference attend)."""

import pytest

from perfbench.tests.test_kernel_time import read, serve_ctx

METRICS = ["engine.attn_ms.chat", "engine.attn_ms.backlog"]


@pytest.mark.parametrize("metric", METRICS)
def test_attend_time_over_the_steps_the_window_held(metric):
  # 24 steps of 50 ms, 24 layers a step, 0.096 s of the kernel in all
  ctx = serve_ctx({"slot_attn": (576.0, 0.096), "kv_write": (576.0, 0.144)})
  assert read(metric, ctx) == pytest.approx(4.0)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_gives_none(metric):
  assert read(metric, serve_ctx({"kv_write": (576.0, 0.144)})) is None
  assert read(metric, {"kind": "train"}) is None
