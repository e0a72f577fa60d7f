"""The ``kv_write`` readers PR 25 added, on hand-made ``ctx``
(``harness/kernel_time.py``): the kernel's device time over the steps the
traced window held, and ``None`` wherever there is nothing to read."""

import pytest

from perfbench import run as run_lib
from perfbench.tests.test_loop_readers import steps

METRICS = ["engine.kv_write_ms.chat", "engine.kv_write_ms.backlog"]


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


def serve_ctx(custom_calls, window_s=1.4, closing_s=0.2, period_ms=50.0):
  return {"trace": {"window_s": window_s, "custom_calls": custom_calls,
                    "idle_gaps": [["(no host span)", closing_s],
                                  ["serving/device_step", 0.05]]},
          "spans": steps([period_ms] * 30)}


@pytest.mark.parametrize("metric", METRICS)
def test_kernel_time_over_the_steps_the_window_held(metric):
  # 1.4 s less 0.2 s of the benchmark's own closing work = 24 steps of
  # 50 ms; 24 layers a step; 0.144 s of the kernel in all
  ctx = serve_ctx({"kv_write": (576.0, 0.144), "paged_attn": (9.0, 0.5)})
  assert read(metric, ctx) == pytest.approx(6.0)
  # without closing work the whole window steps
  ctx = serve_ctx({"kv_write": (672.0, 0.168)}, closing_s=0.0)
  assert read(metric, ctx) == pytest.approx(6.0)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_gives_none(metric):
  # the reference write: no such custom call (a parent commit, a fallback)
  assert read(metric, serve_ctx({"paged_attn": (9.0, 0.5)})) is None
  assert read(metric, serve_ctx({})) is None
  # a program that records no serving/dispatch span
  ctx = serve_ctx({"kv_write": (576.0, 0.144)})
  ctx["spans"] = [s for s in ctx["spans"] if s[0] != "serving/dispatch"]
  assert read(metric, ctx) is None
  # a train cell's ctx, an untraced ctx
  assert read(metric, {"kind": "train"}) is None
  assert read(metric, {"spans": steps([50.0] * 5)}) is None
