"""The readers PR 24 added, on hand-made ``ctx``: the serving loop's
dispatch share and stalls (``harness/loop_spans.py``) and the named flash
kernels' roofline shares; then all six through the entry point of a toy
checkout, added as new files and new manifest entries only."""

import json
import os

import pytest

from perfbench import run as run_lib
from perfbench.harness import flops, loop_spans, spans, xplane
from perfbench.tests import toy_checkout

DATA = os.path.join(toy_checkout.HERE, "data")
MS = 1e6


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


def steps(periods_ms, dispatch_ms=2.0, fetch_ms=80.0, extra=()):
  """One ``device_step`` tiled by ``dispatch`` and ``fetch`` per period,
  a ``plan`` before and a ``commit`` after it, 1 ms each."""
  out, t = list(extra), 0.0
  for p in periods_ms + [periods_ms[-1]]:
    d0, d1, f1 = t, t + dispatch_ms * MS, t + (dispatch_ms + fetch_ms) * MS
    out += [("serving/plan", d0 - 1 * MS, d0),
            ("serving/device_step", d0, f1), ("serving/dispatch", d0, d1),
            ("serving/fetch", d1, f1), ("serving/commit", f1, f1 + 1 * MS)]
    t += p * MS
  return out


def test_quiet_series_has_no_stall_and_a_stalled_one_reads_it():
  quiet = {"spans": steps([90.0, 91.0, 89.5, 90.5] * 5)}
  assert loop_spans.stall_ms(quiet) == 0.0
  # one 2.99 s period among 90 ms ones: what it lost over the median
  stalled = {"spans": steps([90.0] * 10 + [2990.0] + [90.0] * 10)}
  assert loop_spans.stall_ms(stalled) == pytest.approx(2900.0)
  # every step slower alike is no stall
  assert loop_spans.stall_ms({"spans": steps([140.0] * 20)}) == 0.0
  # just under and just over the factor
  assert loop_spans.stall_of([100.0] * 9 + [149.0]) == 0.0
  assert loop_spans.stall_of([100.0] * 9 + [151.0]) == pytest.approx(51.0)


def test_dispatch_is_the_median_and_lies_under_the_step():
  ctx = {"spans": steps([90.0] * 7, dispatch_ms=2.5)}
  assert loop_spans.dispatch_ms(ctx) == pytest.approx(2.5)
  assert loop_spans.dispatch_ms(ctx) < spans.engine_step_ms(ctx)


def test_unspanned_time_per_period():
  # period 90: plan 1 + device_step 82 + commit 1 are spanned, 6 are not;
  # an enqueue span of 2 ms inside the bare part takes it down to 4
  sp = steps([90.0] * 4)
  assert loop_spans.unspanned_ms(sp) == pytest.approx([6.0] * 4)
  sp = steps([90.0] * 4, extra=[("serving/enqueue", 85 * MS, 87 * MS)])
  assert loop_spans.unspanned_ms(sp) == pytest.approx([4.0, 6.0, 6.0, 6.0])


@pytest.mark.parametrize("metric", [
    "engine.dispatch_ms.chat", "engine.dispatch_ms.backlog",
    "engine.stall_ms.chat", "engine.stall_ms.backlog"])
def test_a_program_without_the_span_reads_none(metric):
  parent = [s for s in steps([90.0] * 5)
            if s[0] not in ("serving/dispatch", "serving/fetch")]
  assert read(metric, {"spans": parent}) is None
  assert read(metric, {"spans": []}) is None
  assert read(metric, {"kind": "train"}) is None
  assert read(metric, {"spans": steps([90.0] * 5)}) is not None


def train_ctx(custom_calls, window_s=1.0, step_ms=500.0):
  return {"trace": {"custom_calls": custom_calls, "window_s": window_s},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
          "step_done_gaps_ms": [step_ms] * 5,
          "attention": {"batch_per_chip": 8, "heads": 20, "seq": 1024,
                        "head_dim": 64, "layers": 36}}


def test_named_kernels_give_the_two_shares():
  # two steps in the window; 36 calls a step of each kernel
  ctx = train_ctx({"flash_fwd": (72.0, 0.040), "flash_dkv": (72.0, 0.070),
                   "flash_dq": (72.0, 0.050), "shard_map": (9.0, 0.5)})
  f, _ = flops.flash_fwd_cost(8, 20, 1024, 64)
  b, _ = flops.flash_bwd_cost(8, 20, 1024, 64)
  fwd, bwd = read("flash_fwd_roofline", ctx), read("flash_bwd_roofline", ctx)
  assert fwd == pytest.approx(100 * 36 * f / 197e12 / 0.020)
  assert bwd == pytest.approx(100 * 36 * b / 197e12 / 0.060)
  assert 0 < bwd < fwd < 100
  # the work is 5 : 2, so equal time would give 2.5 x the share
  assert b == 2.5 * f


@pytest.mark.parametrize("metric,gone", [
    ("flash_fwd_roofline", "flash_fwd"), ("flash_bwd_roofline", "flash_dkv"),
    ("flash_bwd_roofline", "flash_dq")])
def test_a_missing_kernel_name_reads_none(metric, gone):
  calls = {"flash_fwd": (36.0, 0.02), "flash_dkv": (36.0, 0.03),
           "flash_dq": (36.0, 0.03)}
  del calls[gone]
  assert read(metric, train_ctx(calls)) is None
  assert read(metric, {"kind": "open_loop", "spans": []}) is None


def test_the_trace_recorded_before_the_names_reads_none():
  with open(os.path.join(DATA, "trace_planes_1chip.json")) as f:
    block = xplane.reduce(json.load(f))
  # ``attn`` is the only kernel's name there (``custom-call`` is XLA's own)
  assert set(block["custom_calls"]) == {"attn", "custom-call"}
  ctx = train_ctx(block["custom_calls"], block["window_s"], 400.0)
  assert read("flash_fwd_roofline", ctx) is None
  assert read("flash_bwd_roofline", ctx) is None
  assert read("flash_attn_roofline", ctx) is not None     # the old one stays


NEW = [("engine.dispatch_ms.chat", "ms", "program_span", "itl_p95_ms"),
       ("engine.dispatch_ms.backlog", "ms", "program_span",
        "serve_tokens_per_s"),
       ("engine.stall_ms.chat", "ms", "program_span", "ttft_p95_ms"),
       ("engine.stall_ms.backlog", "ms", "program_span",
        "serve_tokens_per_s"),
       ("flash_fwd_roofline", "%", "device_trace", "train_tokens_per_s"),
       ("flash_bwd_roofline", "%", "device_trace", "train_tokens_per_s")]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  """The toy checkout with the six entries appended to its manifest."""
  co = toy_checkout.make(str(tmp_path_factory.mktemp("co")))
  path = os.path.join(co, "BENCHMARK.json")
  with open(path) as f:
    doc = json.load(f)
  for name, unit, source, moves in NEW:
    doc["per_layer"].append({
        "name": name, "unit": unit, "source": source, "moves": moves,
        "better": "higher" if unit == "%" else "lower",
        "layer": "kernels" if unit == "%" else "engine fused step"})
  with open(path, "w") as f:
    json.dump(doc, f)
  return co


@pytest.mark.parametrize("cell,tag", [("toy-chat", "chat"),
                                      ("toy-backlog", "backlog")])
def test_traced_serving_run_prints_dispatch_and_stall(checkout, cell, tag):
  r = toy_checkout.run_cell(
      checkout, "--workload", cell, "--seed", str(2 ** 31 + 24),
      "--seconds", "2", "--trace", "1", prelude=toy_checkout.FAKE_TRACE
      % os.path.join(DATA, "trace_planes_1chip.json"))
  assert r.returncode == 0, r.stderr[-2000:]
  m = toy_checkout.last_line(r)["metrics"]
  assert 0 < m[f"engine.dispatch_ms.{tag}"]["value"] \
      < m[f"engine.step_ms.{tag}"]["value"]
  assert m[f"engine.stall_ms.{tag}"]["value"] >= 0
  assert "in no serving span: median" in r.stdout


def test_traced_train_run_leaves_out_what_its_trace_cannot_show(checkout):
  """The toy train run is fed the trace recorded before the kernels had
  names: the two new shares are left out, nothing raises."""
  r = toy_checkout.run_cell(
      checkout, "--workload", "toy-train", "--seed", "7", "--seconds", "2",
      "--trace", "1", prelude=toy_checkout.FAKE_TRACE
      % os.path.join(DATA, "trace_planes_1chip.json"))
  assert r.returncode == 0, r.stderr[-2000:]
  m = toy_checkout.last_line(r)["metrics"]
  assert "train.step_ms" in m
  assert "flash_fwd_roofline" not in m and "flash_bwd_roofline" not in m
