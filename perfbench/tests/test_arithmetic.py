"""Percentiles, measured set, lateness, FLOPs and bytes, the generators."""

import math

import numpy as np
import pytest

from perfbench.harness import flops, spans, stats, traffic


def test_percentile_matches_numpy():
  xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
  for q in (0, 25, 50, 95, 100):
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
  assert stats.percentile([7.0], 95) == 7.0
  with pytest.raises(ValueError):
    stats.percentile([], 50)


def test_quartile_spread_is_the_drivers():
  xs = [100, 101, 102, 103, 104, 105]
  # statistics.quantiles (exclusive): q1 = 100.75, q3 = 104.25
  assert stats.quartile_spread(xs) == pytest.approx(3.5 / 102.5)


def test_measured_set_and_gaps():
  due = [-1.0, 0.0, 3.0, 7.9, 8.0, 9.5]
  assert stats.measured_set(due, window_s=10.0, drain_margin_s=2.0) == [1, 2, 3]
  with pytest.raises(ValueError):
    stats.measured_set(due, 2.0, 2.0)
  assert stats.gaps([1.0, 1.5, 2.5]) == [0.5, 1.0]


def test_lateness_is_submit_minus_due():
  # the reader's arithmetic: p95 of submit - due
  late = [1e3 * (s - d) for s, d in ((1.01, 1.0), (2.03, 2.0), (3.0, 3.0))]
  assert stats.percentile(late, 95) == pytest.approx(28.0, abs=1e-6)


def test_span_sums():
  sp = [("serving/plan", 0, 2e6), ("serving/commit", 5e6, 6e6),
        ("serving/plan", 10e6, 14e6), ("serving/commit", 20e6, 23e6)]
  assert spans.median_sum_ms(sp, ("serving/plan", "serving/commit")) == 5.0
  assert spans.median_ms(sp, "serving/plan") == 3.0
  assert spans.median_ms(sp, "nothing") is None


def test_gpt2_flops_hand_count():
  # 1 layer, d 4, d_ff 16, vocab 10, S 3: weights 4*16 + 2*64 + 40 = 232
  # -> 6 * 232 = 1392; attention fwd 2 * (2*4*2) = 32 a token, x3 = 96.
  assert flops.gpt2_train_flops_per_token(1, 4, 16, 10, 3) == 1392 + 96
  # GPT-2 medium at S 1024: the count PERF.md quotes.
  f = flops.gpt2_train_flops_per_token(24, 1024, 4096, 50304, 1024)
  assert f == pytest.approx(2.2722e9, rel=1e-4)


def test_flash_costs_hand_count():
  # B 1, H 1, S 2, D 4: pairs = 3; fwd 2 matmuls x 2*4*3 = 48 FLOPs
  f, b = flops.flash_fwd_cost(1, 1, 2, 4, dtype_bytes=2)
  assert f == 48 and b == 4 * 2 * 4 * 2 + 4 * 2
  f, b = flops.flash_bwd_cost(1, 1, 2, 4, dtype_bytes=2)
  assert f == 120 and b == 8 * 2 * 4 * 2 + 8 * 2
  pct, bound = flops.roofline_pct(100e12, 1e9, 1.0, 200e12, 800e9)
  assert pct == pytest.approx(50.0) and bound == "compute"
  pct, bound = flops.roofline_pct(1e9, 400e9, 1.0, 200e12, 800e9)
  assert pct == pytest.approx(50.0) and bound == "memory"


def test_length_quantiles_statistics():
  spec = {"dist": "lognormal", "median": 160, "sigma": 0.9, "min": 16,
          "max": 768}
  xs = traffic.length_quantiles(spec, 2000)
  assert xs.min() >= 16 and xs.max() <= 768
  assert np.median(xs) == pytest.approx(160, abs=1)
  # unclipped middle follows the law: the 84th percentile is e^sigma up
  assert np.percentile(xs, 84.13) == pytest.approx(160 * math.e ** 0.9, rel=0.02)
  u = traffic.length_quantiles({"dist": "uniform", "min": 256, "max": 768}, 512)
  assert u.min() >= 256 and u.max() <= 768 and u.mean() == pytest.approx(512, abs=1)


def test_gap_quantiles_statistics():
  g = traffic.gap_quantiles(20.0, 1.0, 1000)
  assert g.mean() == pytest.approx(0.05)
  assert g.std() / g.mean() == pytest.approx(1.0, abs=0.02)
  g2 = traffic.gap_quantiles(20.0, 2.0, 4000)
  assert g2.mean() == pytest.approx(0.05)
  assert g2.std() / g2.mean() == pytest.approx(2.0, abs=0.1)


MIX = {"ramp_s": 1.0, "arrivals": {"rate_per_s": 50.0, "cv": 1.0},
       "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                      "min": 4, "max": 60},
       "output_len": {"dist": "uniform", "min": 2, "max": 10},
       "max_total_len": 70, "token_law": {"dist": "uniform"}}


def test_open_loop_reproducible_and_same_work_for_every_seed():
  big = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits
  a = traffic.open_loop(MIX, 3.0, big, 250)
  b = traffic.open_loop(MIX, 3.0, big, 250)
  c = traffic.open_loop(MIX, 3.0, big + 1, 250)
  assert len(a) == len(b) == len(c) == 200
  for x, y in zip(a, b):
    assert x.due_s == y.due_s and np.array_equal(x.prompt, y.prompt)
    assert x.max_new_tokens == y.max_new_tokens
  # another seed: the window's requests are the same sizes and gaps in
  # another order (the ramp's likewise, drawn apart)
  win = lambda rs: [r for r in rs if r.due_s >= MIX["ramp_s"]]
  assert len(win(a)) == len(win(c)) == 150
  assert sorted(len(r.prompt) for r in win(a)) == sorted(
      len(r.prompt) for r in win(c))
  assert sorted(r.max_new_tokens for r in win(a)) == sorted(
      r.max_new_tokens for r in win(c))
  assert [len(r.prompt) for r in win(a)] != [len(r.prompt) for r in win(c)]
  import collections
  gaps = lambda rs: collections.Counter(
      np.diff([r.due_s for r in win(rs)]).round(9))
  assert sum((gaps(a) & gaps(c)).values()) >= 147   # all but the dropped one
  assert all(r.due_s < MIX["ramp_s"] + 3.0 for r in a)
  assert all(len(r.prompt) + r.max_new_tokens <= 70 for r in a)
  capped = traffic.open_loop(dict(MIX, max_total_len=30), 3.0, big, 250)
  assert all(len(r.prompt) + r.max_new_tokens <= max(30, len(r.prompt) + 1)
             for r in capped)
  assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


def test_train_batches_reproducible_zipf():
  mix = {"global_batch": 4, "seq_len": 16,
         "token_law": {"dist": "zipf", "exponent": 1.0}}
  a, b = traffic.TrainBatches(mix, 7, 100), traffic.TrainBatches(mix, 7, 100)
  x1, x2 = a(), a()
  assert x1.shape == (4, 17) and x1.dtype == np.int32
  assert np.array_equal(x1, b()) and not np.array_equal(x1, x2)
  assert len({tuple(r) for r in x1}) == 4          # every row differs
  big = traffic.draw_tokens(np.random.default_rng(0),
                            traffic.token_cdf(mix["token_law"], 100), 100,
                            (20000,))
  assert big.max() < 100 and (big == 0).mean() == pytest.approx(
      1 / sum(1 / k for k in range(1, 101)), rel=0.1)


def test_hostwatch_reads_ticks_and_sees_a_stalled_heartbeat():
  import time
  from perfbench.harness import hostwatch
  a = {"user": 100, "idle": 1000, "steal": 5}
  b = {"user": 150, "idle": 1900, "steal": 25}
  d = hostwatch.ticks_delta(a, b)
  assert d["steal"] / d["user"] == pytest.approx(0.4)
  watch = hostwatch.HostWatch(heartbeat_s=0.002, late_s=0.01).start()
  time.sleep(0.05)
  report = watch.stop()
  assert report["wall_s"] >= 0.05 and "steal" in report["machine_s"]
  assert "heartbeat late" in hostwatch.summary(report)
  assert all(over > 0.01 for _, over in report["heartbeat_late"])


def test_gap_summary_counts_what_slow_steps_lost():
  from perfbench.harness import hostwatch
  line = hostwatch.gap_summary([400.0] * 9 + [1200.0])
  assert "median 400.000 ms" in line and "max 1200.000" in line
  assert "1 over 1.01 x median lost 800.0 ms" in line
  assert hostwatch.gap_summary([]) == "no step gaps"
