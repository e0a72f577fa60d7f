"""The one traffic generator: a mix is a data file of parameters.

Every seed gets the SAME set of sizes and the SAME set of inter-arrival
gaps, in another order: sizes and gaps are the distribution's quantiles
at evenly spaced probabilities (no draw at all), and the seed only
permutes them and fills in the token ids.  So two seeds offer the same
work and differ the way two days of one deployment do.

Mix kinds (``kind`` in the file):

``train_batches``  ``global_batch`` rows of ``seq_len`` + 1 token ids a
                   step, ids from ``token_law``
``open_loop``      requests due on a schedule (``arrivals``: a gamma
                   renewal process of ``rate_per_s`` and coefficient of
                   variation ``cv``; 1 is Poisson), ``prompt_len`` and
                   ``output_len`` distributions, ``ramp_s`` of load
                   before the window and ``drain_margin_s`` at its end
``backlog``        a queue that never empties: ``population`` requests
                   of the two length distributions, taken in order and
                   kept ``queue_target`` deep; over the ``ramp_s`` before
                   the window ``ramp_fill`` of them are let in evenly, so
                   the slots start out of phase
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


# ---------------------------------------------------------- distributions


def _norm_ppf(p):
  """Inverse of the standard normal distribution (Acklam's rational
  approximation, relative error below 1.2e-9)."""
  a = (-3.969683028665376e+01, 2.209460984245205e+02,
       -2.759285104469687e+02, 1.383577518672690e+02,
       -3.066479806614716e+01, 2.506628277459239e+00)
  b = (-5.447609879822406e+01, 1.615858368580409e+02,
       -1.556989798598866e+02, 6.680131188771972e+01,
       -1.328068155288572e+01)
  c = (-7.784894002430293e-03, -3.223964580411365e-01,
       -2.400758277161838e+00, -2.549732539343734e+00,
       4.374664141464968e+00, 2.938163982698783e+00)
  d = (7.784695709041462e-03, 3.224671290700398e-01,
       2.445134137142996e+00, 3.754408661907416e+00)
  p = np.asarray(p, float)
  out = np.empty_like(p)
  lo, hi = p < 0.02425, p > 1 - 0.02425
  mid = ~(lo | hi)
  q = np.sqrt(-2 * np.log(p[lo]))
  out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
              + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                         + 1))
  q = np.sqrt(-2 * np.log(1 - p[hi]))
  out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                          + 1))
  q = p[mid] - 0.5
  r = q * q
  out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
               + a[5]) * q
              / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r
                 + 1))
  return out


def length_quantiles(spec: dict, n: int) -> np.ndarray:
  """``n`` whole lengths: the quantiles of ``spec`` at (i + 0.5) / n,
  clipped to ``[min, max]``, ascending."""
  p = (np.arange(n) + 0.5) / n
  dist = spec["dist"]
  if dist == "lognormal":
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(p))
  elif dist == "uniform":
    x = spec["min"] + (spec["max"] - spec["min"]) * p
  elif dist == "fixed":
    x = np.full(n, spec["value"], float)
  else:
    raise ValueError(f"length distribution {dist!r}")
  lo = spec.get("min", 1)
  hi = spec.get("max", np.inf)
  return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gap_quantiles(rate_per_s: float, cv: float, n: int) -> np.ndarray:
  """``n`` inter-arrival gaps of a gamma renewal process with mean
  1 / rate and coefficient of variation ``cv``: quantiles at
  (i + 0.5) / n, rescaled so their mean is exactly 1 / rate."""
  p = (np.arange(n) + 0.5) / n
  if abs(cv - 1.0) < 1e-12:
    x = -np.log1p(-p)
  else:
    # Gamma quantiles by a fixed, seed-free table: a large sorted sample
    # from a generator seeded with a constant, read at the quantiles.
    shape = 1.0 / (cv * cv)
    table = np.sort(np.random.default_rng(20260927).gamma(
        shape, 1.0 / shape, size=max(200000, 50 * n)))
    x = table[np.minimum((p * table.size).astype(int), table.size - 1)]
  return x * (1.0 / rate_per_s) / x.mean()


def token_cdf(law: dict, vocab: int):
  """Cumulative distribution over token ids ``[0, vocab)``; None for
  uniform."""
  if law["dist"] == "uniform":
    return None
  if law["dist"] == "zipf":
    w = 1.0 / np.power(np.arange(1, vocab + 1, dtype=np.float64),
                       law["exponent"])
    return np.cumsum(w / w.sum())
  raise ValueError(f"token law {law['dist']!r}")


def draw_tokens(rng: np.random.Generator, cdf, vocab: int, shape):
  if cdf is None:
    return rng.integers(0, vocab, size=shape, dtype=np.int32)
  ids = np.searchsorted(cdf, rng.random(shape), side="right")
  return np.minimum(ids, vocab - 1).astype(np.int32)


# ----------------------------------------------------------------- mixes


@dataclasses.dataclass
class Req:
  uid: int
  due_s: float            # from the start of the load (ramp included)
  prompt: np.ndarray
  max_new_tokens: int


def _requests(mix: dict, n: int, rng, vocab: int, due, uid0: int = 0):
  prompts = rng.permutation(length_quantiles(mix["prompt_len"], n))
  outputs = rng.permutation(length_quantiles(mix["output_len"], n))
  cap = mix.get("max_total_len")
  cdf = token_cdf(mix.get("token_law", {"dist": "uniform"}), vocab)
  reqs = []
  for i in range(n):
    p, o = int(prompts[i]), int(outputs[i])
    if cap is not None and p + o > cap:
      o = max(1, cap - p)
    reqs.append(Req(uid=uid0 + i, due_s=float(due[i]),
                    prompt=draw_tokens(rng, cdf, vocab, (p,)),
                    max_new_tokens=o))
  return reqs


def _phase(mix: dict, span_s: float, t0: float, rng, vocab: int, uid0: int):
  """One phase of an open loop: ``rate x span`` requests whose sizes and
  gaps are the full set of quantiles, due within ``[t0, t0 + span)``."""
  arr = mix["arrivals"]
  n = int(round(arr["rate_per_s"] * span_s))
  if n == 0:
    return []
  gaps = rng.permutation(gap_quantiles(arr["rate_per_s"], arr["cv"], n))
  due = np.cumsum(gaps) - gaps
  # The gaps add up to n / rate = the span: shift by a seeded fraction of
  # the last one so the phase neither starts nor ends on an arrival.
  due = t0 + due + rng.random() * gaps[-1]
  return _requests(mix, n, rng, vocab, due, uid0)


def open_loop(mix: dict, seconds: float, seed: int, vocab: int):
  """Requests due over ``ramp_s + seconds``; ``due_s`` counts from the
  start of the ramp.  The ramp and the window are drawn apart, so the
  window's requests are the same set of sizes for every seed."""
  rng = np.random.default_rng([int(seed), 1])
  ramp = _phase(mix, mix["ramp_s"], 0.0, rng, vocab, 0)
  window = _phase(mix, seconds, mix["ramp_s"], rng, vocab, len(ramp))
  return ramp + window


def backlog(mix: dict, seed: int, vocab: int):
  """``population`` requests, all due at once; the runner feeds them
  in order and never lets the queue fall below ``queue_target``."""
  n = mix["population"]
  rng = np.random.default_rng([int(seed), 1])
  return _requests(mix, n, rng, vocab, np.zeros(n))


class TrainBatches:
  """Fresh ``[global_batch, seq_len + 1]`` batches, one a call, every
  row different; the seed fixes the whole sequence."""

  def __init__(self, mix: dict, seed: int, vocab: int):
    self.shape = (mix["global_batch"], mix["seq_len"] + 1)
    self.vocab = vocab
    self.cdf = token_cdf(mix["token_law"], vocab)
    self.rng = np.random.default_rng([int(seed), 3])

  def __call__(self) -> np.ndarray:
    return draw_tokens(self.rng, self.cdf, self.vocab, self.shape)
