"""Shared helpers for the attention benchmarks.

The timing recipe: warmup and timing force completion by fetching a
scalar that depends on the result, and subtract a measured null
round-trip.  On the v5e ``jax.block_until_ready`` waits for the device
just as well (chip_smoke.py prints both clocks side by side; bench.py
times with it), so the fetch here is a choice, not a workaround.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def force(x):
  """Force execution of `x` and everything it depends on."""
  return float(jax.device_get(jnp.sum(x) if hasattr(x, "shape") else x))


def null_round_trip():
  tiny = jax.jit(lambda v: v + 1)
  force(tiny(jnp.float32(0)))
  t0 = time.perf_counter()
  force(tiny(jnp.float32(1)))
  return time.perf_counter() - t0


def xla_attention(q, k, v):
  """The models' actual XLA attention path — imported, not copied, so
  the benchmark baseline can never drift from what the model computes."""
  from easyparallellibrary_tpu.models.gpt import _dense_causal_attention
  return _dense_causal_attention(q, k, v, q.dtype)


def time_attn_grad(attn, q, k, v, steps=20):
  """Milliseconds per fused fwd+bwd step of `attn`, chained through q so
  the whole sequence must execute."""
  g = jax.jit(jax.grad(lambda *a: jnp.sum(attn(*a) ** 2)))
  out = g(q, k, v)
  force(out[0, 0, 0])
  null = null_round_trip()
  t0 = time.perf_counter()
  acc = q
  for _ in range(steps):
    acc = g(acc, k, v)
  force(acc[0, 0, 0])
  return max(time.perf_counter() - t0 - null, 1e-9) / steps * 1000
