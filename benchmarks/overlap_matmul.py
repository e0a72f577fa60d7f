"""Fused vs latency-hiding collective-matmul — chunk sweep.

Measures the two decomposed adjacencies of ``communicators/overlap.py``
on the active backend (the 8-device virtual CPU mesh by default, a real
TPU slice when one is attached):

  * ``all_gather -> matmul``  (tensor/sequence-parallel dense entry)
  * ``matmul -> reduce_scatter`` (row-parallel dense exit / ZeRO-1 grads)

for ring chunk counts K in {1, 2, 4, 8} (K=1 IS the fused program), and
records the sweep — times plus the planner's analytic crossover verdict
for the same shapes — into the BENCH evidence machinery
(``BENCH_EVIDENCE.json`` via the validated ``_evidence`` writer), printing the
record as one JSON line.

CPU-mesh numbers attest program structure (the ring lowers, stays exact,
and the sweep machinery works); they are NOT a statement about ICI
overlap — XLA's latency-hiding scheduler only pays off on a real
interconnect, which is what the recorded planner verdict models.

Run: ``python benchmarks/overlap_matmul.py`` (honors JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# Virtual 8-device mesh when no accelerator is attached (same recipe as
# tests/conftest.py); ignored by real TPU slices.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
  os.environ["XLA_FLAGS"] = (
      _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from benchmarks._common import force, null_round_trip  # noqa: E402
from easyparallellibrary_tpu.communicators import overlap  # noqa: E402
from easyparallellibrary_tpu.parallel.planner import (  # noqa: E402
    plan_collective_matmul)
import _evidence  # noqa: E402  (the validated shared writer)
from easyparallellibrary_tpu.utils.compat import shard_map  # noqa: E402

METRIC = "overlap_collective_matmul"
AXIS = "model"
SWEEP = (1, 2, 4, 8)


def _time_fn(f, x, w, steps: int = 20) -> float:
  """Milliseconds per execution, null round-trip subtracted.  Each call
  is CHAINED through the previous result (x + 0*out[0,0]) so the whole
  sequence must execute before the final fetch returns (see
  benchmarks/_common.py's chained-timing recipe)."""
  out = f(x, w)
  force(out)
  null = null_round_trip()
  t0 = time.perf_counter()
  for _ in range(steps):
    out = f(x + (out.ravel()[0] * 0).astype(x.dtype), w)
  force(out)
  return max(time.perf_counter() - t0 - null, 1e-9) / steps * 1000


def run(m_per_dev: int = 128, k: int = 512, n_out: int = 512,
        dtype=jnp.float32):
  n = len(jax.devices())
  mesh = Mesh(np.array(jax.devices()).reshape(n), (AXIS,))
  rng = np.random.RandomState(0)
  dtype_bytes = jnp.dtype(dtype).itemsize

  # all_gather -> matmul: x row-sharded [n*m, k], w replicated.
  x_ag = jnp.asarray(rng.randn(n * m_per_dev, k), dtype)
  w_ag = jnp.asarray(rng.randn(k, n_out), dtype)
  # matmul -> reduce_scatter: x contraction-sharded [M, n*k'], w sharded.
  x_rs = jnp.asarray(rng.randn(n * m_per_dev, n * k), dtype)
  w_rs = jnp.asarray(rng.randn(n * k, n_out), dtype)

  rows = {"all_gather_matmul": {}, "matmul_reduce_scatter": {}}
  for K in SWEEP:
    if K > n:
      continue
    f_ag = jax.jit(shard_map(
        lambda x, w, K=K: overlap.all_gather_matmul(x, w, AXIS, K),
        mesh, in_specs=(P(AXIS, None), P(None, None)),
        out_specs=P(None, None)))
    rows["all_gather_matmul"][K] = round(_time_fn(f_ag, x_ag, w_ag), 4)
    f_rs = jax.jit(shard_map(
        lambda x, w, K=K: overlap.matmul_reduce_scatter(x, w, AXIS, K),
        mesh, in_specs=(P(None, AXIS), P(AXIS, None)),
        out_specs=P(AXIS, None)))
    rows["matmul_reduce_scatter"][K] = round(_time_fn(f_rs, x_rs, w_rs), 4)

  # The planner's verdict for the same shapes (what `auto` would do on
  # the modeled interconnect — the CPU mesh has no ICI to overlap).
  plans = {
      "all_gather_matmul": plan_collective_matmul(
          "all_gather_matmul", m=m_per_dev, k=k, n_out=n_out, axis_size=n,
          dtype_bytes=dtype_bytes),
      "matmul_reduce_scatter": plan_collective_matmul(
          "matmul_reduce_scatter", m=n * m_per_dev, k=k, n_out=n_out,
          axis_size=n, dtype_bytes=dtype_bytes),
  }

  record = {
      "metric": METRIC,
      "value": min(rows["all_gather_matmul"].values()),
      "unit": "ms",
      "device": jax.devices()[0].device_kind,
      "config": {"axis_size": n, "m_per_device": m_per_dev, "k": k,
                 "n_out": n_out, "dtype": str(jnp.dtype(dtype)),
                 "chunk_sweep": list(SWEEP)},
      "raw": {
          # K=1 is the fused program; K>1 the ring decompositions.
          "fused_vs_overlapped_ms": {
              kind: {str(K): t for K, t in row.items()}
              for kind, row in rows.items()},
          "planner": {
              kind: {"enabled": p.enabled, "num_chunks": p.num_chunks,
                     "fused_us": round(p.fused_us, 3),
                     "overlapped_us": round(p.overlapped_us, 3),
                     "comm_us": round(p.comm_us, 3),
                     "matmul_us": round(p.matmul_us, 3)}
              for kind, p in plans.items()},
      },
  }
  _evidence.append_record(record)
  print(json.dumps(record), flush=True)
  return record


if __name__ == "__main__":
  run()
