"""Latency-hiding collective-matmul — chunked ring decomposition.

The framework owns the communication schedule (PAPER §1 layers 2/6), yet
the fused collectives XLA emits for tensor/sequence-parallel dense layers
serialize against the adjacent matmul: an ``all_gather`` finishes before
the first MXU cycle of the matmul that consumes it, and a
``psum_scatter`` starts only after the last partial product.  This module
decomposes both adjacencies the way Wang et al. (ASPLOS'23, "Overlap
Communication with Dependent Computation via Decomposition") do: the
collective becomes a ring of ``lax.ppermute`` steps interleaved with
partial matmuls, double-buffered so every permute travels while a chunk
of the matmul runs.

Two primitives (named-axis, for use inside ``shard_map`` regions):

  * :func:`all_gather_matmul` — ``matmul(all_gather(x), w)``: the ring
    rotates the local shard; each arriving shard feeds a row-block
    matmul while the next shard is in flight.  Row blocks are computed
    by the same dot as the fused product, so the result is BIT-exact.
  * :func:`matmul_reduce_scatter` — ``psum_scatter(matmul(x, w))``: the
    accumulator rides the ring; each step adds this device's
    contribution to the block about to be forwarded, while the next
    window's partial matmul runs.  Summation order differs from the
    fused ``psum_scatter`` (per-device ring adds vs XLA's reduction
    tree), so agreement is at accumulation-order tolerance — within the
    test suite's fused-vs-sequential tolerances, not bitwise.
  * :func:`reduce_scatter` — the matmul-free ring (ZeRO-1 gradient
    reduction: the "compute" being hidden is the neighbouring buckets'
    adds and the backward epilogue around the reduction).

``num_chunks`` (K) is the decomposition granularity: K partial matmuls
interleaved with the ring's n-1 permutes (K must divide the axis size n;
K = n is the fully-interleaved ring, K = 1 is the fused program).  The
crossover — below which chunking LOSES (per-step latency dominates the
hidden bytes) — is modeled in ``parallel.planner.plan_collective_matmul``
and drives the ``communication.overlap = auto`` policy; ``on``/``off``
force it.  ``off`` emits exactly today's fused ops — callers route
through :func:`resolve_num_chunks` so the knob is honored everywhere.

Reference analog: none — EPL schedules NCCL collectives on side streams
(csrc/communicators/tensorflow_cuda.h:50-136) but never splits a
collective against its producer/consumer matmul.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ring_step(x, axis_name: str, n: Optional[int] = None):
  """One ring hop: device d's value moves to d+1 (so after t hops the
  buffer on device d is device (d - t) mod n's original value).  The
  shared step primitive for every ring in the framework — the chunked
  collective-matmuls here and the seq-manual ring-attention rotation
  (sequence/ring_attention.py) walk the same ring."""
  if n is None:
    n = lax.axis_size(axis_name)
  return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


_ring_once = ring_step


def normalize_chunks(num_chunks: int, axis_n: int) -> int:
  """Clamp a requested chunk count to a ring-valid one: 0/1 → fused;
  otherwise the largest divisor of ``axis_n`` that is <= the request
  (a non-divisor request rounds DOWN so a chunk never spans a fractional
  shard)."""
  if num_chunks <= 1 or axis_n <= 1:
    return 1
  k = min(num_chunks, axis_n)
  while axis_n % k:
    k -= 1
  return k


def all_gather_matmul(x, w, axis_name: str, num_chunks: int = 0):
  """``matmul(all_gather(x, axis=0, tiled=True), w)`` with the gather
  decomposed into a compute-overlapped ppermute ring.

  ``x``: this device's ``[m, k]`` shard of a row-sharded ``[n*m, k]``
  global operand; ``w``: ``[k, N]`` (replicated over ``axis_name`` —
  other mesh axes may shard it outside this function's view).  Returns
  ``[n*m, N]``.

  K = ``num_chunks`` partial matmuls ride the n-1 permutes; each window
  of ``n/K`` shards is matmul'd while the following window travels the
  ring.  Row blocks are produced by the same dot as the fused product —
  the result is bit-exact vs ``matmul(all_gather(x), w)``.
  """
  if x.ndim != 2 or w.ndim != 2:
    raise ValueError(f"all_gather_matmul wants rank-2 operands; got "
                     f"{x.shape} @ {w.shape}")
  n = lax.axis_size(axis_name)
  K = normalize_chunks(num_chunks, n)
  if K <= 1:
    return jnp.matmul(lax.all_gather(x, axis_name, axis=0, tiled=True), w)
  c = n // K
  m, k = x.shape
  N = w.shape[1]
  d = lax.axis_index(axis_name)

  def collect(buf, count):
    """Append `count` consecutive ring shards starting from `buf`,
    permuting between appends (count-1 hops); returns ([count, m, k],
    final buf)."""
    shards = [buf]
    for _ in range(count - 1):
      buf = _ring_once(buf, axis_name, n)
      shards.append(buf)
    return jnp.stack(shards), buf

  def window_matmul(y, window, g):
    # One dot over the whole window: identical row-block arithmetic to
    # the fused [n*m, k] @ [k, N] product.
    part = jnp.matmul(window.reshape(c * m, k), w).reshape(c, m, N)
    for j in range(c):
      idx = jnp.mod(d - (g * c + j), n)
      y = lax.dynamic_update_index_in_dim(y, part[j], idx, 0)
    return y

  window, buf = collect(x, c)
  y0 = jnp.zeros((n, m, N), part_dtype(x, w))

  def body(g, carry):
    y, window_g, buf_g = carry
    # The window's matmul and the next window's permutes share no data
    # dependency — the double buffer XLA's latency-hiding scheduler
    # overlaps.
    y = window_matmul(y, window_g, g)
    buf_g = _ring_once(buf_g, axis_name, n)
    window_next, buf_g = collect(buf_g, c)
    return y, window_next, buf_g

  y, window, _ = lax.fori_loop(0, K - 1, body, (y0, window, buf))
  y = window_matmul(y, window, K - 1)
  return y.reshape(n * m, N)


def part_dtype(x, w):
  """Result dtype of the partial matmuls — jnp.matmul's promotion, so
  chunked and fused paths agree."""
  return jnp.result_type(x.dtype, w.dtype)


def matmul_reduce_scatter(x, w, axis_name: str, num_chunks: int = 0):
  """``psum_scatter(matmul(x, w), scatter_dimension=0, tiled=True)``
  with the scatter decomposed into a compute-overlapped ppermute ring.

  ``x``: ``[M, k_loc]`` (the contraction dim sharded over ``axis_name``
  by dataflow); ``w``: ``[k_loc, N]``.  Returns this device's ``[M/n,
  N]`` block of the reduced product.  At ring step t device d adds its
  contribution for block ``(d - 1 - t) mod n`` to the accumulator it
  just received and forwards it; after n-1 hops block d's full sum lands
  home.  The next window's partial matmul is issued before the current
  window's permute+add chain, so the ring hides it.

  Cross-device summation order differs from the fused ``psum_scatter``
  — exact to accumulation-order tolerance.
  """
  if x.ndim != 2 or w.ndim != 2:
    raise ValueError(f"matmul_reduce_scatter wants rank-2 operands; got "
                     f"{x.shape} @ {w.shape}")
  n = lax.axis_size(axis_name)
  K = normalize_chunks(num_chunks, n)
  if K <= 1:
    return lax.psum_scatter(jnp.matmul(x, w), axis_name,
                            scatter_dimension=0, tiled=True)
  M = x.shape[0]
  if M % n:
    raise ValueError(f"matmul_reduce_scatter needs rows ({M}) divisible "
                     f"by the axis size ({n})")
  c = n // K
  mb = M // n
  d = lax.axis_index(axis_name)

  def window_matmul(g):
    """[c, mb, N] contributions for micro-steps g*c .. g*c+c-1 (block
    (d - 1 - t) mod n at micro-step t)."""
    rows = []
    for j in range(c):
      b = jnp.mod(d - 1 - (g * c + j), n)
      rows.append(lax.dynamic_slice_in_dim(x, b * mb, mb, axis=0))
    xs = jnp.concatenate(rows, axis=0)              # [c*mb, k_loc]
    return jnp.matmul(xs, w).reshape(c, mb, -1)

  part = window_matmul(0)
  acc = part[0]

  def body(g, carry):
    acc_g, part_cur = carry
    # Window g+1's matmul first: it shares no data with the permute+add
    # chain below (the double buffer), so the ring hops hide it; its
    # first row is consumed only at the end of this body.
    part_next = window_matmul(g + 1)
    for j in range(1, c):
      acc_g = _ring_once(acc_g, axis_name, n) + part_cur[j]
    acc_g = _ring_once(acc_g, axis_name, n) + part_next[0]
    return acc_g, part_next

  acc, part = lax.fori_loop(0, K - 1, body, (acc, part))
  for j in range(1, c):
    acc = _ring_once(acc, axis_name, n) + part[j]
  return acc


def reduce_scatter(x, axis_name: str, axis: int = 0, num_chunks: int = 0):
  """Ring-decomposed ``psum_scatter(x, scatter_dimension=axis,
  tiled=True)`` — the matmul-free plan :func:`matmul_reduce_scatter`
  reduces to when the producer is already materialized (ZeRO-1 gradient
  buckets: successive buckets' rings pipeline against each other's adds).

  ``num_chunks`` is a fused-vs-ring SWITCH here, not a granularity knob:
  every contribution is pre-materialized, so any value >= 2 runs the
  identical full n-step ring (there is no partial compute to coarsen);
  <= 1 emits the fused ``psum_scatter``.  Chunk-count policy still flows
  through so call sites read uniformly, but only its sign matters.
  """
  n = lax.axis_size(axis_name)
  K = normalize_chunks(num_chunks, n)
  if K <= 1:
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                            tiled=True)
  if x.shape[axis] % n:
    raise ValueError(f"reduce_scatter dim {axis} ({x.shape[axis]}) must "
                     f"divide the axis size ({n})")
  xm = jnp.moveaxis(x, axis, 0)
  mb = xm.shape[0] // n
  d = lax.axis_index(axis_name)

  def block(t):
    b = jnp.mod(d - 1 - t, n)
    return lax.dynamic_slice_in_dim(xm, b * mb, mb, axis=0)

  acc = block(0)
  # All contributions are already materialized, so the ring is a pure
  # permute+add chain; fori keeps the program size O(1) in n.
  def body(t, acc_t):
    return _ring_once(acc_t, axis_name, n) + block(t)

  acc = lax.fori_loop(1, n, body, acc)
  return jnp.moveaxis(acc, 0, axis)


# ------------------------------------------------------------------ policy

def resolve_num_chunks(kind: str, axis_n: int, *,
                       m: int, k: int, n_out: int,
                       dtype=jnp.bfloat16,
                       config=None,
                       measured_collective_bytes=None,
                       site: Optional[str] = None) -> int:
  """Chunk count the ``communication.overlap`` policy picks for one
  collective-matmul site: 0/1 = fused, >= 2 = ring with that many
  chunks.

  ``kind``: "all_gather_matmul" | "matmul_reduce_scatter" |
  "reduce_scatter"; ``m/k/n_out`` are the LOCAL operand dims (for
  "reduce_scatter", ``m`` x ``k`` is the buffer and ``n_out`` is
  ignored).  ``auto`` defers to the planner's analytic crossover
  (:func:`parallel.planner.plan_collective_matmul`, fed by the same
  flops/bytes quantities as the XLA cost-model path).
  ``measured_collective_bytes`` feeds a profiler-measured wire-traffic
  figure for this site into the crossover instead of the analytic
  derivation (ROADMAP item 5c; the analytic model stays the fallback).

  ``site`` is the call site's canonical name
  (``parallel.planner.OVERLAP_SITES``): when given and no explicit
  measurement was passed, the device introspector's per-site
  measurement store is consulted automatically — a warmup capture that
  attributed this site's fused collective flips the crossover onto
  evidence with zero caller plumbing (observability/device.py; when
  device observability is off the lookup is a constant-time None and
  the decision is bit-identical to the analytic one).  The site is
  also REGISTERED with its analytic signature here, which is how the
  introspector knows what to attribute in the first place.
  """
  if axis_n <= 1:
    return 1
  if config is None:
    from easyparallellibrary_tpu.env import Env
    config = Env.get().config
  comm = config.communication
  policy = comm.overlap
  if policy == "off":
    return 1
  requested = comm.overlap_chunks
  if policy == "on":
    return normalize_chunks(requested if requested > 1 else axis_n, axis_n)
  # auto
  if site is not None:
    from easyparallellibrary_tpu.observability import device as device_lib
    device_lib.register_site(
        site, kind=kind, axis_n=axis_n, m=m, k=k, n_out=n_out,
        dtype_bytes=jnp.dtype(dtype).itemsize)
    if measured_collective_bytes is None:
      measured_collective_bytes = device_lib.measured_collective_bytes(
          site)
  from easyparallellibrary_tpu.parallel.planner import plan_collective_matmul
  decision = plan_collective_matmul(
      kind, m=m, k=k, n_out=n_out, axis_size=axis_n,
      dtype_bytes=jnp.dtype(dtype).itemsize,
      num_chunks=requested,
      measured_collective_bytes=measured_collective_bytes)
  return decision.num_chunks if decision.enabled else 1
