"""Named-axis collective wrappers — the framework's communication substrate.

TPU-native replacement for the reference's NCCL stack
(csrc/communicators/*.cc + epl/communicators/): every collective becomes an
XLA collective over a named mesh axis, running on ICI/DCN.  The concerns the
reference implements by hand disappear or move:

  * dedicated CUDA streams + event sync (csrc/.../tensorflow_cuda.h:50-136)
      → XLA's async collective scheduling / latency-hiding scheduler
  * gradients of collectives (epl/communicators/nccl_ops.py:37-124)
      → JAX differentiates `lax.psum`/`all_gather`/... natively
  * NCCL unique-id bootstrap over TF grpc (epl/communicators/base.py:44-73)
      → `jax.distributed.initialize` (done once by the launcher)

These wrappers are used *inside* `jax.shard_map` regions (pipeline,
ring attention, MoE dispatch) and by the explicit fusion path; GSPMD
inserts the equivalents automatically for sharded `jit` code.

Reduce-op vocabulary mirrors the reference (SUM/PROD/MAX/MIN,
epl/communicators/base.py:34-40).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Reduce ops (reference: epl/communicators/base.py:34-40).
SUM = "sum"
PROD = "prod"
MAX = "max"
MIN = "min"
MEAN = "mean"

_REDUCERS = {
    SUM: lax.psum,
    MAX: lax.pmax,
    MIN: lax.pmin,
    MEAN: lax.pmean,
}


def axis_index(axis_name: str):
  return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
  return lax.axis_size(axis_name)


def all_reduce(x, axis_name: str, op: str = SUM):
  """All-reduce over a mesh axis (reference AllReduce kernel:
  csrc/communicators/nccl_all_reduce.cc)."""
  if op == PROD:
    # XLA has no pprod primitive; log-sum-exp tricks are unsafe — use
    # all_gather + product for the rare PROD case.
    gathered = lax.all_gather(x, axis_name)
    return jnp.prod(gathered, axis=0)
  try:
    reducer = _REDUCERS[op]
  except KeyError:
    raise ValueError(f"Unknown reduce op {op!r}; one of {sorted(_REDUCERS)}")
  return reducer(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
  """Concatenate shards along `axis` (reference AllGather kernel:
  csrc/communicators/nccl_all_gather.cc:20-98)."""
  return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0, op: str = SUM):
  """Reduce then scatter shards along `axis` (reference ReduceScatter
  kernel: csrc/communicators/nccl_reduce_scatter.cc:20-62)."""
  if op not in (SUM, MEAN):
    raise ValueError("reduce_scatter supports sum/mean")
  out = lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)
  if op == MEAN:
    out = out / axis_size(axis_name)
  return out


def reduce(x, axis_name: str, root: int = 0, op: str = SUM):
  """Reduce-to-root (reference Reduce kernel:
  csrc/communicators/nccl_reduce.cc:20-48).  Non-roots get zeros.

  COST: a full all-reduce.  XLA's SPMD collective vocabulary has no
  rooted reduce — every program runs the same collective, so NCCL's
  cheaper one-receiver reduce is not expressible (rooted trees are a
  host-topology concept; ICI collectives are ring/torus-wide).  If you
  only need the value on one host afterwards, that is free — the result
  is replicated.  Do not benchmark this as a NCCL-style reduce."""
  summed = all_reduce(x, axis_name, op=op)
  idx = lax.axis_index(axis_name)
  return jnp.where(idx == root, summed, jnp.zeros_like(summed))


def broadcast(x, axis_name: str, root: int = 0):
  """Broadcast from `root` (reference Broadcast kernel:
  csrc/communicators/nccl_broadcast.cc:20-46).

  Implemented as mask+psum: every rank contributes zeros except the root.

  COST: a full all-reduce (~2x the bytes of NCCL's rooted broadcast).
  Same SPMD constraint as :func:`reduce` — there is no one-to-all
  primitive; a log-depth ppermute ladder would move MORE bytes because
  every rank's buffer travels in each SPMD permute step.  Prefer keeping
  values replicated (free under GSPMD) over broadcasting at runtime."""
  idx = lax.axis_index(axis_name)
  masked = jnp.where(idx == root, x, jnp.zeros_like(x))
  return lax.psum(masked, axis_name)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int,
               tiled: bool = True):
  """All-to-all (reference AllToAll kernels:
  csrc/communicators/nccl_all_to_all.cc:22-77; grouped send/recv in
  tensorflow_nccl.h:186-206).  Substrate for MoE dispatch/combine and
  Ulysses sequence parallelism."""
  return lax.all_to_all(x, axis_name, split_axis=split_axis,
                        concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis_name: str, perm: Sequence[Tuple[int, int]]):
  """Point-to-point permutation over the axis — the TPU-native
  send/recv (no reference analog; NCCL send/recv pairs are the closest,
  tensorflow_nccl.h:186-206).  Used by the pipeline runner and ring
  attention."""
  return lax.ppermute(x, axis_name, perm=list(perm))


def ring_shift(x, axis_name: str, shift: int = 1):
  """Rotate values around the axis ring by `shift` positions
  (rank i -> rank (i+shift) % n)."""
  n = axis_size(axis_name)
  perm = [(i, (i + shift) % n) for i in range(n)]
  return lax.ppermute(x, axis_name, perm=perm)
