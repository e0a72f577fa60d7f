"""Distributed prediction ops.

TPU-native analog of the reference's ``distributed_argmax`` /
``distributed_equal`` (epl/ops/distributed_ops.py:98,125): the reference
does a two-level argmax — local argmax per shard, allgather of (value,
index) pairs, then a global argmax with shard-offset correction (:58-95).
GSPMD compiles the same dataflow from a plain ``argmax`` over a
vocab-sharded logical array.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants


def distributed_argmax(logits, axis: int = -1):
  """Argmax over (possibly vocab-sharded) logits."""
  from easyparallellibrary_tpu.utils.sharding import constrain
  spec = [P.UNCONSTRAINED] * logits.ndim
  spec[axis if axis >= 0 else logits.ndim + axis] = constants.MODEL_AXIS
  logits = constrain(logits, P(*spec))
  return jnp.argmax(logits, axis=axis)


def distributed_equal(predictions, labels):
  """Elementwise equality between replicated labels and (possibly
  shard-derived) predictions (reference bridges labels to the split
  devices via Replica2Split, epl/ops/distributed_ops.py:125-148)."""
  return jnp.equal(predictions.astype(jnp.int32), labels.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Sequence/tensor-parallel boundary dense paths (latency-hiding).
#
# Named-axis collective-matmuls for callers ALREADY inside a manual region
# (the smap engines' seq-manual mode, explicit shard_map training steps):
# the boundary where token- or feature-sharded activations meet a dense
# layer is a gather->matmul or matmul->scatter adjacency, and these route
# it through the chunked ppermute ring of communicators/overlap.py under
# the ``communication.overlap`` policy (auto consults the planner's
# crossover; off emits the fused collective unchanged).
# ---------------------------------------------------------------------------

def gather_matmul(x, w, axis_name: str = constants.SEQ_AXIS,
                  num_chunks: int | None = None):
  """``matmul(all_gather(x, axis=0, tiled=True), w)`` at a parallel
  boundary — e.g. seq-sharded tokens ``[t_loc, D]`` entering a dense
  layer whose output must see every token.  Ring-overlapped per the
  overlap policy; bit-exact vs the fused gather+matmul."""
  from easyparallellibrary_tpu.communicators import overlap
  from easyparallellibrary_tpu.parallel.planner import SITE_GATHER_MATMUL
  n = jax.lax.axis_size(axis_name)
  if num_chunks is None:
    num_chunks = overlap.resolve_num_chunks(
        "all_gather_matmul", n, m=x.shape[0], k=x.shape[1],
        n_out=w.shape[1], dtype=x.dtype, site=SITE_GATHER_MATMUL)
  return overlap.all_gather_matmul(x, w, axis_name, num_chunks=num_chunks)


def matmul_scatter(x, w, axis_name: str = constants.SEQ_AXIS,
                   num_chunks: int | None = None):
  """``psum_scatter(matmul(x, w), scatter_dimension=0, tiled=True)`` at a
  parallel boundary — e.g. a row-parallel projection whose output drops
  back to token shards.  Ring-overlapped per the overlap policy; exact to
  accumulation-order tolerance vs the fused matmul+psum_scatter."""
  from easyparallellibrary_tpu.communicators import overlap
  from easyparallellibrary_tpu.parallel.planner import SITE_MATMUL_SCATTER
  n = jax.lax.axis_size(axis_name)
  if num_chunks is None:
    num_chunks = overlap.resolve_num_chunks(
        "matmul_reduce_scatter", n, m=x.shape[0], k=x.shape[1],
        n_out=w.shape[1], dtype=x.dtype, site=SITE_MATMUL_SCATTER)
  return overlap.matmul_reduce_scatter(x, w, axis_name,
                                       num_chunks=num_chunks)
