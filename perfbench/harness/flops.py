"""Operations and bytes an algorithm REQUIRES, from shapes alone.

Recomputation (remat, the chunked loss head) is never counted; causal
attention is counted once (the lower triangle), not as a full square.
"""

from __future__ import annotations


def gpt2_train_flops_per_token(n_layer, n_embd, n_inner, vocab,
                               seq_len) -> float:
  """Forward + backward FLOPs per token of a GPT-2 block stack with a
  tied head: 6 per matmul weight, plus causal attention.

  Attention per token and layer, forward: QK^T and PV are each
  2 * n_embd * (number of visible keys); averaged over a causal
  sequence that is (seq_len + 1) / 2 keys.  Backward is twice forward.
  """
  matmul_weights = n_layer * (4 * n_embd * n_embd + 2 * n_embd * n_inner) \
      + n_embd * vocab
  visible = (seq_len + 1) / 2.0
  attn_fwd = n_layer * 2 * (2 * n_embd * visible)
  return 6.0 * matmul_weights + 3.0 * attn_fwd


def flash_fwd_cost(batch, heads, seq, head_dim, dtype_bytes=2):
  """(flops, bytes) one causal flash-attention forward call requires:
  QK^T and PV over the lower triangle (diagonal included); q, k, v read
  once, o written once, one float32 log-sum-exp per row."""
  pairs = seq * (seq + 1) / 2.0
  flops = batch * heads * 2 * (2 * head_dim * pairs)
  nbytes = batch * heads * (4 * seq * head_dim * dtype_bytes + 4 * seq)
  return flops, nbytes


def flash_bwd_cost(batch, heads, seq, head_dim, dtype_bytes=2):
  """(flops, bytes) of the backward: five matmuls over the triangle
  (recomputed S = QK^T, dV = P^T dO, dP = dO V^T, dQ = dS K,
  dK = dS^T Q; the recomputed S is required by the algorithm, which
  stores no probabilities); reads q, k, v, o, do and the row statistics,
  writes dq, dk, dv."""
  pairs = seq * (seq + 1) / 2.0
  flops = batch * heads * 5 * (2 * head_dim * pairs)
  nbytes = batch * heads * (8 * seq * head_dim * dtype_bytes + 8 * seq)
  return flops, nbytes


def roofline_pct(flops, nbytes, seconds, peak_flops, peak_bytes_per_s):
  """Least time the chip could take over the time taken, in percent, and
  which bound holds."""
  t_compute = flops / peak_flops
  t_memory = nbytes / peak_bytes_per_s
  bound = "compute" if t_compute >= t_memory else "memory"
  return 100.0 * max(t_compute, t_memory) / seconds, bound
