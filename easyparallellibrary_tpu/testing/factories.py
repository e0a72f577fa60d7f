"""Replica factories: deterministic ``(model, params)`` builders that a
:class:`~easyparallellibrary_tpu.serving.transport.ProcessTransport`
child can import by name.

A process-hosted replica owns its own JAX runtime, so live model/params
objects never cross the wire — instead the parent ships a factory spec
(``"module:attr"`` + JSON kwargs) and BOTH sides build from it: the
child for serving, the parent for its bit-exactness oracle.  Factories
must therefore be **deterministic in their kwargs** (fixed PRNG seed,
no ambient state): identical kwargs on the same backend yield
bit-identical params in every process, which is what makes
cross-process failover exactly as bit-exact as the in-process kind.

Used by ``make chaos-proc`` (tests/test_serving_transport.py).
"""

from __future__ import annotations

from typing import Tuple


def tiny_gpt(vocab_size: int = 64, num_layers: int = 2,
             num_heads: int = 4, d_model: int = 32, d_ff: int = 64,
             max_seq_len: int = 32, init_len: int = 4,
             seed: int = 0) -> Tuple[object, object]:
  """The chaos/bench workhorse: a tiny fp32 GPT with params initialized
  from ``PRNGKey(seed)`` — small enough that a child process compiles
  its fused step in seconds, big enough that greedy streams are
  non-trivial."""
  import jax
  import jax.numpy as jnp

  from easyparallellibrary_tpu.models import GPT, GPTConfig

  cfg = GPTConfig(vocab_size=vocab_size, num_layers=num_layers,
                  num_heads=num_heads, d_model=d_model, d_ff=d_ff,
                  max_seq_len=max_seq_len, dtype=jnp.float32)
  model = GPT(cfg)
  params = model.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, init_len), jnp.int32))["params"]
  return model, params
