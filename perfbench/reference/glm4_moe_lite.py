"""GLM-4.7-Flash (``model_type: glm4_moe_lite``) in plain ``jax.numpy``:
the forward pass of a sparse-expert decoder with multi-head latent
attention.

The DeepSeek-V3 block (DeepSeek-AI 2024, "DeepSeek-V3 Technical Report",
sections 2.1.1 and 2.1.2) at GLM-4.7-Flash's sizes, as ``transformers``
runs it.  With ``RMS(x) = x * rsqrt(mean(x^2) + eps) * g`` and ``x`` a
position's hidden state:

* block: ``h = x + MLA(RMS(x))``, ``y = h + FF(RMS(h))``; a final ``RMS``;
  an UNTIED head.  ``FF`` is a SiLU-gated MLP of ``intermediate_size`` in
  the first ``first_k_dense_replace`` layers, the expert layer elsewhere;
* MLA, no biases: ``c_q = RMS(W_qa x)``; ``[q_nope | q_rope] = W_qb c_q``
  per head; ``[c_kv | k_r] = W_kva x``; ``c = RMS(c_kv)``; rotary
  (``rope_theta``, every one of the ``qk_rope_head_dim`` dims, position =
  the token's index) on ``q_rope`` of each head and on the ONE ``k_r`` all
  heads share; ``[k_nope | v] = W_kvb c`` per head; ``score = (q_nope .
  k_nope + q_rope . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``,
  causal softmax, ``W_o concat_heads(sum p v)``.  EXPANDED keys and values
  over the whole sequence: no latent cache, no absorbed products;
* expert layer (``topk_method: noaux_tc`` with ``n_group`` 1 and
  ``topk_group`` 1, so the group restriction is the identity): ``s =
  sigmoid(W_g x)`` (``n_routed_experts`` scores); chosen = the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` =
  ``e_score_correction_bias``, in the choice ONLY); ``w =
  routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-20)``
  (``norm_topk_prob``); ``FF(x) = Shared(x) + sum_i w_i Expert_i(x)``, each
  a SiLU-gated MLP of ``moe_intermediate_size`` (the shared one of
  ``n_shared_experts`` times that).  No capacity: every chosen expert is
  computed for every token.

float32 throughout, matmuls at ``highest`` precision, the whole sequence at
once: no cache, no chunks, no kernels, no sorting of tokens.  It imports
nothing of the program under test.

Departures, each also under ``assumed`` in the configuration's file:
rotary pairs are (i, i + d/2) ("rotate half"; with seeded weights the
interleaved convention is a permutation of ``W_qb``'s and ``W_kva``'s
columns); the multi-token-prediction module (``num_nextn_predict_layers``)
takes no part in next-token logits and is not built; weights are random
from a seed.

Weights are ROUNDED TO BFLOAT16 ONCE (the published checkpoint is
bfloat16): program and reference both start from those values, so holding
them as bfloat16 loses nothing.  Norm gains and the selection bias stay
float32.  The 8-layer cut is 5.17B parameters, 20.7 GB in float32, which no
16 GB chip holds: the layers are stacked by kind and walked by ``lax.scan``,
a layer's attention and shared weights are upcast where they are used, and
the routed experts are walked by an inner ``lax.scan`` that upcasts ONE
expert at a time (every expert is applied to every token and weighted by
its ``w``, zero where it was not chosen: the same sum, and the compiler
cannot hoist 64 casts out of a loop indexed by the expert).  Attention runs
a head at a time (``lax.map``) so that one ``[S, S]`` score matrix is live.

``precision``: ``float32`` is the reference.  The controls show that the
check fails when the work is done in a lower precision: ``fp8`` /
``bfloat16`` / ``int8`` round the operands of every matmul (the router's
too); ``bf16router`` keeps every matmul exact and computes only the
router's scores from bfloat16 operands into a bfloat16 result.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

DENSE, MOE = "dense", "moe"
PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16router")


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
  num_hidden_layers: int
  hidden_size: int
  intermediate_size: int
  moe_intermediate_size: int
  num_attention_heads: int
  q_lora_rank: int
  kv_lora_rank: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  n_routed_experts: int
  n_shared_experts: int
  num_experts_per_tok: int
  first_k_dense_replace: int
  vocab_size: int
  n_positions: int               # served context: the most a request holds
  routed_scaling_factor: float = 1.8
  norm_topk_prob: bool = True
  rope_theta: float = 1e6
  rms_norm_eps: float = 1e-5
  initializer_range: float = 0.02
  bias_std: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "Glm4MoeLiteConfig":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum."""
    assumed = doc.get("assumed", {})
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("partial_rotary_factor", 1), ("rope_scaling", None),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False)):
      if doc.get(key, want) != want:
        raise ValueError(f"this reference writes {key} = {want!r} only; "
                         f"the configuration says {doc[key]!r}")
    return Glm4MoeLiteConfig(
        num_hidden_layers=doc["num_hidden_layers"],
        hidden_size=doc["hidden_size"],
        intermediate_size=doc["intermediate_size"],
        moe_intermediate_size=doc["moe_intermediate_size"],
        num_attention_heads=doc["num_attention_heads"],
        q_lora_rank=doc["q_lora_rank"], kv_lora_rank=doc["kv_lora_rank"],
        qk_nope_head_dim=doc["qk_nope_head_dim"],
        qk_rope_head_dim=doc["qk_rope_head_dim"],
        v_head_dim=doc["v_head_dim"],
        n_routed_experts=doc["n_routed_experts"],
        n_shared_experts=doc["n_shared_experts"],
        num_experts_per_tok=doc["num_experts_per_tok"],
        first_k_dense_replace=doc["first_k_dense_replace"],
        vocab_size=doc["vocab_size"],
        n_positions=assumed.get("served_context",
                                doc["max_position_embeddings"]),
        routed_scaling_factor=doc["routed_scaling_factor"],
        norm_topk_prob=doc["norm_topk_prob"],
        rope_theta=float(doc["rope_theta"]),
        rms_norm_eps=doc["rms_norm_eps"],
        initializer_range=assumed.get("initializer_range", 0.02),
        bias_std=assumed.get("e_score_correction_bias_std", 0.02))

  def layer_kinds(self) -> tuple:
    """The leading ``first_k_dense_replace`` layers are dense, the others
    expert layers."""
    return tuple(DENSE if i < self.first_k_dense_replace else MOE
                 for i in range(self.num_hidden_layers))

  def param_count(self) -> int:
    D, F, Fe = (self.hidden_size, self.intermediate_size,
                self.moe_intermediate_size)
    H, qr, r = self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank
    dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                  self.v_head_dim)
    E = self.n_routed_experts
    mla = (D * qr + qr + qr * H * (dn + dr) + D * (r + dr) + r
           + r * H * (dn + dv) + H * dv * D)
    moe = (D * E + E + E * 3 * D * Fe
           + 3 * D * Fe * self.n_shared_experts)
    kinds = self.layer_kinds()
    return (2 * self.vocab_size * D + D + len(kinds) * (mla + 2 * D)
            + kinds.count(DENSE) * 3 * D * F + kinds.count(MOE) * moe)


def seed_key(seed: int, stream: int = 0):
  """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
  words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
  return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# ------------------------------------------------------------- weights --

_BF16 = jnp.bfloat16


def _normal(key, shape, std):
  """N(0, std), rounded to bfloat16 once."""
  return (std * jax.random.normal(key, shape, jnp.float32)).astype(_BF16)


def _gain(key, n, std):
  """A norm's gain: drawn near one (a dropped or misplaced gain then shows
  in the comparison), float32."""
  return 1.0 + std * jax.random.normal(key, (n,), jnp.float32)


def _residual_std(cfg) -> float:
  return cfg.initializer_range / np.sqrt(2.0 * cfg.num_hidden_layers)


def init_attention(cfg: Glm4MoeLiteConfig, key) -> dict:
  """One layer's MLA and its two outer norms."""
  D, H = cfg.hidden_size, cfg.num_attention_heads
  qr, r = cfg.q_lora_rank, cfg.kv_lora_rank
  dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
  std = cfg.initializer_range
  k = jax.random.split(key, 9)
  return {
      "norm_in": _gain(k[0], D, std), "norm_ff": _gain(k[1], D, std),
      "q_a": _normal(k[2], (D, qr), std), "q_norm": _gain(k[3], qr, std),
      "q_b": _normal(k[4], (qr, H * (dn + dr)), std),
      "kv_a": _normal(k[5], (D, r + dr), std),
      "kv_norm": _gain(k[6], r, std),
      "kv_b": _normal(k[7], (r, H * (dn + dv)), std),
      "o": _normal(k[8], (H * dv, D), _residual_std(cfg)),
  }


def _init_mlp(cfg, key, width: int) -> dict:
  D, std = cfg.hidden_size, cfg.initializer_range
  k = jax.random.split(key, 3)
  return {"gate": _normal(k[0], (D, width), std),
          "up": _normal(k[1], (D, width), std),
          "down": _normal(k[2], (width, D), _residual_std(cfg))}


def init_dense_ff(cfg: Glm4MoeLiteConfig, key) -> dict:
  return _init_mlp(cfg, key, cfg.intermediate_size)


def init_moe_ff(cfg: Glm4MoeLiteConfig, key) -> dict:
  """An expert layer: router (values rounded to bfloat16, as the
  checkpoint holds them), the float32 selection bias, the routed experts
  stacked ``[E, ...]`` and made ONE AT A TIME (``lax.map``: the float32
  draws of one expert are all that is live), the shared expert."""
  D, E = cfg.hidden_size, cfg.n_routed_experts
  k = jax.random.split(key, 4)
  experts = jax.lax.map(
      lambda e: _init_mlp(cfg, jax.random.fold_in(k[2], e),
                          cfg.moe_intermediate_size), jnp.arange(E))
  return {
      "router": _normal(k[0], (D, E), cfg.initializer_range),
      "bias": cfg.bias_std * jax.random.normal(k[1], (E,), jnp.float32),
      "experts": experts,
      "shared": _init_mlp(
          cfg, k[3], cfg.n_shared_experts * cfg.moe_intermediate_size),
  }


def layer_keys(key, i: int):
  """``(attention key, feed-forward key)`` of layer ``i``: a layer's
  weights depend on the seed and its index alone, so the glue that places
  them in the program's tree can make them one layer at a time."""
  k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
  return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def top_keys(key):
  """``(embedding key, head key, final norm key)``."""
  k = jax.random.fold_in(key, 0)
  return tuple(jax.random.fold_in(k, j) for j in range(3))


def init_embedding(cfg: Glm4MoeLiteConfig, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_head(cfg: Glm4MoeLiteConfig, key):
  return _normal(key, (cfg.hidden_size, cfg.vocab_size),
                 cfg.initializer_range)


def init_params(cfg: Glm4MoeLiteConfig, key) -> dict:
  """Seeded weights, stacked by kind on a leading axis: ``attention``
  over all layers, ``dense`` and ``moe`` over the layers of that kind in
  order.  Made one layer at a time (``lax.map``)."""
  kinds = cfg.layer_kinds()
  k_embed, k_head, k_norm = top_keys(key)

  def stack(init, which, half):
    return jax.lax.map(lambda i: init(cfg, layer_keys(key, i)[half]),
                       jnp.asarray(which, jnp.int32))

  params = {
      "embed": init_embedding(cfg, k_embed),
      "head": init_head(cfg, k_head),
      "norm_f": _gain(k_norm, cfg.hidden_size, cfg.initializer_range),
      "attention": stack(init_attention, range(len(kinds)), 0),
  }
  for kind, init in ((DENSE, init_dense_ff), (MOE, init_moe_ff)):
    which = [i for i, k in enumerate(kinds) if k == kind]
    if which:
      params[kind] = stack(init, which, 1)
  return params


# ------------------------------------------------------------ precision --


def _int8(x, axis):
  scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
  scale = jnp.where(scale > 0, scale, 1.0)
  return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fp8(x):
  return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _matmul(x, w, precision: str):
  """``x @ w`` over the last axis of ``x`` and the first of ``w``; ``w``
  may be the bfloat16 it is held as (its values are exact in float32)."""
  hi = jax.lax.Precision.HIGHEST
  w = w.astype(jnp.float32)
  if precision in ("float32", "bf16router"):
    return jnp.matmul(x, w, precision=hi)
  if precision == "bfloat16":
    return jnp.matmul(x.astype(_BF16), w.astype(_BF16),
                      preferred_element_type=jnp.float32)
  if precision == "int8":
    return jnp.matmul(_int8(x, -1), _int8(w, 0), precision=hi)
  if precision == "fp8":
    return jnp.matmul(_fp8(x), _fp8(w), precision=hi)
  raise ValueError(f"precision {precision!r}")


def _einsum(spec, a, b, precision: str):
  """Contraction over the LAST axis of both operands."""
  if precision == "bfloat16":
    return jnp.einsum(spec, a.astype(_BF16), b.astype(_BF16),
                      preferred_element_type=jnp.float32)
  if precision == "int8":
    a, b = _int8(a, -1), _int8(b, -1)
  elif precision == "fp8":
    a, b = _fp8(a), _fp8(b)
  elif precision not in ("float32", "bf16router"):
    raise ValueError(f"precision {precision!r}")
  return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# -------------------------------------------------------------- forward --


def rms_norm(x, g, eps):
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                           + eps) * g


def silu(x):
  return x * jax.nn.sigmoid(x)


def rotary(x, theta: float):
  """Rotate-half rotary embedding of ``x`` [B, S, ..., d] over all ``d``
  dims: pair ``i`` is ``(x[i], x[i + d/2])``, turned by ``s * theta^(-2i /
  d)`` at position ``s``."""
  S, d = x.shape[1], x.shape[-1]
  inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # [S, d/2]
  ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (d // 2,))
  a, b = x[..., :d // 2], x[..., d // 2:]
  return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                          b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def mla(cfg: Glm4MoeLiteConfig, h, p, precision: str):
  """Multi-head latent attention on ``h`` [B, S, D], keys and values
  EXPANDED for every position and head."""
  B, S, _ = h.shape
  H, r = cfg.num_attention_heads, cfg.kv_lora_rank
  dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
  eps = cfg.rms_norm_eps
  c_q = rms_norm(_matmul(h, p["q_a"], precision), p["q_norm"], eps)
  q = _matmul(c_q, p["q_b"], precision).reshape(B, S, H, dn + dr)
  kv = _matmul(h, p["kv_a"], precision)
  c = rms_norm(kv[..., :r], p["kv_norm"], eps)
  k_r = rotary(kv[..., r:], cfg.rope_theta)                   # [B, S, dr]
  kvb = _matmul(c, p["kv_b"], precision).reshape(B, S, H, dn + dv)
  q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], cfg.rope_theta)], -1)
  k = jnp.concatenate(
      [kvb[..., :dn], jnp.broadcast_to(k_r[:, :, None], (B, S, H, dr))], -1)
  v = kvb[..., dn:]
  causal = jnp.tril(jnp.ones((S, S), bool))

  def head(qkv):
    qh, kh, vh = qkv                       # [B, S, dn+dr] x2, [B, S, dv]
    scores = _einsum("bqd,bkd->bqk", qh, kh, precision) / np.sqrt(dn + dr)
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return _einsum("bqk,bdk->bqd", probs, vh.transpose(0, 2, 1), precision)

  heads_first = lambda x: jnp.moveaxis(x, 2, 0)
  ctx = jax.lax.map(head, (heads_first(q), heads_first(k), heads_first(v)))
  ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, S, H * dv)
  return _matmul(ctx, p["o"], precision)


def mlp(h, p, precision: str):
  return _matmul(silu(_matmul(h, p["gate"], precision))
                 * _matmul(h, p["up"], precision), p["down"], precision)


def route(cfg: Glm4MoeLiteConfig, h, router, bias, precision: str):
  """``(chosen [B, S, k], weights [B, S, k])`` of the ``noaux_tc``
  router."""
  if precision == "bf16router":
    s = jax.nn.sigmoid(jnp.matmul(h.astype(_BF16), router.astype(_BF16)))
    s = s.astype(jnp.float32)
  else:
    s = jax.nn.sigmoid(_matmul(h, router, precision))
  _, chosen = jax.lax.top_k(s + bias, cfg.num_experts_per_tok)
  w = jnp.take_along_axis(s, chosen, -1)
  if cfg.norm_topk_prob:
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
  return chosen, w * cfg.routed_scaling_factor


def moe(cfg: Glm4MoeLiteConfig, h, p, precision: str):
  """``Shared(h) + sum_i w_i Expert_i(h)``: every expert applied to every
  token, one at a time, weighted by its ``w`` where chosen and 0
  elsewhere."""
  E = cfg.n_routed_experts
  chosen, w = route(cfg, h, p["router"], p["bias"], precision)
  weight_of = jnp.sum(
      jax.nn.one_hot(chosen, E, dtype=jnp.float32) * w[..., None], -2)

  def add_expert(acc, e):
    pe = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, e, keepdims=False),
        p["experts"])
    w_e = jax.lax.dynamic_index_in_dim(weight_of, e, -1, keepdims=True)
    return acc + w_e * mlp(h, pe, precision), None

  routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), jnp.arange(E))
  return mlp(h, p["shared"], precision) + routed


def hidden(cfg: Glm4MoeLiteConfig, params, ids, precision: str = "float32"):
  """Final-RMSNorm hidden states [B, S, D] of token ids [B, S]."""
  if precision not in PRECISIONS:
    raise ValueError(f"precision {precision!r}")
  eps = cfg.rms_norm_eps
  x = params["embed"][ids].astype(jnp.float32)
  kinds = cfg.layer_kinds()
  ffs = {DENSE: lambda h, p: mlp(h, p, precision),
         MOE: lambda h, p: moe(cfg, h, p, precision)}
  first = 0
  for kind in (DENSE, MOE):         # the dense layers lead
    n = kinds.count(kind)
    if not n:
      continue

    def layer(x, ps, ff=ffs[kind]):
      att, p = ps
      x = x + mla(cfg, rms_norm(x, att["norm_in"], eps), att, precision)
      return x + ff(rms_norm(x, att["norm_ff"], eps), p), None

    att = jax.tree_util.tree_map(lambda a: a[first:first + n],
                                 params["attention"])
    x, _ = jax.lax.scan(layer, x, (att, params[kind]))
    first += n
  return rms_norm(x, params["norm_f"], eps)


def logits(cfg: Glm4MoeLiteConfig, params, ids, precision=None):
  """[B, S, vocab] logits through the untied head."""
  precision = precision or "float32"
  return _matmul(hidden(cfg, params, ids, precision), params["head"],
                 precision)
