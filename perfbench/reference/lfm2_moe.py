"""LFM2-8B-A1B (``model_type: lfm2_moe``) in plain ``jax.numpy``: the
forward pass of a decoder of gated short convolutions beside grouped
attention, with routed experts and no shared one.

The published modeling code as ISSUE 32 states it.  With ``RMS(x) = x *
rsqrt(mean(x^2) + norm_eps) * g`` and ``x`` a position's hidden state, no
bias anywhere:

* block ``l``: ``h = x + Mixer_l(RMS_op(x))``, ``y = h + FF_l(RMS_ffn(h))``;
  after the last block ONE ``RMS`` (the published ``embedding_norm``), and
  logits over the TIED embedding;
* ``Mixer``, ``layer_types[l] == "conv"``: ``[B | C | u] = W_in x`` (three
  ``hidden_size``-wide thirds); ``z = B * u``; ``c_t = sum_{j < L} w_j
  z_{t - (L - 1) + j}`` with ``L = conv_L_cache`` (depthwise, causal, ``z``
  before the first position zero, ``conv_bias`` false); ``out = W_out (C *
  c)``.  Written as ``L`` shifted products;
* ``Mixer``, ``"full_attention"``: ``q = W_q x`` as ``num_attention_heads``
  heads of ``hidden_size / num_attention_heads``, ``k`` and ``v`` as
  ``num_key_value_heads``; ``q = RMS_q(q)``, ``k = RMS_k(k)`` over each
  head's own values (one gain of the head's width each); rotate-half rotary
  on ALL of ``q`` and ``k`` (``rope_theta``, position = the token's index);
  causal ``softmax(q k^T / sqrt(head)) v``, query head ``h`` on K/V head
  ``h // (heads / kv heads)``; ``W_o``;
* ``FF``, ``l < num_dense_layers``: ``W_2(silu(W_1 x) * W_3 x)`` of
  ``intermediate_size``;
* ``FF`` elsewhere: ``s = sigmoid(W_g x)`` (``num_experts`` scores); chosen
  = the ``num_experts_per_tok`` largest of ``s + b`` (``use_expert_bias``:
  ``b`` in the choice ONLY); ``w = routed_scaling_factor * s[chosen] / (sum
  s[chosen] + 1e-6)`` (``norm_topk_prob``); ``sum_i w_i E_i(x)``, each a
  SiLU-gated MLP of ``moe_intermediate_size``.  No capacity, no shared
  expert.

float32 throughout, matmuls at ``highest`` precision, the whole sequence at
once: no cache, no window, no chunks, no kernels, no sorting of tokens.  It
imports nothing of the program under test.

Departures, each also under ``assumed`` in the configuration's file: the
order of norm, rotary and what attention keeps, the position of
``embedding_norm`` and the bias entering the choice only are the published
code's as the issue states them (no key of the config says so); the head
is tied (no key; the published 8.3B parameters add up only tied); the
thirds of ``W_in`` are ``B | C | u`` and rotary pairs are ``(i, i + d/2)``
(with seeded weights either is a permutation of columns); the expert bias
is drawn N(0, 0.02); weights are random from a seed.

Weights are ROUNDED TO BFLOAT16 ONCE (the published checkpoint is
bfloat16) and held as bfloat16: program and reference both start from those
values.  Norm gains and the expert bias stay float32.  The 14-layer cut is
4.67B parameters, 18.7 GB in float32, which no 16 GB chip holds: a layer's
mixer and dense weights are upcast where they are used, the routed experts
are walked by a ``lax.scan`` that upcasts ONE expert at a time (every
expert is applied to every token and weighted by its ``w``, zero where it
was not chosen: the same sum), and attention runs a head at a time
(``lax.map``) so that one ``[S, S]`` score matrix is live.

``precision``: ``float32`` is the reference.  The controls show whether
the check fails when the work is done in a lower precision: ``fp8`` /
``bfloat16`` / ``int8`` round the operands of every matmul (the router's
too); ``bf16router`` keeps every matmul exact and computes only the
router's scores from bfloat16 operands into a bfloat16 result;
``bf16conv`` keeps every matmul exact and rounds only the convolution's
inputs ``z`` (what the program's window holds) to bfloat16.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

CONV, ATTENTION = "conv", "full_attention"
PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16router",
              "bf16conv")
_EXACT = ("float32", "bf16router", "bf16conv")
ROUTE_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
  layer_types: tuple
  hidden_size: int
  intermediate_size: int
  moe_intermediate_size: int
  num_attention_heads: int
  num_key_value_heads: int
  conv_L_cache: int
  num_dense_layers: int
  num_experts: int
  num_experts_per_tok: int
  vocab_size: int
  n_positions: int               # served context: the most a request holds
  routed_scaling_factor: float = 1.0
  norm_topk_prob: bool = True
  rope_theta: float = 1e6
  norm_eps: float = 1e-5
  initializer_range: float = 0.02
  bias_std: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "Lfm2MoeConfig":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum."""
    assumed = doc.get("assumed", {})
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("model_type", "lfm2_moe")):
      if doc.get(key, want) != want:
        raise ValueError(f"this reference writes {key} = {want!r} only; "
                         f"the configuration says {doc[key]!r}")
    types = tuple(doc["layer_types"])
    if len(types) != doc["num_hidden_layers"]:
      raise ValueError(f"layer_types names {len(types)} layers, "
                       f"num_hidden_layers {doc['num_hidden_layers']}")
    if set(types) - {CONV, ATTENTION}:
      raise ValueError(f"layer_types may hold {CONV!r} and {ATTENTION!r}; "
                       f"got {sorted(set(types))}")
    return Lfm2MoeConfig(
        layer_types=types, hidden_size=doc["hidden_size"],
        intermediate_size=doc["intermediate_size"],
        moe_intermediate_size=doc["moe_intermediate_size"],
        num_attention_heads=doc["num_attention_heads"],
        num_key_value_heads=doc["num_key_value_heads"],
        conv_L_cache=doc["conv_L_cache"],
        num_dense_layers=doc["num_dense_layers"],
        num_experts=doc["num_experts"],
        num_experts_per_tok=doc["num_experts_per_tok"],
        vocab_size=doc["vocab_size"],
        n_positions=assumed.get("served_context",
                                doc["max_position_embeddings"]),
        routed_scaling_factor=doc["routed_scaling_factor"],
        norm_topk_prob=doc["norm_topk_prob"],
        rope_theta=float(doc["rope_theta"]), norm_eps=doc["norm_eps"],
        initializer_range=assumed.get("initializer_range", 0.02),
        bias_std=assumed.get("expert_bias_std", 0.02))

  @property
  def num_hidden_layers(self) -> int:
    return len(self.layer_types)

  @property
  def head_dim(self) -> int:
    return self.hidden_size // self.num_attention_heads

  def is_dense(self, i: int) -> bool:
    return i < self.num_dense_layers

  def mixer_params(self, kind: str) -> int:
    D, hd = self.hidden_size, self.head_dim
    if kind == CONV:
      return 4 * D * D + self.conv_L_cache * D
    kv = self.num_key_value_heads * hd
    return 2 * D * D + 2 * D * kv + 2 * hd

  def ff_params(self, dense: bool) -> int:
    D = self.hidden_size
    if dense:
      return 3 * D * self.intermediate_size
    E = self.num_experts
    return D * E + E + E * 3 * D * self.moe_intermediate_size

  def param_count(self) -> int:
    """Every weight once: the tied embedding, the final norm, and a
    layer's two norms, mixer and feed-forward."""
    D = self.hidden_size
    return self.vocab_size * D + D + sum(
        2 * D + self.mixer_params(kind) + self.ff_params(self.is_dense(i))
        for i, kind in enumerate(self.layer_types))


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


def init_mixer(cfg: Lfm2MoeConfig, key, kind: str) -> dict:
  """One layer's mixer and its two outer norms."""
  D, hd, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
  k = jax.random.split(key, 8)
  out = {"norm_in": _gain(k[0], D, std), "norm_ff": _gain(k[1], D, std)}
  if kind == CONV:
    L = cfg.conv_L_cache
    bound = L ** -0.5
    out.update(
        in_proj=_normal(k[2], (D, 3 * D), std),
        conv_w=jax.random.uniform(k[3], (L, D), jnp.float32, -bound,
                                  bound).astype(_BF16),
        out_proj=_normal(k[4], (D, D), _residual_std(cfg)))
  else:
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out.update(
        q=_normal(k[2], (D, H * hd), std),
        k=_normal(k[3], (D, Hkv * hd), std),
        v=_normal(k[4], (D, Hkv * hd), std),
        o=_normal(k[5], (H * hd, D), _residual_std(cfg)),
        q_norm=_gain(k[6], hd, std), k_norm=_gain(k[7], hd, std))
  return out


def _init_mlp(cfg, key, width: int) -> dict:
  D, std = cfg.hidden_size, cfg.initializer_range
  k = jax.random.split(key, 3)
  return {"gate": _normal(k[0], (D, width), std),
          "up": _normal(k[1], (D, width), std),
          "down": _normal(k[2], (width, D), _residual_std(cfg))}


def init_ff(cfg: Lfm2MoeConfig, key, dense: bool) -> dict:
  """A layer's feed-forward: the dense MLP, or the router (values rounded
  to bfloat16, as the checkpoint holds them), the float32 expert bias and
  the experts stacked ``[E, ...]``, made ONE AT A TIME (``lax.map``: the
  float32 draws of one expert are all that is live)."""
  if dense:
    return _init_mlp(cfg, key, cfg.intermediate_size)
  D, E = cfg.hidden_size, cfg.num_experts
  k = jax.random.split(key, 3)
  return {
      "router": _normal(k[0], (D, E), cfg.initializer_range),
      "bias": cfg.bias_std * jax.random.normal(k[1], (E,), jnp.float32),
      "experts": jax.lax.map(
          lambda e: _init_mlp(cfg, jax.random.fold_in(k[2], e),
                              cfg.moe_intermediate_size), jnp.arange(E)),
  }


def layer_keys(key, i: int):
  """``(mixer key, feed-forward key)`` of layer ``i``: a layer's weights
  depend on the seed and its index alone, so the glue that places them in
  the program's tree can make them one layer at a time."""
  k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
  return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def top_keys(key):
  """``(embedding key, final norm key)``."""
  k = jax.random.fold_in(key, 0)
  return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def init_embedding(cfg: Lfm2MoeConfig, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_layer(cfg: Lfm2MoeConfig, key, i: int) -> dict:
  k_mix, k_ff = layer_keys(key, i)
  return {"mixer": init_mixer(cfg, k_mix, cfg.layer_types[i]),
          "ff": init_ff(cfg, k_ff, cfg.is_dense(i))}


def init_params(cfg: Lfm2MoeConfig, key) -> dict:
  """Seeded weights: the embedding (also the head), the final norm's
  gain, and ``layers``, a tuple of one ``{"mixer", "ff"}`` a layer."""
  k_embed, k_norm = top_keys(key)
  return {
      "embed": init_embedding(cfg, k_embed),
      "norm_f": _gain(k_norm, cfg.hidden_size, cfg.initializer_range),
      "layers": tuple(init_layer(cfg, key, i)
                      for i in range(cfg.num_hidden_layers)),
  }


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
  if precision in _EXACT:
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
  elif precision not in _EXACT:
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


def short_conv(cfg: Lfm2MoeConfig, h, p, precision: str):
  """The gated short convolution on ``h`` [B, S, D]: ``L`` shifted
  products of ``z = B * u`` with the taps, tap ``L - 1`` on the current
  position."""
  D, L = cfg.hidden_size, cfg.conv_L_cache
  bcu = _matmul(h, p["in_proj"], precision)
  gate_b, gate_c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
  z = gate_b * u
  if precision == "bf16conv":
    z = z.astype(_BF16).astype(jnp.float32)
  w = p["conv_w"].astype(jnp.float32)
  S = z.shape[1]
  padded = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
  conv = sum(padded[:, j:j + S] * w[j] for j in range(L))
  return _matmul(gate_c * conv, p["out_proj"], precision)


def attention(cfg: Lfm2MoeConfig, h, p, precision: str):
  """Grouped attention on ``h`` [B, S, D]: queries and keys normalised a
  head, then rotated; a head at a time."""
  B, S, _ = h.shape
  H, Hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)
  eps = cfg.norm_eps
  q = _matmul(h, p["q"], precision).reshape(B, S, H, hd)
  k = _matmul(h, p["k"], precision).reshape(B, S, Hkv, hd)
  v = _matmul(h, p["v"], precision).reshape(B, S, Hkv, hd)
  q = rotary(rms_norm(q, p["q_norm"], eps), cfg.rope_theta)
  k = rotary(rms_norm(k, p["k_norm"], eps), cfg.rope_theta)
  causal = jnp.tril(jnp.ones((S, S), bool))
  heads_first = lambda x: jnp.moveaxis(x, 2, 0)
  k, v = heads_first(k), heads_first(v)

  def head(args):
    qh, kv_head = args                                  # [B, S, hd], index
    kh = jax.lax.dynamic_index_in_dim(k, kv_head, keepdims=False)
    vh = jax.lax.dynamic_index_in_dim(v, kv_head, keepdims=False)
    scores = _einsum("bqd,bkd->bqk", qh, kh, precision) / np.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return _einsum("bqk,bdk->bqd", probs, vh.transpose(0, 2, 1), precision)

  ctx = jax.lax.map(head, (heads_first(q), jnp.arange(H) // (H // Hkv)))
  ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, S, H * hd)
  return _matmul(ctx, p["o"], precision)


def mlp(h, p, precision: str):
  return _matmul(silu(_matmul(h, p["gate"], precision))
                 * _matmul(h, p["up"], precision), p["down"], precision)


def route(cfg: Lfm2MoeConfig, h, router, bias, precision: str):
  """``(chosen [B, S, k], weights [B, S, k])``: sigmoid scores, the bias
  in the choice only, the chosen scores over their sum plus 1e-6."""
  if precision == "bf16router":
    s = jax.nn.sigmoid(jnp.matmul(h.astype(_BF16), router.astype(_BF16)))
    s = s.astype(jnp.float32)
  else:
    s = jax.nn.sigmoid(_matmul(h, router, precision))
  _, chosen = jax.lax.top_k(s + bias, cfg.num_experts_per_tok)
  w = jnp.take_along_axis(s, chosen, -1)
  if cfg.norm_topk_prob:
    w = w / (jnp.sum(w, -1, keepdims=True) + ROUTE_NORM_EPS)
  return chosen, w * cfg.routed_scaling_factor


def moe(cfg: Lfm2MoeConfig, h, p, precision: str):
  """``sum_i w_i Expert_i(h)``: every expert applied to every token, one
  at a time, weighted by its ``w`` where chosen and 0 elsewhere."""
  E = cfg.num_experts
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
  return routed


def hidden(cfg: Lfm2MoeConfig, params, ids, precision: str = "float32"):
  """Final-RMSNorm hidden states [B, S, D] of token ids [B, S]."""
  if precision not in PRECISIONS:
    raise ValueError(f"precision {precision!r}")
  eps = cfg.norm_eps
  x = params["embed"][ids].astype(jnp.float32)
  for i, (kind, layer) in enumerate(zip(cfg.layer_types, params["layers"])):
    mix, ff = layer["mixer"], layer["ff"]
    mixer = short_conv if kind == CONV else attention
    x = x + mixer(cfg, rms_norm(x, mix["norm_in"], eps), mix, precision)
    h = rms_norm(x, mix["norm_ff"], eps)
    x = x + (mlp(h, ff, precision) if cfg.is_dense(i)
             else moe(cfg, h, ff, precision))
  return rms_norm(x, params["norm_f"], eps)


def logits(cfg: Lfm2MoeConfig, params, ids, precision=None):
  """[B, S, vocab] logits over the tied embedding."""
  precision = precision or "float32"
  return _einsum("bsd,vd->bsv", hidden(cfg, params, ids, precision),
                 params["embed"].astype(jnp.float32), precision)
