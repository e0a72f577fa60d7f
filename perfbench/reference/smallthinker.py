"""SmallThinker-21BA3B-Instruct (``model_type: smallthinker``) in plain
``jax.numpy``: the forward pass of a decoder whose layers attend behind a
window with rotary positions in three of four and over the whole context
with NO positions in the fourth, and route their experts from the layer's
INPUT.

The layer as ISSUE 42 states it.  With ``RMS(x) = x * rsqrt(mean(x^2) +
rms_norm_eps) * g``, ``x`` the layer's input, no bias anywhere:

* ``r = x W_r``: ``moe_num_primary_experts`` router logits from the layer's
  INPUT, before the attention and before any norm ("router placed before
  attention");
* ``a = RMS_1(x)``; ``q = a W_q`` as ``num_attention_heads`` heads of
  ``head_dim`` (28 x 128 = 3584, not ``hidden_size``), ``k`` and ``v`` as
  ``num_key_value_heads``; where ``rope_layout[l]`` is 1 rotate-half rotary
  over ALL ``head_dim`` columns of ``q`` and ``k`` (``rope_theta``,
  position = the token's index), where it is 0 nothing; scores ``q k^T /
  sqrt(head_dim)``, query head ``h`` on K/V head ``h // (heads / kv
  heads)``, causal; where ``sliding_window_layout[l]`` is 1 position ``t``
  sees ``s`` with ``t - sliding_window_size < s <= t`` (its own among
  them); ``h = x + concat(heads) W_o``;
* ``m = RMS_2(h)``; ``chosen = top_k(r)``, ``k =
  moe_num_active_primary_experts``; ``w = softmax(r[chosen])``
  (``moe_primary_router_apply_softmax``; equal to the softmax over all
  renormalised over the chosen, so ``norm_topk_prob`` changes nothing);
  ``y = sum_i w_i W_down,i (relu(m W_gate,i) * (m W_up,i))``; the layer
  gives ``h + y``;
* after the last layer ONE ``RMS`` and the UNTIED head.

float32 throughout, matmuls at ``highest`` precision, the whole sequence at
once: no cache, no ring, no chunks, no kernels, no sorting of tokens.  It
imports nothing of the program under test.

Departures, each also under ``assumed`` in the configuration's file: the
router reads the un-normed input (the published modeling code and
llama.cpp's graph for the family; ``described_as`` says only "before
attention"); rotary pairs are ``(i, i + d/2)`` over all 128 columns; the
window counts the query's own position (Hugging Face's mask); no biases;
``described_as`` also mentions "secondary experts", the config has none and
the config wins; weights are random from a seed.

Weights are ROUNDED TO BFLOAT16 ONCE (the published checkpoint is bfloat16)
and program and reference both start from those values; norm gains stay
float32.  What is HELD: the embedding, the head, a layer's attention and
router as bfloat16, upcast where they are used; a layer's 64 experts as
their KEY, each expert drawn again where the ``lax.scan`` over the experts
uses it (a draw depends on the key and the expert's index alone, so it is
the same expert every time).  The reason is the chip's memory: the check
scores requests of up to 14,848 positions over the whole vocabulary, whose
float32 logits are 9.0 GB; beside them 6.0 GB of held experts (7.9 GB of
weights in all) do not fit 16 GB, the experts' keys do.  Every expert is
applied to every token and weighted by its ``w``, zero where it was not
chosen: the same sum.  Attention runs a head and a block of
:data:`QUERY_BLOCK` queries at a time, so that one ``[block, S]`` score
matrix is live.

``precision``: ``float32`` is the reference.  The controls show whether the
check fails when the work is done differently: ``fp8`` / ``bfloat16`` /
``int8`` round the operands of every matmul (the router's too);
``bf16router`` keeps every matmul exact and computes only the router's
logits from bfloat16 operands into a bfloat16 result; and two PLANTED
FAULTS, for the builder's own use: ``no_window`` (the window layers attend
every ``s <= t``) and ``rope_everywhere`` (the full layers rotate too).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16router",
              "no_window", "rope_everywhere")
_EXACT = ("float32", "bf16router", "no_window", "rope_everywhere")
# Queries scored at a time, a head: the live score matrix is [block, S].
QUERY_BLOCK = 2048


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
  hidden_size: int
  num_attention_heads: int
  num_key_value_heads: int
  head_dim: int
  moe_ffn_hidden_size: int
  moe_num_primary_experts: int
  moe_num_active_primary_experts: int
  sliding_window_size: int
  sliding_window_layout: tuple
  rope_layout: tuple
  vocab_size: int
  n_positions: int               # served context: the most a request holds
  rope_theta: float = 1.5e6
  rms_norm_eps: float = 1e-6
  initializer_range: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "SmallThinkerConfig":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum."""
    assumed = doc.get("assumed", {})
    for key, want in (("moe_primary_router_apply_softmax", True),
                      ("tie_word_embeddings", False), ("rope_scaling", None)):
      if doc.get(key, want) != want:
        raise ValueError(f"this reference writes {key} = {want!r} only; "
                         f"the configuration says {doc[key]!r}")
    window, rope = (tuple(doc[k]) for k in ("sliding_window_layout",
                                            "rope_layout"))
    if not len(window) == len(rope) == doc["num_hidden_layers"]:
      raise ValueError(
          f"sliding_window_layout names {len(window)} layers, rope_layout "
          f"{len(rope)}, num_hidden_layers {doc['num_hidden_layers']}")
    return SmallThinkerConfig(
        hidden_size=doc["hidden_size"],
        num_attention_heads=doc["num_attention_heads"],
        num_key_value_heads=doc["num_key_value_heads"],
        head_dim=doc["head_dim"],
        moe_ffn_hidden_size=doc["moe_ffn_hidden_size"],
        moe_num_primary_experts=doc["moe_num_primary_experts"],
        moe_num_active_primary_experts=doc["moe_num_active_primary_experts"],
        sliding_window_size=doc["sliding_window_size"],
        sliding_window_layout=window, rope_layout=rope,
        vocab_size=doc["vocab_size"],
        n_positions=assumed.get("served_context",
                                doc["max_position_embeddings"]),
        rope_theta=float(doc["rope_theta"]),
        rms_norm_eps=doc["rms_norm_eps"],
        initializer_range=assumed.get("initializer_range", 0.02))

  @property
  def num_hidden_layers(self) -> int:
    return len(self.sliding_window_layout)

  def attention_params(self) -> int:
    D, hd = self.hidden_size, self.head_dim
    return (2 * D * self.num_attention_heads * hd
            + 2 * D * self.num_key_value_heads * hd)

  def expert_params(self) -> int:
    return 3 * self.hidden_size * self.moe_ffn_hidden_size

  def layer_params(self) -> int:
    """A layer's two norms, attention, router and experts."""
    D, E = self.hidden_size, self.moe_num_primary_experts
    return (2 * D + self.attention_params() + D * E
            + E * self.expert_params())

  def param_count(self) -> int:
    """Every weight once: the embedding, the untied head, the final norm
    and the layers."""
    D = self.hidden_size
    return (2 * self.vocab_size * D + D
            + self.num_hidden_layers * self.layer_params())


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


def init_attention(cfg: SmallThinkerConfig, key) -> dict:
  """One layer's attention and its two outer norms."""
  D, hd, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
  H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
  k = jax.random.split(key, 6)
  return {"norm_in": _gain(k[0], D, std), "norm_ff": _gain(k[1], D, std),
          "q": _normal(k[2], (D, H * hd), std),
          "k": _normal(k[3], (D, Hkv * hd), std),
          "v": _normal(k[4], (D, Hkv * hd), std),
          "o": _normal(k[5], (H * hd, D), _residual_std(cfg))}


def init_expert(cfg: SmallThinkerConfig, experts_key, e) -> dict:
  """Expert ``e`` of the layer whose experts' key is ``experts_key``: a
  draw from the key and the index alone."""
  D, F, std = cfg.hidden_size, cfg.moe_ffn_hidden_size, cfg.initializer_range
  k = jax.random.split(jax.random.fold_in(experts_key, e), 3)
  return {"gate": _normal(k[0], (D, F), std),
          "up": _normal(k[1], (D, F), std),
          "down": _normal(k[2], (F, D), _residual_std(cfg))}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HeldExperts:
  """A layer's experts as they are HELD: their key (its data, one array
  leaf of the tree) and the configuration that says their shapes (static);
  :meth:`expert` draws one."""
  key_data: jax.Array
  cfg: SmallThinkerConfig

  def tree_flatten(self):
    return (self.key_data,), self.cfg

  @classmethod
  def tree_unflatten(cls, cfg, children):
    return cls(children[0], cfg)

  def expert(self, e) -> dict:
    return init_expert(self.cfg, jax.random.wrap_key_data(self.key_data), e)

  def sum_of_squares(self):
    """Over every weight of every expert, one drawn at a time."""
    sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                       for x in jax.tree_util.tree_leaves(t))
    total, _ = jax.lax.scan(
        lambda acc, e: (acc + sq(self.expert(e)), None), jnp.float32(0),
        jnp.arange(self.cfg.moe_num_primary_experts))
    return total


def init_ff(cfg: SmallThinkerConfig, key) -> dict:
  """A layer's feed-forward as it is HELD: the router (values rounded to
  bfloat16, as the checkpoint holds them) and the experts' key
  (:class:`HeldExperts`)."""
  k_router, k_experts = jax.random.split(key)
  return {"router": _normal(k_router, (cfg.hidden_size,
                                       cfg.moe_num_primary_experts),
                            cfg.initializer_range),
          "experts": HeldExperts(jax.random.key_data(k_experts), cfg)}


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


def init_embedding(cfg: SmallThinkerConfig, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_head(cfg: SmallThinkerConfig, key):
  return _normal(key, (cfg.hidden_size, cfg.vocab_size),
                 cfg.initializer_range)


def init_params(cfg: SmallThinkerConfig, key) -> dict:
  """Seeded weights as they are held (module docstring): the embedding,
  the head, the final norm's gain, and ``layers``, a tuple of one
  ``{"attention", "ff"}`` a layer."""
  k_embed, k_head, k_norm = top_keys(key)
  layers = []
  for i in range(cfg.num_hidden_layers):
    k_att, k_ff = layer_keys(key, i)
    layers.append({"attention": init_attention(cfg, k_att),
                   "ff": init_ff(cfg, k_ff)})
  return {"embed": init_embedding(cfg, k_embed),
          "lm_head": init_head(cfg, k_head),
          "norm_f": _gain(k_norm, cfg.hidden_size, cfg.initializer_range),
          "layers": tuple(layers)}


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


def rotary(x, theta: float):
  """Rotate-half rotary embedding of ``x`` [B, S, H, d] over all ``d``
  columns: pair ``i`` is ``(x[i], x[i + d/2])``, turned by ``s * theta^(-2i
  / d)`` at position ``s``."""
  S, d = x.shape[1], x.shape[-1]
  inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[None, :, None]
  a, b = x[..., :d // 2], x[..., d // 2:]
  return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                          b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention(cfg: SmallThinkerConfig, a, p, rope: bool, window,
              precision: str):
  """Grouped attention on the normed input ``a`` [B, S, D]: rotary or
  none, behind ``window`` positions or all (``None``); a head and a block
  of queries at a time."""
  B, S, _ = a.shape
  H, Hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)
  q = _matmul(a, p["q"], precision).reshape(B, S, H, hd)
  k = _matmul(a, p["k"], precision).reshape(B, S, Hkv, hd)
  v = _matmul(a, p["v"], precision).reshape(B, S, Hkv, hd)
  if rope:
    q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
  block = min(S, QUERY_BLOCK)
  n_blocks = -(-S // block)
  q = jnp.pad(q, ((0, 0), (0, n_blocks * block - S), (0, 0), (0, 0)))
  # [H, blocks, B, block, hd] against [H_kv, B, S, hd]
  q = jnp.moveaxis(q.reshape(B, n_blocks, block, H, hd), (3, 1), (0, 1))
  k, v = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)
  s = jnp.arange(S)[None, :]

  def tile(args):
    qb, kv_head, first = args                          # [B, block, hd]
    kh = jax.lax.dynamic_index_in_dim(k, kv_head, keepdims=False)
    vh = jax.lax.dynamic_index_in_dim(v, kv_head, keepdims=False)
    t = first + jnp.arange(block)[:, None]
    seen = s <= t
    if window is not None:
      seen &= s > t - window
    scores = _einsum("bqd,bkd->bqk", qb, kh, precision) / np.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return _einsum("bqk,bdk->bqd", probs, vh.transpose(0, 2, 1), precision)

  heads = jnp.repeat(jnp.arange(H) // (H // Hkv), n_blocks)
  firsts = jnp.tile(jnp.arange(n_blocks) * block, H)
  ctx = jax.lax.map(tile, (q.reshape(H * n_blocks, B, block, hd), heads,
                           firsts))
  ctx = jnp.moveaxis(ctx.reshape(H, n_blocks, B, block, hd), (0, 1), (3, 1))
  ctx = ctx.reshape(B, n_blocks * block, H * hd)[:, :S]
  return _matmul(ctx, p["o"], precision)


def expert_mlp(m, p, precision: str):
  """``W_down (relu(m W_gate) * (m W_up))``."""
  return _matmul(jax.nn.relu(_matmul(m, p["gate"], precision))
                 * _matmul(m, p["up"], precision), p["down"], precision)


def route(cfg: SmallThinkerConfig, x, router, precision: str):
  """``(chosen [B, S, k], weights [B, S, k])`` from the layer's INPUT
  ``x``: the ``k`` largest logits, softmax over them alone."""
  if precision == "bf16router":
    r = jnp.matmul(x.astype(_BF16), router.astype(_BF16)).astype(jnp.float32)
  else:
    r = _matmul(x, router, precision)
  top, chosen = jax.lax.top_k(r, cfg.moe_num_active_primary_experts)
  return chosen, jax.nn.softmax(top, axis=-1)


def moe(cfg: SmallThinkerConfig, m, x, p, precision: str):
  """``sum_i w_i Expert_i(m)``, routed from ``x``: every expert drawn and
  applied to every token, one at a time, weighted by its ``w`` where
  chosen and 0 elsewhere."""
  E = cfg.moe_num_primary_experts
  chosen, w = route(cfg, x, p["router"], precision)
  weight_of = jnp.sum(
      jax.nn.one_hot(chosen, E, dtype=jnp.float32) * w[..., None], -2)

  def add_expert(acc, e):
    w_e = jax.lax.dynamic_index_in_dim(weight_of, e, -1, keepdims=True)
    return acc + w_e * expert_mlp(m, p["experts"].expert(e), precision), None

  routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), jnp.arange(E))
  return routed


def hidden(cfg: SmallThinkerConfig, params, ids, precision: str = "float32"):
  """Final-RMSNorm hidden states [B, S, D] of token ids [B, S]."""
  if precision not in PRECISIONS:
    raise ValueError(f"precision {precision!r}")
  eps = cfg.rms_norm_eps
  x = params["embed"][ids].astype(jnp.float32)
  for windowed, rope, layer in zip(cfg.sliding_window_layout, cfg.rope_layout,
                                   params["layers"]):
    att, ff = layer["attention"], layer["ff"]
    window = cfg.sliding_window_size if (
        windowed and precision != "no_window") else None
    h = x + attention(cfg, rms_norm(x, att["norm_in"], eps), att,
                      bool(rope) or precision == "rope_everywhere", window,
                      precision)
    # The router reads the layer's input ``x``, the experts the normed
    # post-attention stream.
    x = h + moe(cfg, rms_norm(h, att["norm_ff"], eps), x, ff, precision)
  return rms_norm(x, params["norm_f"], eps)


def logits(cfg: SmallThinkerConfig, params, ids, precision=None):
  """[B, S, vocab] logits through the untied head."""
  precision = precision or "float32"
  return _matmul(hidden(cfg, params, ids, precision), params["lm_head"],
                 precision)
