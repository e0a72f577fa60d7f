"""GLM-5 (``model_type: glm_moe_dsa``) in plain ``jax.numpy``: the forward
pass of a sparse-expert decoder EVERY layer of which is a multi-head latent
attention that selects the rows it attends through a learned indexer, at
ONE HOST's share of the experts and of the vocabulary.

With ``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``, ``u`` a layer's normed
input, ``t`` a query's position and ``s`` a key's:

* block: ``h = x + Attn_l(RMS(x))``, ``y = h + FF_l(RMS(h))``; a final
  ``RMS``; an UNTIED head over the host's slice of the vocabulary;
* latent attention (DeepSeek-V3's), no biases: ``c_q = RMS(W_qa u)``;
  ``[q_nope | q_rope]_h = W_qb c_q``; ``[c_kv | k_r] = W_kva u``; ``c =
  RMS(c_kv)``; rotate-half rotary on ``q_rope`` and on the ONE ``k_r`` all
  heads share; ``[k_nope | v]_h = W_kvb c``; ``score_h(t, s) = (q_nope .
  k_nope + q_rope . k_r) / sqrt(nope + rope)``; softmax over the VISIBLE
  ``s``; ``o_h = sum p v``; ``W_o`` over the heads.  EXPANDED keys and
  values, a head at a time;
* visible = ``s`` in ``S_t``, the ``min(t + 1, index_topk)`` largest of
  ``I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s))`` over ``s <= t`` (a tie
  AT the k-th value keeps every row that ties, as a threshold does), with
  ``qI_j = WI_q c_q`` (``index_n_heads`` of ``index_head_dim``), ``kI =
  LayerNorm(WI_k u)`` (gain, bias, eps 1e-6), rotary on the leading
  ``qk_rope_head_dim`` of each, ``w = WI_w u``: the full ``[t, s]`` scores
  and an explicit top-k mask (a sort of each row);
* FF: a SiLU-gated MLP of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; elsewhere ``sc = sigmoid(W_r x)`` over
  ALL ``router_width`` experts, chosen = the ``num_experts_per_tok``
  largest of ``sc + b`` (``b`` in the choice only, one group), weights
  ``sc[chosen] / (sum + 1e-20) * routed_scaling_factor``, ``Shared(x) + sum
  over the chosen experts HELD of w_i E_i(x)``: ``experts_held = (first,
  count)`` names the router's experts whose terms are summed (the host's
  64 by default; any chip's 16, or an absent share, when handed in:
  :func:`logits`); what the others would add is left out (model-configs
  guide, section 4).  ``shared=False`` leaves the shared expert out as
  well, so that shares add up to a layer with the shared expert counted
  once.

float32 throughout, matmuls at ``highest`` precision, the whole sequence at
once, no cache, no kernels, no sorting of tokens.  It imports nothing of the
program under test.

Departures, each also under ``assumed`` in the configuration's file: rotary
pairs are (i, i + d/2) where the published code interleaves them (a
permutation of a projection's columns under seeded weights), the rotated
dims the TRAILING 64 of a query head and of the shared key and the LEADING
64 of an index head and of the index key; positive factors common to a
query's index scores change no choice and are left out, as are the
indexer's Hadamard rotation (orthogonal: the dot products are the same) and
its fp8 cast (a kernel's economy); the multi-token-prediction module takes
no part in next-token logits and is not built; weights are random from a
seed, the selection bias N(0, 0.02).

Weights are ROUNDED TO BFLOAT16 ONCE and held so; a layer's routed experts
are HELD AS THEIR KEY (:class:`HeldExperts`: 64 x 5 experts are 24 GB in
bfloat16) and each is drawn where it is used, expert ``e`` of the router's
from the layer's key and ``e`` alone, so a chip's experts do not depend on
which others it holds.  Weights are upcast where they are used: attention a
head at a time (``lax.map``: one ``[S, S]`` score matrix is live), the index
scores a head at a time into one ``[S, S]`` sum, the routed experts one at
a time (``lax.scan``).

``precision``: ``float32`` is the reference.  The controls show that the
check fails when the work is done in a lower precision: ``fp8`` /
``bfloat16`` / ``int8`` round the operands of every matmul (the router's
and the indexer's too); ``bf16router`` computes only the router's scores,
and ``bf16index`` only the index scores, from bfloat16 operands into a
bfloat16 result.  Two more controls plant a SELECTION fault in float32
arithmetic: ``recent`` attends the most recent ``index_topk`` rows (no
indexer), ``loose`` one block of 128 rows more than ``index_topk``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# Planted selection faults, float32 arithmetic.
_FAULTS = ("recent", "loose")
PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16router",
              "bf16index") + _FAULTS
_EXACT = ("float32", "bf16router", "bf16index") + _FAULTS
FAULT_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
  num_hidden_layers: int
  hidden_size: int
  intermediate_size: int
  moe_intermediate_size: int
  heads: int
  q_rank: int
  kv_rank: int
  nope: int
  rope: int
  value: int
  theta: float
  index_n_heads: int
  index_head_dim: int
  index_topk: int
  router_width: int              # the published n_routed_experts
  experts_first: int             # the first expert this host holds
  n_routed_experts: int          # how many it holds
  n_shared_experts: int
  num_experts_per_tok: int
  first_k_dense_replace: int
  vocab_size: int
  n_positions: int               # served context: the most a request holds
  routed_scaling_factor: float = 2.5
  norm_topk_prob: bool = True
  rms_norm_eps: float = 1e-5
  index_norm_eps: float = 1e-6
  initializer_range: float = 0.02
  bias_std: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "GlmMoeDsaConfig":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum; the
    router's width and the held experts from ``n_routed_experts_published``
    and ``assumed.experts_first`` beside ``n_routed_experts``."""
    assumed = doc.get("assumed", {})
    for key, want in (("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"),
                      ("attention_bias", False), ("moe_layer_freq", 1),
                      ("n_group", 1), ("topk_group", 1),
                      ("tie_word_embeddings", False)):
      if doc.get(key, want) != want:
        raise ValueError(f"this reference writes {key} = {want!r} only; "
                         f"the configuration says {doc[key]!r}")
    if doc["rope_parameters"].get("rope_type", "default") != "default":
      raise ValueError("this reference writes the default rotary only")
    return GlmMoeDsaConfig(
        num_hidden_layers=doc["num_hidden_layers"],
        hidden_size=doc["hidden_size"],
        intermediate_size=doc["intermediate_size"],
        moe_intermediate_size=doc["moe_intermediate_size"],
        heads=doc["num_attention_heads"], q_rank=doc["q_lora_rank"],
        kv_rank=doc["kv_lora_rank"], nope=doc["qk_nope_head_dim"],
        rope=doc["qk_rope_head_dim"], value=doc["v_head_dim"],
        theta=float(doc["rope_parameters"]["rope_theta"]),
        index_n_heads=doc["index_n_heads"],
        index_head_dim=doc["index_head_dim"], index_topk=doc["index_topk"],
        router_width=doc.get("n_routed_experts_published",
                             doc["n_routed_experts"]),
        experts_first=assumed.get("experts_first", 0),
        n_routed_experts=doc["n_routed_experts"],
        n_shared_experts=doc["n_shared_experts"],
        num_experts_per_tok=doc["num_experts_per_tok"],
        first_k_dense_replace=doc["first_k_dense_replace"],
        vocab_size=doc["vocab_size"],
        n_positions=assumed.get("served_context",
                                doc["max_position_embeddings"]),
        routed_scaling_factor=doc["routed_scaling_factor"],
        norm_topk_prob=doc["norm_topk_prob"],
        rms_norm_eps=doc["rms_norm_eps"],
        initializer_range=assumed.get("initializer_range", 0.02),
        bias_std=assumed.get("e_score_correction_bias_std", 0.02))

  @property
  def experts_held(self) -> tuple:
    return (self.experts_first, self.n_routed_experts)

  def is_dense(self, i: int) -> bool:
    return i < self.first_k_dense_replace

  def attention_params(self) -> dict:
    """Parameters of one layer's attention by part."""
    D = self.hidden_size
    Hi, di = self.index_n_heads, self.index_head_dim
    return {
        "mixer": (D * self.q_rank + self.q_rank
                  + self.q_rank * self.heads * (self.nope + self.rope)
                  + D * (self.kv_rank + self.rope) + self.kv_rank
                  + self.kv_rank * self.heads * (self.nope + self.value)
                  + self.heads * self.value * D),
        "indexer": self.q_rank * Hi * di + D * di + 2 * di + D * Hi}

  def expert_params(self) -> int:
    return 3 * self.hidden_size * self.moe_intermediate_size

  def param_count(self, experts_a_layer=None) -> int:
    """Parameters of the cut with ``experts_a_layer`` routed experts a
    layer (default: the host's) and its slice of the vocabulary."""
    D = self.hidden_size
    held = (self.n_routed_experts if experts_a_layer is None
            else experts_a_layer)
    total = 2 * self.vocab_size * D + D
    for i in range(self.num_hidden_layers):
      total += sum(self.attention_params().values()) + 2 * D
      if self.is_dense(i):
        total += 3 * D * self.intermediate_size
      else:
        total += (D * self.router_width + self.router_width
                  + (held + self.n_shared_experts) * self.expert_params())
    return total


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


def init_attention(cfg: GlmMoeDsaConfig, key) -> dict:
  """One layer's latent attention, its indexer and its two outer norms."""
  D, std = cfg.hidden_size, cfg.initializer_range
  Hi, di = cfg.index_n_heads, cfg.index_head_dim
  k = jax.random.split(key, 14)
  return {
      "norm_in": _gain(k[0], D, std), "norm_ff": _gain(k[1], D, std),
      "q_a": _normal(k[2], (D, cfg.q_rank), std),
      "q_norm": _gain(k[3], cfg.q_rank, std),
      "q_b": _normal(k[4], (cfg.q_rank, cfg.heads * (cfg.nope + cfg.rope)),
                     std),
      "kv_a": _normal(k[5], (D, cfg.kv_rank + cfg.rope), std),
      "kv_norm": _gain(k[6], cfg.kv_rank, std),
      "kv_b": _normal(k[7], (cfg.kv_rank,
                             cfg.heads * (cfg.nope + cfg.value)), std),
      "o": _normal(k[8], (cfg.heads * cfg.value, D), _residual_std(cfg)),
      "index_q": _normal(k[9], (cfg.q_rank, Hi * di), std),
      "index_k": _normal(k[10], (D, di), std),
      "index_k_gain": _gain(k[11], di, std),
      "index_k_bias": std * jax.random.normal(k[12], (di,), jnp.float32),
      "index_w": _normal(k[13], (D, Hi), std),
  }


def _init_mlp(cfg, key, width: int) -> dict:
  D, std = cfg.hidden_size, cfg.initializer_range
  k = jax.random.split(key, 3)
  return {"gate": _normal(k[0], (D, width), std),
          "up": _normal(k[1], (D, width), std),
          "down": _normal(k[2], (width, D), _residual_std(cfg))}


def init_dense_ff(cfg: GlmMoeDsaConfig, key) -> dict:
  return _init_mlp(cfg, key, cfg.intermediate_size)


def init_expert(cfg: GlmMoeDsaConfig, key, e) -> dict:
  """Expert ``e`` of the router's, from its layer's experts key and ``e``
  alone."""
  return _init_mlp(cfg, jax.random.fold_in(key, e), cfg.moe_intermediate_size)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HeldExperts:
  """A layer's routed experts as they are HELD: their key (its data, one
  array leaf of the tree) and the configuration that says their shapes
  (static); :meth:`expert` draws one of the router's."""
  key_data: jax.Array
  cfg: GlmMoeDsaConfig

  def tree_flatten(self):
    return (self.key_data,), self.cfg

  @classmethod
  def tree_unflatten(cls, cfg, children):
    return cls(children[0], cfg)

  def expert(self, e) -> dict:
    return init_expert(self.cfg, jax.random.wrap_key_data(self.key_data), e)

  def sum_of_squares(self, held=None):
    """Over every weight of the experts ``held = (first, count)`` (the
    host's by default), one drawn at a time."""
    first, count = held or self.cfg.experts_held
    sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                       for x in jax.tree_util.tree_leaves(t))
    total, _ = jax.lax.scan(
        lambda acc, e: (acc + sq(self.expert(e)), None), jnp.float32(0),
        first + jnp.arange(count))
    return total


def init_moe_ff(cfg: GlmMoeDsaConfig, key) -> dict:
  """An expert layer as it is HELD: the router over ALL ``router_width``
  experts (values rounded to bfloat16, as the checkpoint holds them), the
  float32 selection bias, the routed experts' key, the shared expert."""
  D, E = cfg.hidden_size, cfg.router_width
  k = jax.random.split(key, 4)
  return {
      "router": _normal(k[0], (D, E), cfg.initializer_range),
      "bias": cfg.bias_std * jax.random.normal(k[1], (E,), jnp.float32),
      "experts": HeldExperts(jax.random.key_data(k[2]), cfg),
      "shared": _init_mlp(
          cfg, k[3], cfg.n_shared_experts * cfg.moe_intermediate_size),
  }


def layer_keys(key, i: int):
  """``(attention key, feed-forward key)`` of layer ``i``: a layer's
  weights depend on the seed and its index alone."""
  k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
  return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def top_keys(key):
  """``(embedding key, head key, final norm key)``."""
  k = jax.random.fold_in(key, 0)
  return tuple(jax.random.fold_in(k, j) for j in range(3))


def init_embedding(cfg: GlmMoeDsaConfig, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_head(cfg: GlmMoeDsaConfig, key):
  return _normal(key, (cfg.hidden_size, cfg.vocab_size),
                 cfg.initializer_range)


def init_layer(cfg: GlmMoeDsaConfig, key, i: int) -> dict:
  k_att, k_ff = layer_keys(key, i)
  init_ff = init_dense_ff if cfg.is_dense(i) else init_moe_ff
  return {"att": init_attention(cfg, k_att), "ff": init_ff(cfg, k_ff)}


def init_params(cfg: GlmMoeDsaConfig, key) -> dict:
  """Seeded weights as they are held (module docstring), a list of
  layers."""
  k_embed, k_head, k_norm = top_keys(key)
  return {
      "embed": init_embedding(cfg, k_embed),
      "head": init_head(cfg, k_head),
      "norm_f": _gain(k_norm, cfg.hidden_size, cfg.initializer_range),
      "layers": [init_layer(cfg, key, i)
                 for i in range(cfg.num_hidden_layers)],
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


def layer_norm(x, g, b, eps):
  x = x - jnp.mean(x, -1, keepdims=True)
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                           + eps) * g + b


def silu(x):
  return x * jax.nn.sigmoid(x)


def rotary(x, theta: float):
  """Rotate-half rotary embedding of ``x`` [S, ..., d] over all ``d``
  dims: pair ``i`` is ``(x[i], x[i + d/2])``, turned by ``s * theta^(-2i /
  d)`` at position ``s``."""
  S, d = x.shape[0], x.shape[-1]
  inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # [S, d/2]
  ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
  a, b = x[..., :d // 2], x[..., d // 2:]
  return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                          b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _rotate_leading(x, theta: float, width: int):
  return jnp.concatenate([rotary(x[..., :width], theta), x[..., width:]], -1)


def index_scores(cfg: GlmMoeDsaConfig, u, c_q, p, precision: str):
  """``I(t, s)`` for every pair, ``[S, S]`` float32 (causality is the
  caller's): a head at a time into one sum."""
  S = u.shape[0]
  Hi, di, rope, theta = (cfg.index_n_heads, cfg.index_head_dim, cfg.rope,
                         cfg.theta)
  q = _rotate_leading(
      _matmul(c_q, p["index_q"], precision).reshape(S, Hi, di), theta, rope)
  k = _rotate_leading(
      layer_norm(_matmul(u, p["index_k"], precision), p["index_k_gain"],
                 p["index_k_bias"], cfg.index_norm_eps), theta, rope)
  w = _matmul(u, p["index_w"], precision)                     # [S, Hi]

  def add_head(acc, qw):
    q_j, w_j = qw                                             # [S, di], [S]
    if precision == "bf16index":
      dots = jnp.matmul(q_j.astype(_BF16), k.astype(_BF16).T).astype(
          jnp.float32)
    else:
      dots = _einsum("qd,kd->qk", q_j, k, precision)
    return acc + w_j[:, None] * jnp.maximum(dots, 0.0), None

  acc, _ = jax.lax.scan(add_head, jnp.zeros((S, S), jnp.float32),
                        (jnp.moveaxis(q, 1, 0), w.T))
  return acc


def selection(scores, top_k: int, fault=None):
  """The explicit top-k mask ``[S, S]``: ``s`` is in ``S_t`` iff ``s <= t``
  and ``I(t, s)`` is among the ``min(t + 1, top_k)`` largest of row ``t``
  (each row sorted; a row with fewer than ``top_k`` visible keeps all).
  ``fault``: one of the planted faults (module docstring), a control."""
  S = scores.shape[0]
  causal = jnp.tril(jnp.ones((S, S), bool))
  if fault == "recent":
    return causal & ~jnp.tril(jnp.ones((S, S), bool), -top_k)
  if fault == "loose":
    top_k += FAULT_BLOCK
  masked = jnp.where(causal, scores, -jnp.inf)
  if top_k >= S:
    return causal
  kth = jnp.sort(masked, axis=-1)[:, S - top_k]
  return causal & (masked >= kth[:, None])


def latent_attention(cfg: GlmMoeDsaConfig, u, p, precision: str):
  """One layer's attention on ``u`` [S, D], keys and values EXPANDED for
  every position, a head at a time."""
  S, _ = u.shape
  eps = cfg.rms_norm_eps
  c_q = rms_norm(_matmul(u, p["q_a"], precision), p["q_norm"], eps)
  kv = _matmul(u, p["kv_a"], precision)
  c = rms_norm(kv[:, :cfg.kv_rank], p["kv_norm"], eps)
  k_r = rotary(kv[:, cfg.kv_rank:], cfg.theta)                # [S, rope]
  visible = selection(index_scores(cfg, u, c_q, p, precision),
                      cfg.index_topk,
                      precision if precision in _FAULTS else None)
  w_qb = p["q_b"].reshape(cfg.q_rank, cfg.heads, cfg.nope + cfg.rope)
  w_kvb = p["kv_b"].reshape(cfg.kv_rank, cfg.heads, cfg.nope + cfg.value)

  def head(ws):
    w_q, w_kv = ws
    q = _matmul(c_q, w_q, precision)
    q = jnp.concatenate([q[:, :cfg.nope],
                         rotary(q[:, cfg.nope:], cfg.theta)], -1)
    kvh = _matmul(c, w_kv, precision)
    k = jnp.concatenate([kvh[:, :cfg.nope], k_r], -1)
    scores = _einsum("qd,kd->qk", q, k, precision) / np.sqrt(
        cfg.nope + cfg.rope)
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return _einsum("qk,dk->qd", probs, kvh[:, cfg.nope:].T, precision)

  ctx = jax.lax.map(head, (jnp.moveaxis(w_qb, 1, 0),
                           jnp.moveaxis(w_kvb, 1, 0)))
  ctx = jnp.moveaxis(ctx, 0, 1).reshape(S, cfg.heads * cfg.value)
  return _matmul(ctx, p["o"], precision)


def mlp(h, p, precision: str):
  return _matmul(silu(_matmul(h, p["gate"], precision))
                 * _matmul(h, p["up"], precision), p["down"], precision)


def route(cfg: GlmMoeDsaConfig, h, router, bias, precision: str):
  """``(chosen [S, k], weights [S, k])`` of the ``noaux_tc`` router over
  all ``router_width`` experts."""
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


def routed(cfg: GlmMoeDsaConfig, h, p, precision: str, held=None):
  """``sum over the chosen experts among ``held = (first, count)`` of w_i
  Expert_i(h)``: each of them drawn and applied to every token, one at a
  time, weighted by its ``w`` where chosen and 0 elsewhere."""
  first, count = held or cfg.experts_held
  chosen, w = route(cfg, h, p["router"], p["bias"], precision)
  weight_of = jnp.sum(
      jax.nn.one_hot(chosen, cfg.router_width, dtype=jnp.float32)
      * w[..., None], -2)

  def add_expert(acc, e):
    w_e = jax.lax.dynamic_index_in_dim(weight_of, e, -1, keepdims=True)
    return acc + w_e * mlp(h, p["experts"].expert(e), precision), None

  out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        first + jnp.arange(count))
  return out


def moe(cfg: GlmMoeDsaConfig, h, p, precision: str, held=None,
        shared: bool = True):
  out = routed(cfg, h, p, precision, held)
  return out + mlp(h, p["shared"], precision) if shared else out


def hidden(cfg: GlmMoeDsaConfig, params, ids, precision: str = "float32",
           experts_held=None):
  """Final-RMSNorm hidden states [B, S, D] of token ids [B, S], a
  sequence at a time."""
  if precision not in PRECISIONS:
    raise ValueError(f"precision {precision!r}")
  eps = cfg.rms_norm_eps

  def one(seq):
    x = params["embed"][seq].astype(jnp.float32)
    for i, layer in enumerate(params["layers"]):
      att, ff = layer["att"], layer["ff"]
      x = x + latent_attention(cfg, rms_norm(x, att["norm_in"], eps), att,
                               precision)
      h = rms_norm(x, att["norm_ff"], eps)
      x = x + (mlp(h, ff, precision) if cfg.is_dense(i)
               else moe(cfg, h, ff, precision, experts_held))
    return rms_norm(x, params["norm_f"], eps)

  return jax.lax.map(one, ids)


def logits(cfg: GlmMoeDsaConfig, params, ids, precision=None,
           experts_held=None):
  """[B, S, vocab] logits through the untied head; ``experts_held =
  (first, count)`` sums those experts' terms in place of the host's."""
  precision = precision or "float32"
  return _matmul(hidden(cfg, params, ids, precision, experts_held),
                 params["head"], precision)
