"""GigaChat3.5-432B-A28B (``model_type: gigachat3_5``) in plain
``jax.numpy``: the forward pass of a decoder three layers in four of which
mix by the gated delta rule (a float32 matrix of state a head) and one in
four by a gated multi-head latent attention, at ONE CHIP's share of the
experts and of the vocabulary.

With ``N(x; w) = x * rsqrt(mean(x^2) + eps) * 2 sigmoid(w)`` (the model's
``ZeroCenteredGatedNorm``, ``layernorm_gating_weight`` 2), ``t`` a query's
position and ``s`` a key's:

* block (``layernorm_type: pre_post``): ``h = x + N(Mixer_l(N(x)))``, ``y =
  h + N(FF_l(N(h)))``, four norms a layer; a final ``N``; an UNTIED head
  over the chip's slice of the vocabulary;
* linear layer (``l`` not in ``full_attention_layers``), the gated delta
  rule (Yang, Kautz, Hatamizadeh 2024) in the form Qwen3-Next's public
  modelling code ships, for a position's normed input ``u``:
  ``[q | k | v | z] = W_qkvz u`` (``Hk dk``, ``Hk dk``, ``Hv dv``, ``Hv
  dv``); ``[b | a] = W_ba u`` (``Hv`` each); ``(q, k, v) <- silu(conv(q | k
  | v))``, depthwise, causal, ``K`` taps, no bias; a head: ``q <- q /
  sqrt(sum q^2 + 1e-6) / sqrt(dk)``, ``k <- k / sqrt(sum k^2 + 1e-6)``,
  value head ``h`` reading key head ``h // (Hv / Hk)``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; POSITION BY
  POSITION, ``S`` ``[dk, dv]`` from zero: ``S <- exp(g) S``; ``u' = beta (v
  - S^T k)``; ``S <- S + k u'^T``; ``o = S^T q``; then a head ``y = o *
  rsqrt(mean(o^2) + eps_o) * (1 + w_o) * 2 sigmoid(z)``
  (``gated_rmsnorm_sigmoid_zero_centered``, ``linear_sigmoid_gate_scale``
  2) and ``W_o`` over the heads;
* full layer: DeepSeek-V3's latent attention, no biases: ``c_q =
  RMS(W_qa u)``; ``[q_nope | q_rope]_h = W_qb c_q``; ``[c_kv | k_r] = W_kva
  u``; ``c = RMS(c_kv)`` (``RMS``: gain ``w``, the attention's own);
  rotate-half rotary with YaRN's frequencies on ``q_rope`` and on the ONE
  ``k_r`` all heads share; ``[k_nope | v]_h = W_kvb c``; ``score_h(t, s) =
  (q_nope . k_nope + q_rope . k_r) m^2 / sqrt(nope + rope)``, ``m = 0.1
  mscale_all_dim ln(factor) + 1`` (``use_mla_scaling_factor``); causal
  softmax; ``o_h = sum p v``; an ELEMENTWISE gate ``o <- o sigmoid(W_g u)``
  (``gated_attention``, ``W_g`` ``[D, H dv]``); ``W_o`` over the heads.
  EXPANDED keys and values, a head at a time;
* YaRN (``rope_scaling``): pair ``i`` of ``d / 2`` turns by ``position *
  f_i``, ``f_i = theta^(-2i/d) ((1 - r_i) / factor + r_i)``, ``r_i = 1 -
  clip((i - low) / (high - low), 0, 1)``, ``low`` / ``high`` the floor /
  ceiling of ``d ln(L / (2 pi beta)) / (2 ln theta)`` at ``beta_fast`` /
  ``beta_slow``, ``L`` the original 32768; cos and sin times ``mscale(
  factor, mscale) / mscale(factor, mscale_all_dim)`` (1 here);
* FF: a SiLU-gated MLP of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; elsewhere ``sc = sigmoid(W_r x)`` over
  ALL ``router_width`` experts, chosen = the ``num_experts_per_tok``
  largest of ``sc + b`` (``b`` in the choice only, one group), weights
  ``sc[chosen] / (sum + 1e-20) * routed_scaling_factor``, ``Shared(x) + sum
  over the chosen experts HELD of w_i E_i(x)``: ``experts_held = (first,
  count)`` names the router's experts whose terms are summed (the chip's
  16 by default; any other share when handed in: :func:`logits`); what the
  others would add is left out (model-configs guide, section 4).
  ``shared=False`` leaves the shared expert out as well, so that shares add
  up to a layer with the shared expert counted once.  EVERY gated MLP
  (dense, shared, routed) holds its gate's pre-activation to at most
  ``swiglu_limit`` and its up projection within ``+-swiglu_limit`` before
  the product.

float32 throughout, matmuls at ``highest`` precision, the whole sequence at
once, no cache, no kernels, no chunked form, no sorting of tokens.  It
imports nothing of the program under test.

ASSUMED, each also under ``assumed`` in the configuration's file with the
other reading: the forms of ``gated_norm``, of the model's norm, of the
attention's gate, of the clamp and of the softmax factor above, which the
config names and does not define; the router's scoring (DeepSeek-V3's
``noaux_tc``).  Departures: rotary pairs are (i, i + d/2) where the
published code interleaves them (a permutation of a projection's columns
under seeded weights); the two multi-token-prediction layers take no part
in next-token logits and are not built; weights are random from a seed,
the selection bias N(0, 0.02).

Weights are ROUNDED TO BFLOAT16 ONCE and held so; a layer's routed experts
are HELD AS THEIR KEY (:class:`HeldExperts`) and each is drawn where it is
used, expert ``e`` of the router's from the layer's key and ``e`` alone, so
a chip's experts do not depend on which others it holds.  Weights are
upcast where they are used: attention a head at a time (``lax.map``), the
routed experts one at a time (``lax.scan``).

``precision``: ``float32`` is the reference.  The controls show that the
check fails when the work is done in a lower precision: ``fp8`` /
``bfloat16`` / ``int8`` round the operands of every matmul (the router's
too); ``bf16router`` computes only the router's scores from bfloat16
operands into a bfloat16 result; ``bf16state`` keeps the delta rule's
state ``S`` in bfloat16 (rounded after every position's update), all else
float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16router",
              "bf16state")
_EXACT = ("float32", "bf16router", "bf16state")
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GigaChat35Config:
  num_hidden_layers: int
  full_attention_layers: tuple
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
  yarn_factor: float
  yarn_original: int
  yarn_beta_fast: float
  yarn_beta_slow: float
  yarn_mscale: float
  yarn_mscale_all_dim: float
  mla_scaling_factor: bool
  linear_key_heads: int
  linear_value_heads: int
  linear_key_dim: int
  linear_value_dim: int
  conv_kernel: int
  gate_scale: float              # linear_sigmoid_gate_scale
  o_norm_eps: float              # linear_attn_o_norm_eps
  norm_gating_weight: float      # layernorm_gating_weight
  swiglu_limit: float
  router_width: int              # the published n_routed_experts
  experts_first: int             # the first expert this chip holds
  n_routed_experts: int          # how many it holds
  n_shared_experts: int
  num_experts_per_tok: int
  first_k_dense_replace: int
  vocab_size: int
  n_positions: int               # served context: the most a request holds
  routed_scaling_factor: float = 2.5
  norm_topk_prob: bool = True
  rms_norm_eps: float = 1e-6
  initializer_range: float = 0.02
  bias_std: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "GigaChat35Config":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum; the
    router's width and the held experts from ``n_routed_experts_published``
    and ``assumed.experts_first`` beside ``n_routed_experts``."""
    assumed = doc.get("assumed", {})
    for key, want in (
        ("hidden_act", "silu"), ("attention_bias", False), ("n_group", 1),
        ("topk_group", 1), ("tie_word_embeddings", False),
        ("norm_type", "ZeroCenteredGatedNorm"),
        ("layernorm_type", "pre_post"), ("gated_attention", True),
        ("use_shared_expert_sigmoid", False),
        ("linear_attention_type", "GigaChat35GatedDeltaNet"),
        ("linear_gating_type", "gated_rmsnorm_sigmoid_zero_centered")):
      if doc.get(key, want) != want:
        raise ValueError(f"this reference writes {key} = {want!r} only; "
                         f"the configuration says {doc[key]!r}")
    yarn = doc["rope_scaling"]
    if yarn.get("type") != "yarn":
      raise ValueError("this reference writes YaRN's rotary only")
    return GigaChat35Config(
        num_hidden_layers=doc["num_hidden_layers"],
        full_attention_layers=tuple(doc["full_attention_layers"]),
        hidden_size=doc["hidden_size"],
        intermediate_size=doc["intermediate_size"],
        moe_intermediate_size=doc["moe_intermediate_size"],
        heads=doc["num_attention_heads"], q_rank=doc["q_lora_rank"],
        kv_rank=doc["kv_lora_rank"], nope=doc["qk_nope_head_dim"],
        rope=doc["qk_rope_head_dim"], value=doc["v_head_dim"],
        theta=float(doc["rope_theta"]), yarn_factor=float(yarn["factor"]),
        yarn_original=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        mla_scaling_factor=doc["use_mla_scaling_factor"],
        linear_key_heads=doc["linear_num_key_heads"],
        linear_value_heads=doc["linear_num_value_heads"],
        linear_key_dim=doc["linear_key_head_dim"],
        linear_value_dim=doc["linear_value_head_dim"],
        conv_kernel=doc["linear_conv_kernel_dim"],
        gate_scale=float(doc["linear_sigmoid_gate_scale"]),
        o_norm_eps=doc["linear_attn_o_norm_eps"],
        norm_gating_weight=float(doc["layernorm_gating_weight"]),
        swiglu_limit=float(doc["swiglu_limit"]),
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

  @property
  def conv_dim(self) -> int:
    return (2 * self.linear_key_heads * self.linear_key_dim
            + self.linear_value_heads * self.linear_value_dim)

  @property
  def value_dim(self) -> int:
    return self.linear_value_heads * self.linear_value_dim

  def is_dense(self, i: int) -> bool:
    return i < self.first_k_dense_replace

  def is_full(self, i: int) -> bool:
    return i in self.full_attention_layers

  def linear_params(self) -> int:
    """Parameters of one linear-attention mixer."""
    D, Hv = self.hidden_size, self.linear_value_heads
    return (D * (self.conv_dim + self.value_dim) + D * 2 * Hv
            + self.conv_kernel * self.conv_dim + 2 * Hv
            + self.linear_value_dim + self.value_dim * D)

  def latent_params(self) -> dict:
    """Parameters of one latent-attention mixer by part."""
    D = self.hidden_size
    return {
        "mixer": (D * self.q_rank + self.q_rank
                  + self.q_rank * self.heads * (self.nope + self.rope)
                  + D * (self.kv_rank + self.rope) + self.kv_rank
                  + self.kv_rank * self.heads * (self.nope + self.value)
                  + self.heads * self.value * D),
        "gate": D * self.heads * self.value}

  def expert_params(self) -> int:
    return 3 * self.hidden_size * self.moe_intermediate_size

  def layer_params(self, i: int, experts_a_layer=None) -> int:
    """Parameters of layer ``i`` with ``experts_a_layer`` routed experts
    (default: the chip's), its four norms among them."""
    D = self.hidden_size
    held = (self.n_routed_experts if experts_a_layer is None
            else experts_a_layer)
    total = 4 * D + (sum(self.latent_params().values()) if self.is_full(i)
                     else self.linear_params())
    if self.is_dense(i):
      return total + 3 * D * self.intermediate_size
    return total + (D * self.router_width + self.router_width
                    + (held + self.n_shared_experts) * self.expert_params())

  def param_count(self, experts_a_layer=None) -> int:
    """Parameters of the cut with ``experts_a_layer`` routed experts a
    layer (default: the chip's) and its slice of the vocabulary."""
    D = self.hidden_size
    return 2 * self.vocab_size * D + D + sum(
        self.layer_params(i, experts_a_layer)
        for i in range(self.num_hidden_layers))


def seed_key(seed: int, stream: int = 0):
  """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
  words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
  return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# ------------------------------------------------------------- weights --

_BF16 = jnp.bfloat16


def _normal(key, shape, std):
  """N(0, std), rounded to bfloat16 once."""
  return (std * jax.random.normal(key, shape, jnp.float32)).astype(_BF16)


def _small(key, n, std):
  """A float32 vector drawn near zero: a norm's ``w`` (its gain ``2
  sigmoid(w)`` or ``1 + w`` is then near one, and a dropped or misplaced
  one shows in the comparison)."""
  return std * jax.random.normal(key, (n,), jnp.float32)


def _gain(key, n, std):
  """A plain RMSNorm's gain, drawn near one, float32."""
  return 1.0 + _small(key, n, std)


def _residual_std(cfg) -> float:
  return cfg.initializer_range / np.sqrt(2.0 * cfg.num_hidden_layers)


def init_norms(cfg: GigaChat35Config, key) -> dict:
  """A layer's four outer norms."""
  k = jax.random.split(key, 4)
  names = ("norm_in", "norm_mix_out", "norm_ff", "norm_ff_out")
  return {n: _small(k[j], cfg.hidden_size, cfg.initializer_range)
          for j, n in enumerate(names)}


def init_linear(cfg: GigaChat35Config, key) -> dict:
  """One layer's gated delta-rule mixer: the convolution's taps N(0,
  K^-1/2) (tap ``K - 1`` on the current token); the decay rate ``exp(
  A_log)`` uniform in (0, 16) and ``dt_bias`` the inverse softplus of a
  step log-uniform in [1e-3, 1e-1], as Qwen3-Next initialises them."""
  D, std = cfg.hidden_size, cfg.initializer_range
  Hv, K = cfg.linear_value_heads, cfg.conv_kernel
  k = jax.random.split(key, 7)
  dt = jnp.exp(jax.random.uniform(k[4], (Hv,), jnp.float32)
               * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
  return {
      "in_proj": _normal(k[0], (D, cfg.conv_dim + cfg.value_dim), std),
      "ba": _normal(k[1], (D, 2 * Hv), std),
      "conv": _normal(k[2], (K, cfg.conv_dim), K ** -0.5),
      "A_log": jnp.log(jax.random.uniform(k[3], (Hv,), jnp.float32, 1e-3,
                                          16.0)),
      "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
      "o_norm": _small(k[5], cfg.linear_value_dim, std),
      "o": _normal(k[6], (cfg.value_dim, D), _residual_std(cfg)),
  }


def init_latent(cfg: GigaChat35Config, key) -> dict:
  """One layer's latent attention and its elementwise gate."""
  D, std = cfg.hidden_size, cfg.initializer_range
  k = jax.random.split(key, 8)
  return {
      "q_a": _normal(k[0], (D, cfg.q_rank), std),
      "q_norm": _gain(k[1], cfg.q_rank, std),
      "q_b": _normal(k[2], (cfg.q_rank, cfg.heads * (cfg.nope + cfg.rope)),
                     std),
      "kv_a": _normal(k[3], (D, cfg.kv_rank + cfg.rope), std),
      "kv_norm": _gain(k[4], cfg.kv_rank, std),
      "kv_b": _normal(k[5], (cfg.kv_rank,
                             cfg.heads * (cfg.nope + cfg.value)), std),
      "o": _normal(k[6], (cfg.heads * cfg.value, D), _residual_std(cfg)),
      "gate": _normal(k[7], (D, cfg.heads * cfg.value), std),
  }


def init_mixer(cfg: GigaChat35Config, key, i: int) -> dict:
  """Layer ``i``'s mixer and its four outer norms."""
  k_norms, k_mix = jax.random.split(key)
  init = init_latent if cfg.is_full(i) else init_linear
  return {**init_norms(cfg, k_norms), **init(cfg, k_mix)}


def _init_mlp(cfg, key, width: int) -> dict:
  D, std = cfg.hidden_size, cfg.initializer_range
  k = jax.random.split(key, 3)
  return {"gate": _normal(k[0], (D, width), std),
          "up": _normal(k[1], (D, width), std),
          "down": _normal(k[2], (width, D), _residual_std(cfg))}


def init_dense_ff(cfg: GigaChat35Config, key) -> dict:
  return _init_mlp(cfg, key, cfg.intermediate_size)


def init_expert(cfg: GigaChat35Config, key, e) -> dict:
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
  cfg: GigaChat35Config

  def tree_flatten(self):
    return (self.key_data,), self.cfg

  @classmethod
  def tree_unflatten(cls, cfg, children):
    return cls(children[0], cfg)

  def expert(self, e) -> dict:
    return init_expert(self.cfg, jax.random.wrap_key_data(self.key_data), e)

  def sum_of_squares(self, held=None):
    """Over every weight of the experts ``held = (first, count)`` (the
    chip's by default), one drawn at a time."""
    first, count = held or self.cfg.experts_held
    sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                       for x in jax.tree_util.tree_leaves(t))
    total, _ = jax.lax.scan(
        lambda acc, e: (acc + sq(self.expert(e)), None), jnp.float32(0),
        first + jnp.arange(count))
    return total


def init_moe_ff(cfg: GigaChat35Config, key) -> dict:
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
  """``(mixer key, feed-forward key)`` of layer ``i``: a layer's weights
  depend on the seed and its index alone."""
  k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
  return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def top_keys(key):
  """``(embedding key, head key, final norm key)``."""
  k = jax.random.fold_in(key, 0)
  return tuple(jax.random.fold_in(k, j) for j in range(3))


def init_embedding(cfg: GigaChat35Config, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_head(cfg: GigaChat35Config, key):
  return _normal(key, (cfg.hidden_size, cfg.vocab_size),
                 cfg.initializer_range)


def init_final_norm(cfg: GigaChat35Config, key):
  return _small(key, cfg.hidden_size, cfg.initializer_range)


def init_layer(cfg: GigaChat35Config, key, i: int) -> dict:
  k_mix, k_ff = layer_keys(key, i)
  init_ff = init_dense_ff if cfg.is_dense(i) else init_moe_ff
  return {"mix": init_mixer(cfg, k_mix, i), "ff": init_ff(cfg, k_ff)}


def init_params(cfg: GigaChat35Config, key) -> dict:
  """Seeded weights as they are held (module docstring), a list of
  layers."""
  k_embed, k_head, k_norm = top_keys(key)
  return {
      "embed": init_embedding(cfg, k_embed),
      "head": init_head(cfg, k_head),
      "norm_f": init_final_norm(cfg, k_norm),
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


def model_norm(cfg: GigaChat35Config, x, w):
  """The model's norm: RMS with gain ``layernorm_gating_weight
  sigmoid(w)``."""
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), -1, keepdims=True) + cfg.rms_norm_eps) * (
          cfg.norm_gating_weight * jax.nn.sigmoid(w))


def rms_norm(x, g, eps):
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                           + eps) * g


def silu(x):
  return x * jax.nn.sigmoid(x)


def _mscale(factor: float, mscale: float) -> float:
  return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def yarn_frequencies(cfg: GigaChat35Config, d: int) -> np.ndarray:
  """The ``d / 2`` pair frequencies (module docstring), float32."""
  i = np.arange(d // 2, dtype=np.float64)
  plain = cfg.theta ** (-2.0 * i / d)
  dim = lambda beta: d * np.log(cfg.yarn_original / (beta * 2 * np.pi)) / (
      2 * np.log(cfg.theta))
  low = max(np.floor(dim(cfg.yarn_beta_fast)), 0)
  high = min(np.ceil(dim(cfg.yarn_beta_slow)), d - 1)
  if low == high:
    high += 0.001
  r = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
  return (plain * ((1.0 - r) / cfg.yarn_factor + r)).astype(np.float32)


def rotary(cfg: GigaChat35Config, x):
  """Rotate-half rotary embedding of ``x`` [S, ..., d] over all ``d``
  dims with YaRN's frequencies: pair ``i`` is ``(x[i], x[i + d/2])``."""
  S, d = x.shape[0], x.shape[-1]
  ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg, d)
  ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
  amp = (_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / _mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
  cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
  a, b = x[..., :d // 2], x[..., d // 2:]
  return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def softmax_scale(cfg: GigaChat35Config) -> float:
  scale = 1.0 / np.sqrt(cfg.nope + cfg.rope)
  if cfg.mla_scaling_factor and cfg.yarn_mscale_all_dim:
    scale *= _mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
  return float(scale)


def latent_attention(cfg: GigaChat35Config, u, p, precision: str):
  """One full layer's attention on ``u`` [S, D], keys and values EXPANDED
  for every position, a head at a time."""
  S, _ = u.shape
  eps = cfg.rms_norm_eps
  c_q = rms_norm(_matmul(u, p["q_a"], precision), p["q_norm"], eps)
  kv = _matmul(u, p["kv_a"], precision)
  c = rms_norm(kv[:, :cfg.kv_rank], p["kv_norm"], eps)
  k_r = rotary(cfg, kv[:, cfg.kv_rank:])                      # [S, rope]
  causal = jnp.tril(jnp.ones((S, S), bool))
  w_qb = p["q_b"].reshape(cfg.q_rank, cfg.heads, cfg.nope + cfg.rope)
  w_kvb = p["kv_b"].reshape(cfg.kv_rank, cfg.heads, cfg.nope + cfg.value)
  scale = softmax_scale(cfg)

  def head(ws):
    w_q, w_kv = ws
    q = _matmul(c_q, w_q, precision)
    q = jnp.concatenate([q[:, :cfg.nope], rotary(cfg, q[:, cfg.nope:])], -1)
    kvh = _matmul(c, w_kv, precision)
    k = jnp.concatenate([kvh[:, :cfg.nope], k_r], -1)
    scores = _einsum("qd,kd->qk", q, k, precision) * scale
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return _einsum("qk,dk->qd", probs, kvh[:, cfg.nope:].T, precision)

  ctx = jax.lax.map(head, (jnp.moveaxis(w_qb, 1, 0),
                           jnp.moveaxis(w_kvb, 1, 0)))
  ctx = jnp.moveaxis(ctx, 0, 1).reshape(S, cfg.heads * cfg.value)
  ctx = ctx * jax.nn.sigmoid(_matmul(u, p["gate"], precision))
  return _matmul(ctx, p["o"], precision)


def _l2norm(x):
  return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta, low_state: bool = False):
  """The recurrence position by position from zero state: ``q``, ``k``
  ``[S, Hv, dk]`` (normed), ``v`` ``[S, Hv, dv]``, ``g``, ``beta`` ``[S,
  Hv]``; ``[S, Hv, dv]``.  ``low_state`` rounds the state to bfloat16 after
  every position (a control)."""
  hi = jax.lax.Precision.HIGHEST

  def step(S, xs):
    q_t, k_t, v_t, g_t, b_t = xs
    S = S * jnp.exp(g_t)[:, None, None]
    u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t, precision=hi))
    S = S + k_t[:, :, None] * u[:, None, :]
    if low_state:
      S = S.astype(_BF16).astype(jnp.float32)
    return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=hi)

  Hv, dk, dv = q.shape[1], q.shape[2], v.shape[2]
  _, out = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
  return out


def linear_attention(cfg: GigaChat35Config, u, p, precision: str):
  """One linear layer's mixer on ``u`` [S, D]."""
  S, _ = u.shape
  Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
  dk, dv, K = cfg.linear_key_dim, cfg.linear_value_dim, cfg.conv_kernel
  qkvz = _matmul(u, p["in_proj"], precision)
  ba = _matmul(u, p["ba"], precision)
  qkv, z = qkvz[:, :cfg.conv_dim], qkvz[:, cfg.conv_dim:]
  taps = p["conv"].astype(jnp.float32)
  padded = jnp.concatenate([jnp.zeros((K - 1, cfg.conv_dim)), qkv], 0)
  qkv = silu(sum(padded[j:j + S] * taps[j] for j in range(K)))
  q, k, v = jnp.split(qkv, [Hk * dk, 2 * Hk * dk], axis=-1)
  heads = lambda x: jnp.repeat(x.reshape(S, Hk, dk), Hv // Hk, axis=1)
  q, k = _l2norm(heads(q)) / np.sqrt(dk), _l2norm(heads(k))
  beta = jax.nn.sigmoid(ba[:, :Hv])
  g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, Hv:] + p["dt_bias"])
  o = delta_rule(q, k, v.reshape(S, Hv, dv), g, beta,
                 precision == "bf16state")
  y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                        + cfg.o_norm_eps) * (1.0 + p["o_norm"])
  y = y * (cfg.gate_scale * jax.nn.sigmoid(z.reshape(S, Hv, dv)))
  return _matmul(y.reshape(S, Hv * dv), p["o"], precision)


def mlp(cfg: GigaChat35Config, h, p, precision: str):
  gate = jnp.minimum(_matmul(h, p["gate"], precision), cfg.swiglu_limit)
  up = jnp.clip(_matmul(h, p["up"], precision), -cfg.swiglu_limit,
                cfg.swiglu_limit)
  return _matmul(silu(gate) * up, p["down"], precision)


def route(cfg: GigaChat35Config, h, router, bias, precision: str):
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


def routed(cfg: GigaChat35Config, h, p, precision: str, held=None):
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
    return acc + w_e * mlp(cfg, h, p["experts"].expert(e), precision), None

  out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        first + jnp.arange(count))
  return out


def moe(cfg: GigaChat35Config, h, p, precision: str = "float32", held=None,
        shared: bool = True):
  out = routed(cfg, h, p, precision, held)
  return out + mlp(cfg, h, p["shared"], precision) if shared else out


def hidden(cfg: GigaChat35Config, params, ids, precision: str = "float32",
           experts_held=None):
  """Final-norm hidden states [B, S, D] of token ids [B, S], a sequence
  at a time."""
  if precision not in PRECISIONS:
    raise ValueError(f"precision {precision!r}")
  norm = lambda x, w: model_norm(cfg, x, w)

  def one(seq):
    x = params["embed"][seq].astype(jnp.float32)
    for i, layer in enumerate(params["layers"]):
      mix, ff = layer["mix"], layer["ff"]
      mixer = latent_attention if cfg.is_full(i) else linear_attention
      x = x + norm(mixer(cfg, norm(x, mix["norm_in"]), mix, precision),
                   mix["norm_mix_out"])
      h = norm(x, mix["norm_ff"])
      x = x + norm(mlp(cfg, h, ff, precision) if cfg.is_dense(i)
                   else moe(cfg, h, ff, precision, experts_held),
                   mix["norm_ff_out"])
    return norm(x, params["norm_f"])

  return jax.lax.map(one, ids)


def logits(cfg: GigaChat35Config, params, ids, precision=None,
           experts_held=None):
  """[B, S, vocab] logits through the untied head; ``experts_held =
  (first, count)`` sums those experts' terms in place of the chip's."""
  precision = precision or "float32"
  return _matmul(hidden(cfg, params, ids, precision, experts_held),
                 params["head"], precision)
