"""dots3-note-prev (``model_type: dots3_note``) in plain ``jax.numpy``: the
forward pass of a sparse-expert decoder whose full layers select the rows
they attend through a learned indexer and whose other layers attend behind
a window, both by multi-head latent attention, at ONE chip's share of the
experts and of the vocabulary.

With ``RMS(x) = x * rsqrt(mean(x^2) + eps) * g``, ``u`` a layer's normed
input, ``t`` a query's position and ``s`` a key's:

* block: ``h = x + Attn_l(RMS(x))``, ``y = h + FF_l(RMS(h))``; a final
  ``RMS``; an UNTIED head over the chip's slice of the vocabulary;
* latent attention (DeepSeek-V3's), no biases, at the layer type's own
  sizes: ``c_q = a_q RMS(W_qa u)``; ``[q_nope | q_rope]_h = W_qb c_q``;
  ``[c_kv | k_r] = W_kva u``; ``c = a_kv RMS(c_kv)``; rotate-half rotary on
  ``q_rope`` and on the ONE ``k_r`` all heads share; ``[k_nope | v]_h =
  W_kvb c``; ``score_h(t, s) = (q_nope . k_nope + q_rope . k_r) /
  sqrt(nope + rope)``; softmax over the VISIBLE ``s``; ``o_h = sum p v``;
  ``a_q = sqrt(hidden / q_rank)``, ``a_kv = sqrt(hidden / kv_rank)``
  (``apply_mla_qkv_lora_rescale``); headwise gate ``o_h <- sigmoid(W_g
  u)_h o_h`` before ``W_o``.  EXPANDED keys and values, a head at a time;
* a FULL layer (``full_attention``): 128 heads of 128 | 64 | 128 on ranks
  1024 | 512, theta 8e7; visible = ``s`` in ``S_t``, the ``min(t + 1,
  index_topk)`` largest of ``I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s))``
  over ``s <= t``, with ``qI_j = WI_q c_q`` (``index_n_heads`` of
  ``index_head_dim``), ``kI = LayerNorm(WI_k u)`` (gain, bias, eps 1e-6),
  rotary on the leading ``qk_rope_head_dim`` of each, ``w = WI_w u``: the
  full ``[t, s]`` scores and an explicit top-k mask (a sort of each row);
* a WINDOW layer (``sliding_attention``): the ``swa_*`` sizes, theta 5e4;
  visible = ``0 <= t - s < sliding_window_size``; no indexer;
* FF: a SiLU-gated MLP of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; elsewhere ``sc = sigmoid(W_r x)`` over
  ALL ``router_width`` experts, chosen = the ``num_experts_per_tok``
  largest of ``sc + b`` (``b`` in the choice only), weights ``sc[chosen] /
  (sum + 1e-20) * routed_scaling_factor``, ``Shared(x) + sum over the
  chosen experts HELD here of w_i E_i(x)``: the chip holds experts
  ``[experts_first, experts_first + n_routed_experts)`` of the router's
  and computes their part of the sum; what the absent ones would add is
  left out (model-configs guide, section 4).

float32 throughout, matmuls at ``highest`` precision, the whole sequence at
once, no cache, no kernels, no sorting of tokens.  It imports nothing of the
program under test.

Departures, each also under ``assumed`` in the configuration's file: the
rescale, the gate's form and the window's count of the query's own position
are readings no key of the config settles; rotary pairs are (i, i + d/2);
positive factors common to a query's index scores (the published code's
``64^-1/2`` and ``128^-1/2``) change no choice and are left out, as are its
Hadamard rotation of ``qI`` and ``kI`` (orthogonal: the dot products are
the same) and its fp8 cast (a kernel's economy); the vision and audio
towers and the prediction module take no part in next-token logits from
token ids and are not built; weights are random from a seed, a full
layer's ``q_b`` and ``kv_b`` narrower than the rest (:func:`init_attention`
says why).

Weights are ROUNDED TO BFLOAT16 ONCE and held so (8.17 GB for the cut);
they are upcast where they are used: attention a head at a time
(``lax.map``: one ``[S, S]`` score matrix is live, and one head's slices of
``W_qb`` and ``W_kvb``), the index scores a head at a time into one ``[S,
S]`` sum, the routed experts one at a time (``lax.scan``).

``precision``: ``float32`` is the reference.  The controls show that the
check fails when the work is done in a lower precision: ``fp8`` /
``bfloat16`` / ``int8`` round the operands of every matmul (the router's
and the indexer's too); ``bf16router`` computes only the router's scores,
and ``bf16index`` only the index scores, from bfloat16 operands into a
bfloat16 result.  Two more controls plant a SELECTION fault in float32
arithmetic, to show what the check sees of the selection itself:
``recent`` attends the most recent ``index_topk`` rows (no indexer),
``loose`` one block of 128 rows more than ``index_topk`` (a threshold one
block too low).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"
# Planted selection faults, float32 arithmetic: the rows a full layer's
# query attends are the most RECENT ``index_topk`` (no indexer), or one
# block of 128 more than ``index_topk`` (a threshold one block too low).
_FAULTS = ("recent", "loose")
PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16router",
              "bf16index") + _FAULTS
_EXACT = ("float32", "bf16router", "bf16index") + _FAULTS
FAULT_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class LatentSizes:
  heads: int
  q_rank: int
  kv_rank: int
  nope: int
  rope: int
  value: int
  theta: float


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
  layer_types: tuple
  hidden_size: int
  intermediate_size: int
  moe_intermediate_size: int
  full: LatentSizes
  swa: LatentSizes
  index_n_heads: int
  index_head_dim: int
  index_topk: int
  sliding_window_size: int
  router_width: int              # the published n_routed_experts
  experts_first: int             # the first expert this chip holds
  n_routed_experts: int          # how many it holds
  n_shared_experts: int
  num_experts_per_tok: int
  first_k_dense_replace: int
  vocab_size: int
  n_positions: int               # served context: the most a request holds
  routed_scaling_factor: float = 1.0
  norm_topk_prob: bool = True
  rms_norm_eps: float = 1e-5
  index_norm_eps: float = 1e-6
  initializer_range: float = 0.02
  bias_std: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "Dots3NoteConfig":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum; the
    router's width and the held experts from ``n_routed_experts_published``
    and ``assumed.experts_first`` beside ``n_routed_experts``."""
    assumed = doc.get("assumed", {})
    for key, want in (("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"), ("rope_scaling", None),
                      ("attention_bias", False), ("moe_layer_freq", 1),
                      ("apply_mla_qkv_lora_rescale", True),
                      ("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"),
                      ("tie_word_embeddings", False)):
      if doc.get(key, want) != want:
        raise ValueError(f"this reference writes {key} = {want!r} only; "
                         f"the configuration says {doc[key]!r}")
    sizes = lambda p: LatentSizes(
        heads=doc[p + "num_attention_heads"], q_rank=doc[p + "q_lora_rank"],
        kv_rank=doc[p + "kv_lora_rank"], nope=doc[p + "qk_nope_head_dim"],
        rope=doc[p + "qk_rope_head_dim"], value=doc[p + "v_head_dim"],
        theta=float(doc[p + "rope_theta"]))
    if len(doc["layer_types"]) != doc["num_hidden_layers"]:
      raise ValueError("layer_types must name num_hidden_layers layers")
    return Dots3NoteConfig(
        layer_types=tuple(doc["layer_types"]),
        hidden_size=doc["hidden_size"],
        intermediate_size=doc["intermediate_size"],
        moe_intermediate_size=doc["moe_intermediate_size"],
        full=sizes(""), swa=sizes("swa_"),
        index_n_heads=doc["index_n_heads"],
        index_head_dim=doc["index_head_dim"], index_topk=doc["index_topk"],
        sliding_window_size=doc["sliding_window_size"],
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
  def num_hidden_layers(self) -> int:
    return len(self.layer_types)

  def sizes(self, layer_type: str) -> LatentSizes:
    return {FULL: self.full, SLIDING: self.swa}[layer_type]

  def is_dense(self, i: int) -> bool:
    return i < self.first_k_dense_replace

  def mixer_params(self, layer_type: str) -> dict:
    """Parameters of one layer's attention by part: the latent attention,
    its gate, and (a full layer) its indexer."""
    D, z = self.hidden_size, self.sizes(layer_type)
    out = {
        "mixer": (D * z.q_rank + z.q_rank
                  + z.q_rank * z.heads * (z.nope + z.rope)
                  + D * (z.kv_rank + z.rope) + z.kv_rank
                  + z.kv_rank * z.heads * (z.nope + z.value)
                  + z.heads * z.value * D),
        "gate": D * z.heads}
    if layer_type == FULL:
      Hi, di = self.index_n_heads, self.index_head_dim
      out["indexer"] = z.q_rank * Hi * di + D * di + 2 * di + D * Hi
    return out

  def param_count(self) -> int:
    """Parameters of the cut as it is held here: the chip's experts and
    its slice of the vocabulary."""
    D, Fe = self.hidden_size, self.moe_intermediate_size
    total = 2 * self.vocab_size * D + D
    for i, layer_type in enumerate(self.layer_types):
      total += sum(self.mixer_params(layer_type).values()) + 2 * D
      if self.is_dense(i):
        total += 3 * D * self.intermediate_size
      else:
        total += (D * self.router_width + self.router_width
                  + self.n_routed_experts * 3 * D * Fe
                  + self.n_shared_experts * 3 * D * Fe)
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


def init_attention(cfg: Dots3NoteConfig, key, layer_type: str) -> dict:
  """One layer's latent attention, its gate, its two outer norms and, for
  a full layer, its indexer.

  A FULL layer's ``q_b`` and ``kv_b`` are drawn with ``std /
  sqrt(hidden_size / rank)``: narrower by the constant by which the latent
  each reads is rescaled, so that the attention logits of the layers that
  select have the spread of an un-rescaled latent attention's (a standard
  deviation near 0.3 at the published widths, not 2).  With weights from a
  seed the indexer's ranking is independent of the attention's, so under
  sharply peaked attention a served token hinges on whichever single row
  lies at the selection's edge, and ANY rounding upstream of the index
  scores moves it: a bfloat16 program then reads against this reference
  what a threshold one block off reads (control ``loose``), and no limit
  parts it from fp8 arithmetic.  The same program wholly in float32 agrees
  with this reference beyond the selection's start under either draw
  (``perfbench/selection_witness.py``), and with this draw the comparison
  still fails a selection that ignores the indexer (control ``recent``).  A
  trained model's indexer ranks the rows its attention weighs; its weights
  have also absorbed the constant."""
  D, z, std = cfg.hidden_size, cfg.sizes(layer_type), cfg.initializer_range
  k = jax.random.split(key, 15)
  narrow = lambda rank: np.sqrt(D / rank) if layer_type == FULL else 1.0
  p = {
      "norm_in": _gain(k[0], D, std), "norm_ff": _gain(k[1], D, std),
      "q_a": _normal(k[2], (D, z.q_rank), std),
      "q_norm": _gain(k[3], z.q_rank, std),
      "q_b": _normal(k[4], (z.q_rank, z.heads * (z.nope + z.rope)),
                     std / narrow(z.q_rank)),
      "kv_a": _normal(k[5], (D, z.kv_rank + z.rope), std),
      "kv_norm": _gain(k[6], z.kv_rank, std),
      "kv_b": _normal(k[7], (z.kv_rank, z.heads * (z.nope + z.value)),
                      std / narrow(z.kv_rank)),
      "o": _normal(k[8], (z.heads * z.value, D), _residual_std(cfg)),
      "gate": _normal(k[9], (D, z.heads), std),
  }
  if layer_type == FULL:
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    p.update({
        "index_q": _normal(k[10], (z.q_rank, Hi * di), std),
        "index_k": _normal(k[11], (D, di), std),
        "index_k_gain": _gain(k[12], di, std),
        "index_k_bias": std * jax.random.normal(k[13], (di,), jnp.float32),
        "index_w": _normal(k[14], (D, Hi), std),
    })
  return p


def _init_mlp(cfg, key, width: int) -> dict:
  D, std = cfg.hidden_size, cfg.initializer_range
  k = jax.random.split(key, 3)
  return {"gate": _normal(k[0], (D, width), std),
          "up": _normal(k[1], (D, width), std),
          "down": _normal(k[2], (width, D), _residual_std(cfg))}


def init_dense_ff(cfg: Dots3NoteConfig, key) -> dict:
  return _init_mlp(cfg, key, cfg.intermediate_size)


def init_moe_ff(cfg: Dots3NoteConfig, key) -> dict:
  """An expert layer: the router over ALL ``router_width`` experts (values
  rounded to bfloat16, as the checkpoint holds them), the float32 selection
  bias, the HELD experts stacked ``[n_routed_experts, ...]`` (expert ``e``
  of the router's is made from ``fold_in(key, e)``: a chip's experts do
  not depend on which others it holds) and made one at a time, the shared
  expert."""
  D, E = cfg.hidden_size, cfg.router_width
  k = jax.random.split(key, 4)
  held = cfg.experts_first + jnp.arange(cfg.n_routed_experts)
  experts = jax.lax.map(
      lambda e: _init_mlp(cfg, jax.random.fold_in(k[2], e),
                          cfg.moe_intermediate_size), held)
  return {
      "router": _normal(k[0], (D, E), cfg.initializer_range),
      "bias": cfg.bias_std * jax.random.normal(k[1], (E,), jnp.float32),
      "experts": experts,
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


def init_embedding(cfg: Dots3NoteConfig, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_head(cfg: Dots3NoteConfig, key):
  return _normal(key, (cfg.hidden_size, cfg.vocab_size),
                 cfg.initializer_range)


def init_layer(cfg: Dots3NoteConfig, key, i: int) -> dict:
  k_att, k_ff = layer_keys(key, i)
  init_ff = init_dense_ff if cfg.is_dense(i) else init_moe_ff
  return {"att": init_attention(cfg, k_att, cfg.layer_types[i]),
          "ff": init_ff(cfg, k_ff)}


def init_params(cfg: Dots3NoteConfig, key) -> dict:
  """Seeded weights, a list of layers (their shapes differ by type)."""
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


def index_scores(cfg: Dots3NoteConfig, u, c_q, p, precision: str):
  """``I(t, s)`` for every pair, ``[S, S]`` float32 (causality is the
  caller's): a head at a time into one sum."""
  S = u.shape[0]
  Hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.full.rope
  theta = cfg.full.theta
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


def latent_attention(cfg: Dots3NoteConfig, u, p, layer_type: str,
                     precision: str):
  """One layer's attention on ``u`` [S, D], keys and values EXPANDED for
  every position, a head at a time."""
  S, D = u.shape
  z = cfg.sizes(layer_type)
  eps = cfg.rms_norm_eps
  c_q = np.sqrt(D / z.q_rank) * rms_norm(
      _matmul(u, p["q_a"], precision), p["q_norm"], eps)
  kv = _matmul(u, p["kv_a"], precision)
  c = np.sqrt(D / z.kv_rank) * rms_norm(kv[:, :z.kv_rank], p["kv_norm"], eps)
  k_r = rotary(kv[:, z.kv_rank:], z.theta)                    # [S, rope]
  if layer_type == FULL:
    visible = selection(index_scores(cfg, u, c_q, p, precision),
                        cfg.index_topk,
                        precision if precision in _FAULTS else None)
  else:
    age = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    visible = (age >= 0) & (age < cfg.sliding_window_size)
  gate = jax.nn.sigmoid(_matmul(u, p["gate"], precision))     # [S, heads]
  w_qb = p["q_b"].reshape(z.q_rank, z.heads, z.nope + z.rope)
  w_kvb = p["kv_b"].reshape(z.kv_rank, z.heads, z.nope + z.value)

  def head(ws):
    w_q, w_kv, g = ws
    q = _matmul(c_q, w_q, precision)
    q = jnp.concatenate([q[:, :z.nope], rotary(q[:, z.nope:], z.theta)], -1)
    kvh = _matmul(c, w_kv, precision)
    k = jnp.concatenate([kvh[:, :z.nope], k_r], -1)
    scores = _einsum("qd,kd->qk", q, k, precision) / np.sqrt(z.nope + z.rope)
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    o = _einsum("qk,dk->qd", probs, kvh[:, z.nope:].T, precision)
    return g[:, None] * o

  ctx = jax.lax.map(head, (jnp.moveaxis(w_qb, 1, 0),
                           jnp.moveaxis(w_kvb, 1, 0), gate.T))
  ctx = jnp.moveaxis(ctx, 0, 1).reshape(S, z.heads * z.value)
  return _matmul(ctx, p["o"], precision)


def mlp(h, p, precision: str):
  return _matmul(silu(_matmul(h, p["gate"], precision))
                 * _matmul(h, p["up"], precision), p["down"], precision)


def route(cfg: Dots3NoteConfig, h, router, bias, precision: str):
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


def routed(cfg: Dots3NoteConfig, h, p, precision: str):
  """``sum over the chosen experts held here of w_i Expert_i(h)``: every
  held expert applied to every token, one at a time, weighted by its ``w``
  where chosen and 0 elsewhere."""
  chosen, w = route(cfg, h, p["router"], p["bias"], precision)
  weight_of = jnp.sum(
      jax.nn.one_hot(chosen, cfg.router_width, dtype=jnp.float32)
      * w[..., None], -2)
  weight_of = weight_of[:, cfg.experts_first:
                        cfg.experts_first + cfg.n_routed_experts]

  def add_expert(acc, e):
    pe = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, e, keepdims=False),
        p["experts"])
    w_e = jax.lax.dynamic_index_in_dim(weight_of, e, -1, keepdims=True)
    return acc + w_e * mlp(h, pe, precision), None

  out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        jnp.arange(cfg.n_routed_experts))
  return out


def moe(cfg: Dots3NoteConfig, h, p, precision: str):
  return mlp(h, p["shared"], precision) + routed(cfg, h, p, precision)


def hidden(cfg: Dots3NoteConfig, params, ids, precision: str = "float32"):
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
                               cfg.layer_types[i], precision)
      h = rms_norm(x, att["norm_ff"], eps)
      x = x + (mlp(h, ff, precision) if cfg.is_dense(i)
               else moe(cfg, h, ff, precision))
    return rms_norm(x, params["norm_f"], eps)

  return jax.lax.map(one, ids)


def logits(cfg: Dots3NoteConfig, params, ids, precision=None):
  """[B, S, vocab] logits through the untied head."""
  precision = precision or "float32"
  return _matmul(hidden(cfg, params, ids, precision), params["head"],
                 precision)
