"""Jamba in plain ``jax.numpy``: the forward pass of the hybrid decoder.

Lieber et al. 2024 ("Jamba: A Hybrid Transformer-Mamba Language Model") as
``transformers``' ``modeling_jamba.py`` runs ``model_type: jamba`` with
``num_experts`` 1: pre-RMSNorm layers whose mixer is attention where
``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 block (Gu &
Dao 2023) elsewhere, each followed by a SiLU-gated MLP; a final RMSNorm;
the head tied to the embedding.  With ``RMS(x) = x * rsqrt(mean(x^2) +
eps) * g``:

* attention: ``q = h W_q`` (H heads), ``k = h W_k``, ``v = h W_v`` (H_kv
  heads, shared by groups of H / H_kv query heads), no bias, NO positional
  encoding of any kind, causal ``softmax(q k^T / sqrt(hd)) v``, then ``W_o``;
* Mamba: ``[u, z] = h W_in``; ``u = silu(conv1d(u))`` (depthwise, causal,
  kernel ``d_conv``, with bias); ``[dt, B, C] = u W_x``; ``dt = RMS(dt)``,
  ``B = RMS(B)``, ``C = RMS(C)`` (Jamba's addition to Mamba-1);
  ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; per channel d
  and state n ``s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t``,
  ``y_t = sum_n s_t C_t + D u_t``; output ``W_out (y * silu(z))``.

float32 throughout, matmuls at ``highest`` precision, the whole sequence by
a plain ``lax.scan`` over time: no cache, no chunks, no kernels.  It
imports nothing of the program under test.

Weights come from a seed and are ROUNDED TO BFLOAT16 ONCE (the published
checkpoint is bfloat16): program and reference both start from those
values.  ``A_log``, ``D``, the ``dt`` bias and the norm gains stay float32,
as the architecture's own code keeps them.  The layers are stacked by kind
and walked by one ``lax.scan`` that picks a layer's weights by index and
upcasts them there, so the bfloat16 stacks and ONE layer's float32 copy are
all that is live (3.03B parameters fit a 16 GB chip beside nothing else).

``precision``: ``float32`` is the reference.  The controls show that the
check fails when the work is done in a lower precision: ``fp8`` /
``bfloat16`` / ``int8`` round the matmul operands (as ``gpt2.py``);
``bf16state`` keeps the matmuls exact and rounds the recurrent state to
bfloat16 after every token.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION, MAMBA = "attention", "mamba"
PRECISIONS = ("float32", "bfloat16", "int8", "fp8", "bf16state")


@dataclasses.dataclass(frozen=True)
class JambaConfig:
  num_hidden_layers: int
  hidden_size: int
  intermediate_size: int
  num_attention_heads: int
  num_key_value_heads: int
  vocab_size: int
  attn_layer_period: int
  attn_layer_offset: int
  mamba_d_state: int
  mamba_d_conv: int
  mamba_expand: int
  mamba_dt_rank: int
  n_positions: int               # served context: the most a request holds
  rms_norm_eps: float = 1e-6
  initializer_range: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "JambaConfig":
    """From a configuration file: the published keys; the served context
    (``assumed.served_context``) in place of the published maximum."""
    assumed = doc.get("assumed", {})
    return JambaConfig(
        num_hidden_layers=doc["num_hidden_layers"],
        hidden_size=doc["hidden_size"],
        intermediate_size=doc["intermediate_size"],
        num_attention_heads=doc["num_attention_heads"],
        num_key_value_heads=doc["num_key_value_heads"],
        vocab_size=doc["vocab_size"],
        attn_layer_period=doc["attn_layer_period"],
        attn_layer_offset=doc["attn_layer_offset"],
        mamba_d_state=doc["mamba_d_state"], mamba_d_conv=doc["mamba_d_conv"],
        mamba_expand=doc["mamba_expand"], mamba_dt_rank=doc["mamba_dt_rank"],
        n_positions=assumed.get("served_context",
                                doc["max_position_embeddings"]),
        rms_norm_eps=doc["rms_norm_eps"],
        initializer_range=assumed.get("initializer_range", 0.02))

  @property
  def head_dim(self) -> int:
    return self.hidden_size // self.num_attention_heads

  @property
  def d_inner(self) -> int:
    return self.mamba_expand * self.hidden_size

  def layer_kinds(self) -> tuple:
    """HF's rule: layer ``i`` is attention where ``i % attn_layer_period
    == attn_layer_offset``, Mamba elsewhere."""
    return tuple(
        ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
        else MAMBA for i in range(self.num_hidden_layers))

  def param_count(self) -> int:
    D, F, Di = self.hidden_size, self.intermediate_size, self.d_inner
    N, K, R = self.mamba_d_state, self.mamba_d_conv, self.mamba_dt_rank
    H, Hkv, hd = (self.num_attention_heads, self.num_key_value_heads,
                  self.head_dim)
    mamba = (D * 2 * Di + Di * K + Di + Di * (R + 2 * N) + R + 2 * N
             + R * Di + Di + Di * N + Di + Di * D)
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    ff = 3 * D * F + 2 * D
    kinds = self.layer_kinds()
    return (self.vocab_size * D + D + len(kinds) * ff
            + kinds.count(MAMBA) * mamba + kinds.count(ATTENTION) * attn)


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
  """A norm's gain: drawn near one (a dropped or transposed gain then
  shows in the comparison), float32."""
  return 1.0 + std * jax.random.normal(key, (n,), jnp.float32)


def init_mamba(cfg: JambaConfig, key) -> dict:
  """One Mamba mixer.  What the recurrence depends on follows Mamba's own
  published initialisation (``mamba_ssm`` ``Mamba.__init__``): ``A_log =
  log(1..N)`` per channel, ``D = 1``, ``dt_proj`` uniform in +-
  ``dt_rank^-0.5``, its bias the inverse softplus of a step drawn
  log-uniform in [1e-3, 1e-1] (floor 1e-4); the convolution as PyTorch
  initialises a ``Conv1d`` (uniform in +- 1/sqrt(kernel)).  The three
  large projections are N(0, 0.02), the residual output scaled by
  1/sqrt(2 L)."""
  D, Di = cfg.hidden_size, cfg.d_inner
  N, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
  std = cfg.initializer_range
  k = jax.random.split(key, 10)
  dt = jnp.exp(jax.random.uniform(k[5], (Di,), jnp.float32)
               * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
  dt = jnp.maximum(dt, 1e-4)
  bound = 1.0 / np.sqrt(K)
  uni = lambda key, shape, b: jax.random.uniform(
      key, shape, jnp.float32, -b, b).astype(_BF16)
  return {
      "in_proj": _normal(k[0], (D, 2 * Di), std),
      "conv_w": uni(k[1], (Di, K), bound),
      "conv_b": uni(k[2], (Di,), bound),
      "x_proj": _normal(k[3], (Di, R + 2 * N), std),
      "dt_proj": uni(k[4], (R, Di), R ** -0.5),
      "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
      "dt_norm": _gain(k[6], R, std),
      "b_norm": _gain(k[7], N, std),
      "c_norm": _gain(k[8], N, std),
      "A_log": jnp.broadcast_to(
          jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (Di, N)),
      "D": jnp.ones((Di,), jnp.float32),
      "out_proj": _normal(k[9], (Di, D),
                          std / np.sqrt(2.0 * cfg.num_hidden_layers)),
  }


def init_attention(cfg: JambaConfig, key) -> dict:
  D, hd = cfg.hidden_size, cfg.head_dim
  H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
  std = cfg.initializer_range
  k = jax.random.split(key, 4)
  return {
      "q": _normal(k[0], (D, H * hd), std),
      "k": _normal(k[1], (D, Hkv * hd), std),
      "v": _normal(k[2], (D, Hkv * hd), std),
      "o": _normal(k[3], (H * hd, D),
                   std / np.sqrt(2.0 * cfg.num_hidden_layers)),
  }


def init_ff(cfg: JambaConfig, key) -> dict:
  """What every layer has beside its mixer: the two norms' gains and the
  gated MLP."""
  D, F = cfg.hidden_size, cfg.intermediate_size
  std = cfg.initializer_range
  k = jax.random.split(key, 5)
  return {
      "norm_in": _gain(k[0], D, std), "norm_ff": _gain(k[1], D, std),
      "gate": _normal(k[2], (D, F), std), "up": _normal(k[3], (D, F), std),
      "down": _normal(k[4], (F, D),
                      std / np.sqrt(2.0 * cfg.num_hidden_layers)),
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


def init_embedding(cfg: JambaConfig, key):
  return _normal(key, (cfg.vocab_size, cfg.hidden_size),
                 cfg.initializer_range)


def init_params(cfg: JambaConfig, key) -> dict:
  """Seeded weights, stacked by kind on a leading axis: ``ff`` over all
  layers, ``mamba`` and ``attention`` over the layers of that kind in
  order.  Made one layer at a time (``lax.map``), so the float32 draws
  of one layer are all that is live beside the bfloat16 result."""
  kinds = cfg.layer_kinds()
  k_embed, k_norm = top_keys(key)

  def stack(init, which, half):
    idx = jnp.asarray(which, jnp.int32)
    return jax.lax.map(
        lambda i: init(cfg, layer_keys(key, i)[half]), idx)

  params = {
      "embed": init_embedding(cfg, k_embed),
      "norm_f": _gain(k_norm, cfg.hidden_size, cfg.initializer_range),
      "ff": stack(init_ff, range(len(kinds)), 1),
  }
  for kind, init in ((MAMBA, init_mamba), (ATTENTION, init_attention)):
    which = [i for i, k in enumerate(kinds) if k == kind]
    if which:
      params[kind] = stack(init, which, 0)
  return params


# ------------------------------------------------------------ precision --


def _int8(x, axis):
  scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
  scale = jnp.where(scale > 0, scale, 1.0)
  return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fp8(x):
  return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _matmul(x, w, precision: str):
  """``x @ w`` over the last axis of ``x`` and the first of ``w``."""
  hi = jax.lax.Precision.HIGHEST
  if precision in ("float32", "bf16state"):
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
  elif precision not in ("float32", "bf16state"):
    raise ValueError(f"precision {precision!r}")
  return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# -------------------------------------------------------------- forward --


def rms_norm(x, g, eps):
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                           + eps) * g


def silu(x):
  return x * jax.nn.sigmoid(x)


def attention(cfg: JambaConfig, h, p, precision: str):
  """Grouped-query causal attention on ``h`` [B, S, D]: no positions."""
  B, S, _ = h.shape
  H, Hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)
  f32 = lambda w: w.astype(jnp.float32)
  q = _matmul(h, f32(p["q"]), precision).reshape(B, S, Hkv, H // Hkv, hd)
  k = _matmul(h, f32(p["k"]), precision).reshape(B, S, Hkv, hd)
  v = _matmul(h, f32(p["v"]), precision).reshape(B, S, Hkv, hd)
  scores = _einsum("bqhgd,bkhd->bhgqk", q, k, precision) / np.sqrt(hd)
  causal = jnp.tril(jnp.ones((S, S), bool))
  scores = jnp.where(causal, scores, -jnp.inf)
  probs = jax.nn.softmax(scores, axis=-1)
  # contract over k, the last axis of both operands
  ctx = _einsum("bhgqk,bhdk->bqhgd", probs, v.transpose(0, 2, 3, 1),
                precision).reshape(B, S, H * hd)
  return _matmul(ctx, f32(p["o"]), precision)


def mamba(cfg: JambaConfig, h, p, precision: str):
  """The Mamba mixer on ``h`` [B, S, D], from zero state and zero
  convolution history, token by token."""
  B, S, _ = h.shape
  Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                 cfg.mamba_dt_rank)
  f32 = lambda w: w.astype(jnp.float32)
  uz = _matmul(h, f32(p["in_proj"]), precision)
  u, z = uz[..., :Di], uz[..., Di:]
  # causal depthwise convolution: tap K-1 multiplies the current token
  padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
  w = f32(p["conv_w"])
  u = sum(padded[:, j:j + S] * w[:, j] for j in range(K)) + f32(p["conv_b"])
  u = silu(u)
  dbc = _matmul(u, f32(p["x_proj"]), precision)
  eps = cfg.rms_norm_eps
  dt = rms_norm(dbc[..., :R], p["dt_norm"], eps)
  Bm = rms_norm(dbc[..., R:R + N], p["b_norm"], eps)
  Cm = rms_norm(dbc[..., R + N:], p["c_norm"], eps)
  delta = jax.nn.softplus(_matmul(dt, f32(p["dt_proj"]), precision)
                          + p["dt_bias"])
  A = -jnp.exp(p["A_log"])                                  # [Di, N]

  def step(s, xs):
    d_t, u_t, b_t, c_t = xs          # [B, Di], [B, Di], [B, N], [B, N]
    s = (jnp.exp(d_t[..., None] * A) * s
         + (d_t * u_t)[..., None] * b_t[:, None, :])
    if precision == "bf16state":
      s = s.astype(_BF16).astype(jnp.float32)
    return s, jnp.sum(s * c_t[:, None, :], -1) + p["D"] * u_t

  t_major = lambda x: jnp.moveaxis(x, 1, 0)
  _, y = jax.lax.scan(step, jnp.zeros((B, Di, N), jnp.float32),
                      (t_major(delta), t_major(u), t_major(Bm), t_major(Cm)))
  return _matmul(t_major(y) * silu(z), f32(p["out_proj"]), precision)


def mlp(h, p, precision: str):
  f32 = lambda w: w.astype(jnp.float32)
  return _matmul(silu(_matmul(h, f32(p["gate"]), precision))
                 * _matmul(h, f32(p["up"]), precision),
                 f32(p["down"]), precision)


def hidden(cfg: JambaConfig, params, ids, precision: str = "float32"):
  """Final-RMSNorm hidden states [B, S, D] of token ids [B, S]."""
  if precision not in PRECISIONS:
    raise ValueError(f"precision {precision!r}")
  kinds = cfg.layer_kinds()
  x = params["embed"][ids].astype(jnp.float32)
  # position of each layer in its kind's stack
  seen = {MAMBA: 0, ATTENTION: 0}
  within = []
  for k in kinds:
    within.append(seen[k])
    seen[k] += 1
  mixers = {MAMBA: mamba, ATTENTION: attention}
  pick = lambda kind, j: jax.tree_util.tree_map(
      lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False),
      params[kind])

  def layer(x, xs):
    is_attn, j, ff = xs
    h = rms_norm(x, ff["norm_in"], cfg.rms_norm_eps)
    present = [k for k in (MAMBA, ATTENTION) if k in params]
    if len(present) == 1:
      mixed = mixers[present[0]](cfg, h, pick(present[0], j), precision)
    else:
      mixed = jax.lax.cond(
          is_attn,
          lambda: attention(cfg, h, pick(ATTENTION, j), precision),
          lambda: mamba(cfg, h, pick(MAMBA, j), precision))
    x = x + mixed
    x = x + mlp(rms_norm(x, ff["norm_ff"], cfg.rms_norm_eps), ff, precision)
    return x, None

  x, _ = jax.lax.scan(
      layer, x,
      (jnp.asarray([k == ATTENTION for k in kinds]),
       jnp.asarray(within, jnp.int32), params["ff"]))
  return rms_norm(x, params["norm_f"], cfg.rms_norm_eps)


def logits(cfg: JambaConfig, params, ids, precision=None):
  """[B, S, vocab] logits; the head is the token embedding."""
  precision = precision or "float32"
  return _matmul(hidden(cfg, params, ids, precision),
                 params["embed"].astype(jnp.float32).T, precision)
