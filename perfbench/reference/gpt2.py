"""GPT-2 in plain ``jax.numpy``: forward, loss, gradients, AdamW.

Radford et al. 2019 as published in ``transformers``' ``GPT2LMHeadModel``:
learned positions, pre-LayerNorm blocks, full multi-head causal attention,
tanh-GELU (``gelu_new``) MLP, final LayerNorm, head tied to the token
embedding.  float32 throughout, matmuls at ``highest`` precision (on a TPU
a float32 matmul is otherwise computed in bfloat16 passes).  No kernels, no
cache, no batching tricks; it imports nothing of the program under test and
is handed nothing the program has made.

Departures from the published model, shared with the system under test and
listed in each configuration file under ``assumed``: no biases on the
attention projections, vocabulary padded to a multiple of 128, no dropout,
random weights from a seed.

Layers are stacked on a leading axis and scanned, each under
``jax.checkpoint``: the same mathematics with one layer's temporaries
live at a time, so the reference fits beside nothing else on one chip.

``precision`` selects how the matmul operands are held: ``float32`` is the
reference; ``bfloat16``, ``int8`` (one scale per row or column along the
contraction) and ``fp8`` (e4m3) exist for the control that shows the check
fails when the work is done in a lower precision (operands rounded to that
type, straight-through for the gradient, float32 accumulation).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GPT2Config:
  n_layer: int
  n_embd: int
  n_head: int
  n_inner: int
  n_positions: int
  vocab_size: int                # rows of the embedding as run (padded)
  layer_norm_epsilon: float = 1e-5
  initializer_range: float = 0.02

  @staticmethod
  def from_file(doc: dict) -> "GPT2Config":
    """From a configuration file: the published keys, with the two
    departures of ``assumed`` that change the arithmetic (the padded
    vocabulary, the LayerNorm epsilon as the system runs it) in place
    of the published values."""
    assumed = doc.get("assumed", {})
    return GPT2Config(
        n_layer=doc["n_layer"], n_embd=doc["n_embd"], n_head=doc["n_head"],
        n_inner=doc["n_inner"] or 4 * doc["n_embd"],
        n_positions=doc["n_positions"],
        vocab_size=assumed.get("padded_vocab_size", doc["vocab_size"]),
        layer_norm_epsilon=assumed.get("layer_norm_epsilon_as_run",
                                       doc["layer_norm_epsilon"]),
        initializer_range=doc["initializer_range"])


def seed_key(seed: int, stream: int = 0):
  """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
  words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
  return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def init_params(cfg: GPT2Config, key):
  """Seeded weights, GPT-2's own scheme: N(0, 0.02) matrices, residual
  projections scaled by 1/sqrt(2 n_layer), LayerNorm gains near one and
  biases near zero (drawn, not constant, so that a transposed or dropped
  gain or bias shows in the comparison)."""
  L, D, F = cfg.n_layer, cfg.n_embd, cfg.n_inner
  std = cfg.initializer_range
  k = iter(jax.random.split(key, 16))
  n = lambda shape, s: s * jax.random.normal(next(k), shape, jnp.float32)
  res = std / np.sqrt(2.0 * L)
  return {
      "wte": n((cfg.vocab_size, D), std),
      "wpe": n((cfg.n_positions, D), std),
      "h": {
          "ln_1_g": 1.0 + n((L, D), std), "ln_1_b": n((L, D), std),
          "c_attn_w": n((L, D, 3 * D), std),
          "attn_proj_w": n((L, D, D), res),
          "ln_2_g": 1.0 + n((L, D), std), "ln_2_b": n((L, D), std),
          "c_fc_w": n((L, D, F), std), "c_fc_b": n((L, F), std),
          "mlp_proj_w": n((L, F, D), res), "mlp_proj_b": n((L, D), std),
      },
      "ln_f_g": 1.0 + n((D,), std), "ln_f_b": n((D,), std),
  }


# ------------------------------------------------------------ precision --


def _int8(x, axis):
  """Symmetric int8 with one scale per slice along ``axis``, straight
  through for the gradient."""
  scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
  scale = jnp.where(scale > 0, scale, 1.0)
  q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
  return x + jax.lax.stop_gradient(q - x)


def _fp8(x):
  """Rounded to float8 e4m3 (3 mantissa bits), straight through for the
  gradient."""
  q = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
  return x + jax.lax.stop_gradient(q - x)


def _matmul(x, w, precision: str):
  """``x @ w`` over the last axis of ``x`` and the first of ``w``."""
  if precision == "float32":
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
  if precision == "bfloat16":
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
  if precision == "int8":
    return jnp.matmul(_int8(x, -1), _int8(w, 0),
                      precision=jax.lax.Precision.HIGHEST)
  if precision == "fp8":
    return jnp.matmul(_fp8(x), _fp8(w), precision=jax.lax.Precision.HIGHEST)
  raise ValueError(f"precision {precision!r}")


def _einsum(spec, a, b, precision: str):
  if precision == "bfloat16":
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
  if precision == "int8":
    a, b = _int8(a, -1), _int8(b, -1)
  elif precision == "fp8":
    a, b = _fp8(a), _fp8(b)
  elif precision != "float32":
    raise ValueError(f"precision {precision!r}")
  return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# -------------------------------------------------------------- forward --


def layer_norm(x, g, b, eps):
  mean = jnp.mean(x, -1, keepdims=True)
  var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
  return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def gelu_new(x):
  return 0.5 * x * (1.0 + jnp.tanh(
      np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def block(cfg: GPT2Config, x, p, precision: str):
  """One pre-LayerNorm block on ``x`` [B, S, D]."""
  B, S, D = x.shape
  H = cfg.n_head
  y = layer_norm(x, p["ln_1_g"], p["ln_1_b"], cfg.layer_norm_epsilon)
  qkv = _matmul(y, p["c_attn_w"], precision).reshape(B, S, 3, H, D // H)
  q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
  scores = _einsum("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(D // H)
  causal = jnp.tril(jnp.ones((S, S), bool))
  scores = jnp.where(causal[None, None], scores, -jnp.inf)
  probs = jax.nn.softmax(scores, axis=-1)
  # [b,h,q,k] x [b,k,h,d]: contract over k, which must be the last axis
  # of both operands for the int8 scales to run along it.
  ctx = _einsum("bhqk,bhdk->bqhd", probs, v.transpose(0, 2, 3, 1),
                precision).reshape(B, S, D)
  x = x + _matmul(ctx, p["attn_proj_w"], precision)
  y = layer_norm(x, p["ln_2_g"], p["ln_2_b"], cfg.layer_norm_epsilon)
  h = gelu_new(_matmul(y, p["c_fc_w"], precision) + p["c_fc_b"])
  return x + _matmul(h, p["mlp_proj_w"], precision) + p["mlp_proj_b"]


def hidden(cfg: GPT2Config, params, ids, precision: str = "float32"):
  """Final-LayerNorm hidden states [B, S, D] of token ids [B, S]."""
  S = ids.shape[1]
  x = params["wte"][ids] + params["wpe"][:S][None]
  step = jax.checkpoint(
      lambda x, p: (block(cfg, x, p, precision), None))
  x, _ = jax.lax.scan(step, x, params["h"])
  return layer_norm(x, params["ln_f_g"], params["ln_f_b"],
                    cfg.layer_norm_epsilon)


def logits(cfg: GPT2Config, params, ids, precision: str = "float32"):
  """[B, S, vocab] logits; the head is the token embedding."""
  return _matmul(hidden(cfg, params, ids, precision), params["wte"].T,
                 precision)


def loss(cfg: GPT2Config, params, ids, precision: str = "float32"):
  """Sum over rows and positions of the next-token cross entropy of
  ``ids`` [B, S + 1] (a sum, so that row blocks add up)."""
  lg = logits(cfg, params, ids[:, :-1], precision)
  logz = jax.nn.logsumexp(lg, axis=-1)
  picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
  return jnp.sum(logz - picked)


# ------------------------------------------------------------- training --


def loss_and_grads(cfg: GPT2Config, params, ids, row_block: int,
                   precision: str = "float32"):
  """Mean loss of the batch ``ids`` [B, S + 1] and its gradient, summed
  over blocks of ``row_block`` rows so that one block's activations are
  live at a time."""
  B, S1 = ids.shape
  if B % row_block:
    raise ValueError(f"{B} rows do not split into blocks of {row_block}")
  # Block j takes rows j, j + B/row_block, ...: the sum is the same, and
  # rows placed across several chips in contiguous runs give each chip one
  # row of every block, so the blocks' work spreads over the chips.
  blocks = ids.reshape(row_block, B // row_block, S1).swapaxes(0, 1)
  vg = jax.value_and_grad(lambda p, b: loss(cfg, p, b, precision))

  def body(carry, b):
    total, acc = carry
    l, g = vg(params, b)
    return (total + l, jax.tree_util.tree_map(jnp.add, acc, g)), None

  zero = jax.tree_util.tree_map(jnp.zeros_like, params)
  (total, grads), _ = jax.lax.scan(body, (jnp.float32(0), zero), blocks)
  n = B * (S1 - 1)
  return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def adamw_update(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.0):
  """One AdamW step (Loshchilov & Hutter 2019, as ``optax.adamw``: the
  decay is added to the bias-corrected Adam direction and both are scaled
  by the learning rate).  ``t`` counts from 1."""
  m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
  v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                             grads)
  c1, c2 = 1 - b1 ** t, 1 - b2 ** t

  def upd(p, m, v):
    return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p)

  return jax.tree_util.tree_map(upd, params, m, v), m, v


def sq_norms(tree):
  """Sum of squares of every leaf, the stacked layers one by one (a
  vector per stacked leaf); jit-able."""
  top = {k: jnp.sum(jnp.square(v)) for k, v in tree.items() if k != "h"}
  top["h"] = {k: jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim)))
              for k, v in tree["h"].items()}
  return top


def name_leaves(sq: dict) -> dict:
  """``{name: L2 norm}`` from :func:`sq_norms`' result, one entry per
  leaf of the model as a per-leaf comparison walks them
  (``h.<i>.<name>`` for the layers)."""
  sq = jax.device_get(sq)
  out = {k: float(np.sqrt(v)) for k, v in sq.items() if k != "h"}
  for k, per_layer in sq["h"].items():
    for i, v in enumerate(per_layer):
      out[f"h.{i}.{k}"] = float(np.sqrt(v))
  return out


def train_step(cfg: GPT2Config, params, m, v, t, ids, opt: dict,
               row_block: int, precision: str = "float32"):
  """One optimizer step on the batch ``ids``; ``t`` counts from 1.
  Returns ``(loss before the update, sq_norms of the gradient, params,
  m, v)``."""
  l, g = loss_and_grads(cfg, params, ids, row_block, precision)
  params, m, v = adamw_update(
      params, g, m, v, t, opt["learning_rate"], opt.get("b1", 0.9),
      opt.get("b2", 0.999), opt.get("eps", 1e-8),
      opt.get("weight_decay", 0.0))
  return l, sq_norms(g), params, m, v


def follow_steps(cfg: GPT2Config, params, batches, opt: dict, row_block: int,
                 precision: str = "float32", place=lambda x: x):
  """Follow the first ``len(batches)`` optimizer steps from ``params``.

  Returns ``(losses, first gradient's leaf norms, leaf norms of the
  parameters' change)``, the norms as ``{name: value}``.
  """
  step = jax.jit(
      functools.partial(train_step, cfg, opt=opt, row_block=row_block,
                        precision=precision), donate_argnums=(1, 2))
  start = params
  m = jax.tree_util.tree_map(jnp.zeros_like, params)
  v = jax.tree_util.tree_map(jnp.zeros_like, params)
  losses, first = [], None
  for t, ids in enumerate(batches, start=1):
    l, gsq, params, m, v = step(params, m, v, jnp.float32(t),
                                place(jnp.asarray(ids)))
    losses.append(float(l))
    if first is None:
      first = name_leaves(gsq)
  change = jax.jit(lambda a, b: sq_norms(
      jax.tree_util.tree_map(jnp.subtract, a, b)))(params, start)
  return losses, first, name_leaves(change)
