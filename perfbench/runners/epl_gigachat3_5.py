"""Glue between the benchmark's GigaChat3.5 weights and the program's
``GigaChat``.

The benchmark makes the weights (``perfbench/reference/gigachat3_5.py``,
from the seed, a layer's from the seed and its index alone, an expert's
from its layer's key and its own index alone); this module only says where
each of them sits in the tree of
``easyparallellibrary_tpu.models.gigachat.GigaChat``, and which the program
keeps joined: an expert layer's gate and up matrices are ONE ``[held, D, 2
F]`` stack there (gate columns, then up).  Both sides hold the same share
of the experts (``experts_first``, ``n_routed_experts`` of the router's
``router_width``) and the same slice of the vocabulary.

The reference HOLDS a layer's experts as their key, so the checksum by
which a run shows that both started from the same weights draws them again
(:func:`sum_of_squares` on the reference's tree; on the program's it is the
sum over its leaves).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import gigachat3_5 as giga
# How a leaf is placed and checked is the expert decoders' glue's.
from perfbench.runners.epl_glm4_moe_lite import _keys, _place

STACKS = (("moe", "experts_gate_up"), ("moe", "experts_down"))

# program path inside a block -> the reference's leaf of that layer's mixer
_NORMS = {(n, "scale"): n
          for n in ("norm_in", "norm_mix_out", "norm_ff", "norm_ff_out")}
_LINEAR = {
    ("linear", "in_proj", "kernel"): "in_proj",
    ("linear", "ba", "kernel"): "ba", ("linear", "conv_w"): "conv",
    ("linear", "A_log"): "A_log", ("linear", "dt_bias"): "dt_bias",
    ("linear", "norm"): "o_norm", ("linear", "o", "kernel"): "o",
}
_LATENT = {
    ("latent", "q_a", "kernel"): "q_a", ("latent", "q_norm", "scale"): "q_norm",
    ("latent", "q_b", "kernel"): "q_b", ("latent", "kv_a", "kernel"): "kv_a",
    ("latent", "kv_norm", "scale"): "kv_norm", ("latent", "kv_b"): "kv_b",
    ("latent", "o", "kernel"): "o", ("latent", "gate", "kernel"): "gate",
}
_MIXER = {**_NORMS, **_LINEAR, **_LATENT}
_MLP = {(n, "kernel"): n for n in ("gate", "up", "down")}

ref_config = giga.GigaChat35Config.from_file


def _stacks(experts: giga.HeldExperts, first, count: int) -> dict:
  """The two stacks of the ``count`` experts from ``first``, each drawn
  once from the layer's experts key."""
  ex = jax.lax.map(experts.expert, first + jnp.arange(count))
  return {STACKS[0]: jnp.concatenate([ex["gate"], ex["up"]], -1),
          STACKS[1]: ex["down"]}


def layer_to_program(ref_cfg, mix: dict, ff: dict, block_tree):
  """One block of the program's tree filled from the reference's weights
  of that layer; every weight the reference made must find its place."""
  used = set()
  stacks = None if "experts" not in ff else _stacks(
      ff["experts"], *ref_cfg.experts_held)

  def moe_leaf(keys):
    if keys[1] == "shared":
      return ff["shared"][_MLP[keys[2:]]]
    if keys in STACKS:
      return stacks[keys]
    return {"router_kernel": ff["router"],
            "e_score_correction_bias": ff["bias"]}[keys[1]]

  def pick(path, leaf):
    keys = _keys(path)
    if keys in _MIXER:
      used.add(_MIXER[keys])
      return _place(keys, mix[_MIXER[keys]], leaf)
    if keys[0] == "mlp":
      return _place(keys, ff[_MLP[keys[1:]]], leaf)
    if keys[0] == "moe":
      return _place(keys, moe_leaf(keys), leaf)
    raise KeyError(f"the program has a parameter the reference lacks: "
                   f"{keys}")

  out = jax.tree_util.tree_map_with_path(pick, block_tree)
  if used != set(mix):
    raise KeyError(f"the reference has weights the program lacks: "
                   f"{sorted(set(mix) - used)}")
  return out


def program_params(ref_cfg, key, shell):
  """The program's ``params`` (``shell``: a tree shaped like them, arrays
  or shapes) filled with the seeded weights, made ONE LAYER AT A TIME from
  the same per-layer keys as ``giga.init_params`` (4.73B parameters twice
  do not fit a chip), one small program a layer kind (mixer x
  dense-or-expert)."""
  from flax import linen as nn
  shell = nn.meta.unbox(shell)
  k_embed, k_head, k_norm = giga.top_keys(key)
  make, out = {}, {}
  for i in range(ref_cfg.num_hidden_layers):
    kind = (ref_cfg.is_full(i), ref_cfg.is_dense(i))
    if kind not in make:
      # ``i`` only selects the layer's kind here; the keys are handed in.
      make[kind] = jax.jit(
          lambda k_mix, k_ff, i=i, tree=shell[f"block_{i}"]:
          layer_to_program(
              ref_cfg, giga.init_mixer(ref_cfg, k_mix, i),
              (giga.init_dense_ff if ref_cfg.is_dense(i)
               else giga.init_moe_ff)(ref_cfg, k_ff), tree))
    out[f"block_{i}"] = make[kind](*giga.layer_keys(key, i))
  fill = lambda name, make_value: jax.tree_util.tree_map(
      lambda leaf: _place(name, make_value(), leaf), shell[name])
  out.update(jax.jit(lambda: {
      "embed": fill("embed", lambda: giga.init_embedding(ref_cfg, k_embed)),
      "lm_head": fill("lm_head", lambda: giga.init_head(ref_cfg, k_head)),
      "norm_f": fill("norm_f", lambda: giga.init_final_norm(
          ref_cfg, k_norm))})())
  if set(out) != set(shell):
    raise KeyError(f"the program's tree has {sorted(set(shell) - set(out))} "
                   "beyond what the reference fills")
  return out


def sum_of_squares(tree):
  """Sum of squares over every weight, float32 accumulation: the checksum
  by which a run shows that program and reference started from the same
  weights (joining gate and up does not enter it).  The reference's tree
  holds a layer's experts as their key (``giga.HeldExperts``), which draws
  the chip's share again for its part of the sum."""
  held = lambda x: isinstance(x, giga.HeldExperts)
  return sum(leaf.sum_of_squares() if held(leaf)
             else jnp.sum(jnp.square(leaf.astype(jnp.float32)))
             for leaf in jax.tree_util.tree_leaves(tree, is_leaf=held))


def model_config(ref_cfg, model_opts: dict):
  """The program's ``GigaChatConfig`` at the configuration's widths and
  this chip's share."""
  from easyparallellibrary_tpu.models.gigachat import GigaChatConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  held = (None if ref_cfg.n_routed_experts == ref_cfg.router_width
          else ref_cfg.experts_held)
  return GigaChatConfig(
      vocab_size=ref_cfg.vocab_size, num_layers=ref_cfg.num_hidden_layers,
      full_attention_layers=ref_cfg.full_attention_layers,
      d_model=ref_cfg.hidden_size, d_ff=ref_cfg.intermediate_size,
      moe_d_ff=ref_cfg.moe_intermediate_size, num_heads=ref_cfg.heads,
      q_lora_rank=ref_cfg.q_rank, kv_lora_rank=ref_cfg.kv_rank,
      qk_nope_head_dim=ref_cfg.nope, qk_rope_head_dim=ref_cfg.rope,
      v_head_dim=ref_cfg.value, rope_theta=ref_cfg.theta,
      rope_factor=ref_cfg.yarn_factor,
      rope_original_max=ref_cfg.yarn_original,
      rope_beta_fast=ref_cfg.yarn_beta_fast,
      rope_beta_slow=ref_cfg.yarn_beta_slow,
      rope_mscale=ref_cfg.yarn_mscale,
      rope_mscale_all_dim=ref_cfg.yarn_mscale_all_dim,
      rope_scale_softmax=ref_cfg.mla_scaling_factor,
      linear_num_key_heads=ref_cfg.linear_key_heads,
      linear_num_value_heads=ref_cfg.linear_value_heads,
      linear_key_head_dim=ref_cfg.linear_key_dim,
      linear_value_head_dim=ref_cfg.linear_value_dim,
      linear_conv_kernel_dim=ref_cfg.conv_kernel,
      linear_sigmoid_gate_scale=ref_cfg.gate_scale,
      linear_attn_o_norm_eps=ref_cfg.o_norm_eps,
      layernorm_gating_weight=ref_cfg.norm_gating_weight,
      swiglu_limit=ref_cfg.swiglu_limit,
      n_routed_experts=ref_cfg.router_width, experts_held=held,
      n_shared_experts=ref_cfg.n_shared_experts,
      num_experts_per_tok=ref_cfg.num_experts_per_tok,
      first_k_dense=ref_cfg.first_k_dense_replace,
      routed_scaling_factor=ref_cfg.routed_scaling_factor,
      norm_topk_prob=ref_cfg.norm_topk_prob,
      rms_norm_eps=ref_cfg.rms_norm_eps, max_seq_len=ref_cfg.n_positions,
      **opts)


def build_model(ref_cfg, model_opts: dict):
  """``(model, ids -> params shell)`` of the program under test; the
  shell holds shapes only (nothing is initialised)."""
  from easyparallellibrary_tpu.models.gigachat import GigaChat
  model = GigaChat(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
