"""Glue between the benchmark's GLM-4.7-Flash weights and the program's
``GlmMoe``.

The benchmark makes the weights (``perfbench/reference/glm4_moe_lite.py``,
from the seed, a layer's from the seed and its index alone); this module
only says where each of them sits in the tree of
``easyparallellibrary_tpu.models.glm_moe.GlmMoe``, and which the program
keeps joined: an expert layer's gate and up matrices are ONE
``[E, D, 2 F]`` stack there (gate columns, then up), so that a layer's
experts take two grouped matmuls and not three.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import glm4_moe_lite as glm

# program path inside a block -> the reference's leaf of that layer
_ATTENTION = {
    ("norm_in", "scale"): "norm_in", ("norm_ff", "scale"): "norm_ff",
    ("latent", "q_a", "kernel"): "q_a", ("latent", "q_norm", "scale"): "q_norm",
    ("latent", "q_b", "kernel"): "q_b", ("latent", "kv_a", "kernel"): "kv_a",
    ("latent", "kv_norm", "scale"): "kv_norm", ("latent", "kv_b"): "kv_b",
    ("latent", "o", "kernel"): "o",
}
_MLP = {(n, "kernel"): n for n in ("gate", "up", "down")}


ref_config = glm.Glm4MoeLiteConfig.from_file


def _keys(path) -> tuple:
  return tuple(k.key for k in path
               if isinstance(k, jax.tree_util.DictKey))


def _place(name, value, leaf):
  if value.shape != leaf.shape:
    raise ValueError(f"{name}: reference {value.shape}, program "
                     f"{leaf.shape}")
  return value.astype(leaf.dtype)


def _moe_leaf(keys, ff: dict):
  if keys[0] == "shared":
    return ff["shared"][_MLP[keys[1:]]]
  ex = ff["experts"]
  return {
      ("router_kernel",): lambda: ff["router"],
      ("e_score_correction_bias",): lambda: ff["bias"],
      ("experts_gate_up",): lambda: jnp.concatenate(
          [ex["gate"], ex["up"]], -1),
      ("experts_down",): lambda: ex["down"],
  }[keys]()


def layer_to_program(att: dict, ff: dict, block_tree):
  """One block of the program's tree filled from the reference's weights
  of that layer (``init_attention`` and ``init_dense_ff`` /
  ``init_moe_ff``)."""
  def pick(path, leaf):
    keys = _keys(path)
    if keys in _ATTENTION:
      return _place(keys, att[_ATTENTION[keys]], leaf)
    if keys[0] == "mlp":
      return _place(keys, ff[_MLP[keys[1:]]], leaf)
    if keys[0] == "moe":
      return _place(keys, _moe_leaf(keys[1:], ff), leaf)
    raise KeyError(f"the program has a parameter the reference lacks: "
                   f"{keys}")
  return jax.tree_util.tree_map_with_path(pick, block_tree)


def program_params(ref_cfg, key, shell):
  """The program's ``params`` (``shell``: any tree shaped like them, boxed
  or not, arrays or shapes) filled with the seeded weights, made ONE
  LAYER AT A TIME from the same per-layer keys as ``glm.init_params``: the
  stacked reference tree never exists beside the program's (5.17B
  parameters twice do not fit a chip), and one small program a layer KIND
  is compiled, not 8 layers unrolled in one."""
  kinds = ref_cfg.layer_kinds()
  init_ff = {glm.DENSE: glm.init_dense_ff, glm.MOE: glm.init_moe_ff}
  k_embed, k_head, k_norm = glm.top_keys(key)
  make = {}
  for i, kind in enumerate(kinds):
    if kind not in make:
      make[kind] = jax.jit(
          lambda k_att, k_ff, kind=kind, tree=shell[f"block_{i}"]:
          layer_to_program(glm.init_attention(ref_cfg, k_att),
                           init_ff[kind](ref_cfg, k_ff), tree))
  fill = lambda name, make_value: jax.tree_util.tree_map(
      lambda leaf: _place(name, make_value(), leaf), shell[name])
  out = dict(jax.jit(lambda: {
      "embed": fill("embed", lambda: glm.init_embedding(ref_cfg, k_embed)),
      "lm_head": fill("lm_head", lambda: glm.init_head(ref_cfg, k_head)),
      "norm_f": fill("norm_f", lambda: glm._gain(
          k_norm, ref_cfg.hidden_size, ref_cfg.initializer_range))})())
  for i, kind in enumerate(kinds):
    out[f"block_{i}"] = make[kind](*glm.layer_keys(key, i))
  if set(out) != set(shell):
    raise KeyError(f"the program's tree has {sorted(set(shell) - set(out))} "
                   "beyond what the reference fills")
  return out


def sum_of_squares(tree):
  """Sum of squares over every leaf, float32 accumulation: the checksum
  by which a run shows that program and reference started from the same
  weights (joining gate and up does not enter it)."""
  return sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
             for leaf in jax.tree_util.tree_leaves(tree))


def model_config(ref_cfg, model_opts: dict):
  """The program's ``GlmMoeConfig`` at the configuration's widths."""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoeConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  return GlmMoeConfig(
      vocab_size=ref_cfg.vocab_size, num_layers=ref_cfg.num_hidden_layers,
      d_model=ref_cfg.hidden_size, d_ff=ref_cfg.intermediate_size,
      moe_d_ff=ref_cfg.moe_intermediate_size,
      num_heads=ref_cfg.num_attention_heads,
      q_lora_rank=ref_cfg.q_lora_rank, kv_lora_rank=ref_cfg.kv_lora_rank,
      qk_nope_head_dim=ref_cfg.qk_nope_head_dim,
      qk_rope_head_dim=ref_cfg.qk_rope_head_dim,
      v_head_dim=ref_cfg.v_head_dim,
      n_routed_experts=ref_cfg.n_routed_experts,
      n_shared_experts=ref_cfg.n_shared_experts,
      num_experts_per_tok=ref_cfg.num_experts_per_tok,
      first_k_dense=ref_cfg.first_k_dense_replace,
      routed_scaling_factor=ref_cfg.routed_scaling_factor,
      norm_topk_prob=ref_cfg.norm_topk_prob, rope_theta=ref_cfg.rope_theta,
      rms_norm_eps=ref_cfg.rms_norm_eps, max_seq_len=ref_cfg.n_positions,
      **opts)


def build_model(ref_cfg, model_opts: dict):
  """``(model, ids -> params shell)`` of the program under test; the
  shell holds shapes only (nothing is initialised)."""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoe
  model = GlmMoe(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
