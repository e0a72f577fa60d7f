"""Glue between the benchmark's GPT-2 weights and the program's GPT.

The benchmark makes the weights (``perfbench/reference/gpt2.py``
``init_params``, from the seed); this module only says where each of them
sits in the tree of ``easyparallellibrary_tpu.models.GPT``.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

# (program path inside a block) -> stacked reference leaf
_BLOCK = {
    ("ln1", "scale"): "ln_1_g", ("ln1", "bias"): "ln_1_b",
    ("attn", "qkv", "kernel"): "c_attn_w",
    ("attn", "proj", "kernel"): "attn_proj_w",
    ("ln2", "scale"): "ln_2_g", ("ln2", "bias"): "ln_2_b",
    ("mlp", "wi", "kernel"): "c_fc_w", ("mlp", "wi", "bias"): "c_fc_b",
    ("mlp", "wo", "kernel"): "mlp_proj_w", ("mlp", "wo", "bias"): "mlp_proj_b",
}
_TOP = {
    ("wte", "embedding"): "wte", ("wpe",): "wpe",
    ("ln_f", "scale"): "ln_f_g", ("ln_f", "bias"): "ln_f_b",
}


def _keys(path) -> tuple:
  return tuple(k.key for k in path
               if isinstance(k, jax.tree_util.DictKey))


def ref_name(path) -> str:
  """The reference's name of the program leaf at ``path``."""
  keys = _keys(path)
  if keys in _TOP:
    return _TOP[keys]
  m = re.fullmatch(r"block_(\d+)", keys[0])
  if not m or keys[1:] not in _BLOCK:
    raise KeyError(f"the program has a parameter the reference lacks: "
                   f"{keys}")
  return f"h.{m.group(1)}.{_BLOCK[keys[1:]]}"


def to_program_tree(ref_params: dict, program_tree):
  """``program_tree`` (any tree shaped like the program's ``params``,
  boxed or not) with every leaf replaced by the reference's weight."""
  def pick(path, leaf):
    name = ref_name(path)
    if name.startswith("h."):
      _, i, leaf_name = name.split(".", 2)
      value = ref_params["h"][leaf_name][int(i)]
    else:
      value = ref_params[name]
    if value.shape != leaf.shape:
      raise ValueError(f"{name}: reference {value.shape}, program "
                       f"{leaf.shape}")
    return value.astype(leaf.dtype)
  return jax.tree_util.tree_map_with_path(pick, program_tree)


def named_sq_norms(tree) -> dict:
  """``{reference name: sum of squares}`` of a program-shaped tree, as
  device scalars (jit-able)."""
  return {ref_name(path): jnp.sum(jnp.square(leaf.astype(jnp.float32)))
          for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def gpt_config(ref_cfg, model_opts: dict):
  """The program's ``GPTConfig`` at the configuration's widths."""
  from easyparallellibrary_tpu.models import GPTConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  return GPTConfig(
      vocab_size=ref_cfg.vocab_size, num_layers=ref_cfg.n_layer,
      num_heads=ref_cfg.n_head, d_model=ref_cfg.n_embd,
      d_ff=ref_cfg.n_inner, max_seq_len=ref_cfg.n_positions, **opts)
