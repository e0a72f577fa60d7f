"""Glue between the benchmark's LFM2-8B-A1B weights and the program's
``Lfm2Moe``.

The benchmark makes the weights (``perfbench/reference/lfm2_moe.py``, from
the seed, a layer's from the seed and its index alone); this module only
says where each of them sits in the tree of
``easyparallellibrary_tpu.models.lfm2_moe.Lfm2Moe``, and which the program
keeps joined: an expert layer's gate and up matrices are ONE ``[E, D, 2
F]`` stack there (gate columns, then up), so that a layer's experts take
two grouped matmuls and not three.  The program's tree has no shared
expert and no head of its own (the embedding is the head).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import lfm2_moe as lfm
# Where a leaf goes is this family's; how it is placed and checked is the
# expert decoder's glue's (its ``_moe_leaf`` also knows a shared expert,
# which this tree lacks).
from perfbench.runners.epl_glm4_moe_lite import (  # noqa: F401
    _keys, _moe_leaf, _place, sum_of_squares)

# program path inside a block -> the reference's leaf of that layer's mixer
_MIXER = {
    ("norm_in", "scale"): "norm_in", ("norm_ff", "scale"): "norm_ff",
    ("conv", "in_proj", "kernel"): "in_proj", ("conv", "conv_w"): "conv_w",
    ("conv", "out_proj", "kernel"): "out_proj",
    ("attn", "q", "kernel"): "q", ("attn", "k", "kernel"): "k",
    ("attn", "v", "kernel"): "v", ("attn", "o", "kernel"): "o",
    ("attn", "q_norm", "scale"): "q_norm",
    ("attn", "k_norm", "scale"): "k_norm",
}
_MLP = {(n, "kernel"): n for n in ("gate", "up", "down")}


ref_config = lfm.Lfm2MoeConfig.from_file


def layer_to_program(layer: dict, block_tree):
  """One block of the program's tree filled from the reference's weights
  of that layer (``init_layer``)."""
  mix, ff = layer["mixer"], layer["ff"]

  def pick(path, leaf):
    keys = _keys(path)
    if keys in _MIXER:
      return _place(keys, mix[_MIXER[keys]], leaf)
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
  LAYER AT A TIME from the same per-layer keys as ``lfm.init_params``: the
  reference's tree never exists beside the program's (4.67B parameters
  twice do not fit a chip), and one small program a KIND of layer (its
  mixer and its feed-forward) is compiled, not 14 layers unrolled in
  one."""
  k_embed, k_norm = lfm.top_keys(key)
  make = {}
  for i, kind in enumerate(ref_cfg.layer_types):
    sort = (kind, ref_cfg.is_dense(i))
    if sort not in make:
      make[sort] = jax.jit(
          lambda k_mix, k_ff, sort=sort, tree=shell[f"block_{i}"]:
          layer_to_program(
              {"mixer": lfm.init_mixer(ref_cfg, k_mix, sort[0]),
               "ff": lfm.init_ff(ref_cfg, k_ff, sort[1])}, tree))
  fill = lambda name, make_value: jax.tree_util.tree_map(
      lambda leaf: _place(name, make_value(), leaf), shell[name])
  out = dict(jax.jit(lambda: {
      "embed": fill("embed", lambda: lfm.init_embedding(ref_cfg, k_embed)),
      "norm_f": fill("norm_f", lambda: lfm._gain(
          k_norm, ref_cfg.hidden_size, ref_cfg.initializer_range))})())
  for i, kind in enumerate(ref_cfg.layer_types):
    out[f"block_{i}"] = make[(kind, ref_cfg.is_dense(i))](
        *lfm.layer_keys(key, i))
  if set(out) != set(shell):
    raise KeyError(f"the program's tree has {sorted(set(shell) - set(out))} "
                   "beyond what the reference fills")
  return out


def model_config(ref_cfg, model_opts: dict):
  """The program's ``Lfm2MoeConfig`` at the configuration's widths."""
  from easyparallellibrary_tpu.models.lfm2_moe import Lfm2MoeConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  return Lfm2MoeConfig(
      vocab_size=ref_cfg.vocab_size, d_model=ref_cfg.hidden_size,
      d_ff=ref_cfg.intermediate_size,
      moe_d_ff=ref_cfg.moe_intermediate_size,
      num_heads=ref_cfg.num_attention_heads,
      num_kv_heads=ref_cfg.num_key_value_heads,
      conv_L_cache=ref_cfg.conv_L_cache, layer_types=ref_cfg.layer_types,
      num_dense_layers=ref_cfg.num_dense_layers,
      n_routed_experts=ref_cfg.num_experts, n_shared_experts=0,
      num_experts_per_tok=ref_cfg.num_experts_per_tok,
      routed_scaling_factor=ref_cfg.routed_scaling_factor,
      norm_topk_prob=ref_cfg.norm_topk_prob,
      route_norm_eps=lfm.ROUTE_NORM_EPS, rope_theta=ref_cfg.rope_theta,
      norm_eps=ref_cfg.norm_eps, max_seq_len=ref_cfg.n_positions, **opts)


def build_model(ref_cfg, model_opts: dict):
  """``(model, ids -> params shell)`` of the program under test; the
  shell holds shapes only (nothing is initialised)."""
  from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe
  model = Lfm2Moe(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
