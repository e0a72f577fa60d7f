"""Glue between the benchmark's Jamba weights and the program's Jamba.

The benchmark makes the weights (``perfbench/reference/jamba.py``, from
the seed, a layer's from the seed and its index alone); this module only
says where each of them sits in the tree of
``easyparallellibrary_tpu.models.jamba.Jamba``, and which of them the
program keeps in another orientation (``A_log`` and the convolution's taps
are channel-minor there: the channels ride the lanes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import jamba

# program path inside a block -> (reference group, leaf, transposed?)
_MAMBA = {
    ("mamba", "in_proj", "kernel"): ("in_proj", False),
    ("mamba", "conv_w"): ("conv_w", True),
    ("mamba", "conv_b"): ("conv_b", False),
    ("mamba", "x_proj", "kernel"): ("x_proj", False),
    ("mamba", "dt_norm", "scale"): ("dt_norm", False),
    ("mamba", "b_norm", "scale"): ("b_norm", False),
    ("mamba", "c_norm", "scale"): ("c_norm", False),
    ("mamba", "dt_proj"): ("dt_proj", False),
    ("mamba", "dt_bias"): ("dt_bias", False),
    ("mamba", "A_log"): ("A_log", True),
    ("mamba", "D"): ("D", False),
    ("mamba", "out_proj", "kernel"): ("out_proj", False),
}
_ATTN = {("attn", n, "kernel"): (n, False) for n in ("q", "k", "v", "o")}
_FF = {
    ("norm_in", "scale"): "norm_in", ("norm_ff", "scale"): "norm_ff",
    ("mlp", "gate", "kernel"): "gate", ("mlp", "up", "kernel"): "up",
    ("mlp", "down", "kernel"): "down",
}


ref_config = jamba.JambaConfig.from_file


def _keys(path) -> tuple:
  return tuple(k.key for k in path
               if isinstance(k, jax.tree_util.DictKey))


def _place(name, value, leaf):
  if value.shape != leaf.shape:
    raise ValueError(f"{name}: reference {value.shape}, program "
                     f"{leaf.shape}")
  return value.astype(leaf.dtype)


def layer_to_program(mixer: dict, ff: dict, block_tree):
  """One block of the program's tree filled from the reference's weights
  of that layer (``init_mamba`` / ``init_attention`` and ``init_ff``)."""
  def pick(path, leaf):
    keys = _keys(path)
    if keys in _FF:
      return _place(keys, ff[_FF[keys]], leaf)
    table = _MAMBA if keys[0] == "mamba" else _ATTN
    if keys not in table:
      raise KeyError(f"the program has a parameter the reference lacks: "
                     f"{keys}")
    name, transposed = table[keys]
    value = mixer[name]
    return _place(keys, value.T if transposed else value, leaf)
  return jax.tree_util.tree_map_with_path(pick, block_tree)


def program_params(ref_cfg, key, shell):
  """The program's ``params`` (``shell``: any tree shaped like them, boxed
  or not, arrays or shapes) filled with the seeded weights, made ONE
  LAYER AT A TIME from the same per-layer keys as ``jamba.init_params``:
  the stacked reference tree never exists beside the program's (3.03B
  parameters twice would not leave room for the engine), and one small
  program a layer KIND is compiled, not 28 layers unrolled in one."""
  kinds = ref_cfg.layer_kinds()
  init = {jamba.MAMBA: jamba.init_mamba, jamba.ATTENTION: jamba.init_attention}
  k_embed, k_norm = jamba.top_keys(key)
  block_of = lambda i: shell[f"block_{i}"]
  make = {}
  for i, kind in enumerate(kinds):
    if kind not in make:
      make[kind] = jax.jit(lambda k_mix, k_ff, kind=kind, tree=block_of(i):
                           layer_to_program(init[kind](ref_cfg, k_mix),
                                            jamba.init_ff(ref_cfg, k_ff),
                                            tree))
  top = jax.jit(lambda: {
      "embed": jax.tree_util.tree_map(
          lambda leaf: _place("embed", jamba.init_embedding(ref_cfg, k_embed),
                              leaf), shell["embed"]),
      "norm_f": jax.tree_util.tree_map(
          lambda leaf: _place("norm_f", jamba._gain(
              k_norm, ref_cfg.hidden_size, ref_cfg.initializer_range), leaf),
          shell["norm_f"])})()
  out = dict(top)
  for i, kind in enumerate(kinds):
    out[f"block_{i}"] = make[kind](*jamba.layer_keys(key, i))
  if set(out) != set(shell):
    raise KeyError(f"the program's tree has {sorted(set(shell) - set(out))} "
                   "beyond what the reference fills")
  return out


def sum_of_squares(tree):
  """Sum of squares over every leaf, float32 accumulation: the checksum
  by which a run shows that program and reference started from the same
  weights (orientation does not enter it)."""
  return sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
             for leaf in jax.tree_util.tree_leaves(tree))


def model_config(ref_cfg, model_opts: dict):
  """The program's ``JambaConfig`` at the configuration's widths."""
  from easyparallellibrary_tpu.models.jamba import JambaConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  return JambaConfig(
      vocab_size=ref_cfg.vocab_size, num_layers=ref_cfg.num_hidden_layers,
      d_model=ref_cfg.hidden_size, d_ff=ref_cfg.intermediate_size,
      num_heads=ref_cfg.num_attention_heads,
      num_kv_heads=ref_cfg.num_key_value_heads,
      attn_layer_period=ref_cfg.attn_layer_period,
      attn_layer_offset=ref_cfg.attn_layer_offset,
      mamba_d_state=ref_cfg.mamba_d_state, mamba_d_conv=ref_cfg.mamba_d_conv,
      mamba_expand=ref_cfg.mamba_expand, mamba_dt_rank=ref_cfg.mamba_dt_rank,
      rms_norm_eps=ref_cfg.rms_norm_eps, max_seq_len=ref_cfg.n_positions,
      **opts)


def build_model(ref_cfg, model_opts: dict):
  """``(model, ids -> params shell)`` of the program under test; the
  shell holds shapes only (nothing is initialised)."""
  from easyparallellibrary_tpu.models.jamba import Jamba
  model = Jamba(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
