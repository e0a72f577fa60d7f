"""Glue between the benchmark's SmallThinker-21BA3B weights and the
program's ``SmallThinker``.

The benchmark makes the weights (``perfbench/reference/smallthinker.py``,
from the seed, a layer's from the seed and its index alone, an expert's
from its layer's key and its own index alone); this module only says where
each of them sits in the tree of
``easyparallellibrary_tpu.models.smallthinker.SmallThinker``, and which the
program keeps joined: an expert layer's gate and up matrices are ONE ``[E,
D, 2 F]`` stack there (gate columns, then up), so that a layer's experts
take two grouped matmuls and not three.  The program's router has no bias
and its tree none.

The reference HOLDS a layer's experts as their key (the check's logits over
the whole vocabulary at 14,848 positions leave no room for 6 GB of them:
the reference's module docstring), so the checksum by which a run shows
that both started from the same weights draws them again
(:func:`sum_of_squares` on the reference's tree; on the program's it is the
sum over its leaves).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import smallthinker as st
# How a leaf is placed and checked is the expert decoders' glue's.
from perfbench.runners.epl_glm4_moe_lite import _keys, _place

# program path inside a block -> the reference's leaf of that layer
_ATTENTION = {
    ("norm_in", "scale"): "norm_in", ("norm_ff", "scale"): "norm_ff",
    ("attn", "q", "kernel"): "q", ("attn", "k", "kernel"): "k",
    ("attn", "v", "kernel"): "v", ("attn", "o", "kernel"): "o",
}

ref_config = st.SmallThinkerConfig.from_file


def _moe_leaves(ref_cfg, ff: dict) -> dict:
  """The program's expert-layer leaves by name, the layer's experts drawn
  once from their key and stacked."""
  ex = jax.lax.map(ff["experts"].expert,
                   jnp.arange(ref_cfg.moe_num_primary_experts))
  return {("router_kernel",): ff["router"],
          ("experts_gate_up",): jnp.concatenate([ex["gate"], ex["up"]], -1),
          ("experts_down",): ex["down"]}


def layer_to_program(ref_cfg, att: dict, ff: dict, block_tree):
  """One block of the program's tree filled from the reference's weights
  of that layer (``init_attention``, ``init_ff`` and the experts its key
  draws)."""
  moe = _moe_leaves(ref_cfg, ff)

  def pick(path, leaf):
    keys = _keys(path)
    if keys in _ATTENTION:
      return _place(keys, att[_ATTENTION[keys]], leaf)
    if keys[0] == "moe":
      return _place(keys, moe[keys[1:]], leaf)
    raise KeyError(f"the program has a parameter the reference lacks: "
                   f"{keys}")
  return jax.tree_util.tree_map_with_path(pick, block_tree)


def program_params(ref_cfg, key, shell):
  """The program's ``params`` (``shell``: any tree shaped like them, boxed
  or not, arrays or shapes) filled with the seeded weights, made ONE LAYER
  AT A TIME from the same per-layer keys as ``st.init_params``; every layer
  is of one shape, so one small program is compiled, not 8 layers unrolled
  in one."""
  k_embed, k_head, k_norm = st.top_keys(key)
  make = jax.jit(
      lambda k_att, k_ff, tree=shell["block_0"]: layer_to_program(
          ref_cfg, st.init_attention(ref_cfg, k_att),
          st.init_ff(ref_cfg, k_ff), tree))
  fill = lambda name, make_value: jax.tree_util.tree_map(
      lambda leaf: _place(name, make_value(), leaf), shell[name])
  out = dict(jax.jit(lambda: {
      "embed": fill("embed", lambda: st.init_embedding(ref_cfg, k_embed)),
      "lm_head": fill("lm_head", lambda: st.init_head(ref_cfg, k_head)),
      "norm_f": fill("norm_f", lambda: st._gain(
          k_norm, ref_cfg.hidden_size, ref_cfg.initializer_range))})())
  for i in range(ref_cfg.num_hidden_layers):
    out[f"block_{i}"] = make(*st.layer_keys(key, i))
  if set(out) != set(shell):
    raise KeyError(f"the program's tree has {sorted(set(shell) - set(out))} "
                   "beyond what the reference fills")
  return out


def sum_of_squares(tree):
  """Sum of squares over every weight, float32 accumulation: the checksum
  by which a run shows that program and reference started from the same
  weights (joining gate and up does not enter it).  The reference's tree
  holds a layer's experts as their key (``st.HeldExperts``), which draws
  them again for its share of the sum."""
  held = lambda x: isinstance(x, st.HeldExperts)
  return sum(leaf.sum_of_squares() if held(leaf)
             else jnp.sum(jnp.square(leaf.astype(jnp.float32)))
             for leaf in jax.tree_util.tree_leaves(tree, is_leaf=held))


def model_config(ref_cfg, model_opts: dict):
  """The program's ``SmallThinkerConfig`` at the configuration's widths."""
  from easyparallellibrary_tpu.models.smallthinker import SmallThinkerConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  return SmallThinkerConfig(
      vocab_size=ref_cfg.vocab_size, d_model=ref_cfg.hidden_size,
      num_heads=ref_cfg.num_attention_heads,
      num_kv_heads=ref_cfg.num_key_value_heads, head_dim=ref_cfg.head_dim,
      moe_d_ff=ref_cfg.moe_ffn_hidden_size,
      n_routed_experts=ref_cfg.moe_num_primary_experts,
      num_experts_per_tok=ref_cfg.moe_num_active_primary_experts,
      sliding_window=ref_cfg.sliding_window_size,
      window_layout=ref_cfg.sliding_window_layout,
      rope_layout=ref_cfg.rope_layout, rope_theta=ref_cfg.rope_theta,
      norm_eps=ref_cfg.rms_norm_eps, max_seq_len=ref_cfg.n_positions, **opts)


def build_model(ref_cfg, model_opts: dict):
  """``(model, ids -> params shell)`` of the program under test; the
  shell holds shapes only (nothing is initialised)."""
  from easyparallellibrary_tpu.models.smallthinker import SmallThinker
  model = SmallThinker(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
