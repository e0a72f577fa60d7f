"""Glue between the benchmark's dots3-note-prev weights and the program's
``Dots3Note``.

The benchmark makes the weights (``perfbench/reference/dots3_note.py``, from
the seed, a layer's from the seed and its index alone); this module only
says where each of them sits in the tree of
``easyparallellibrary_tpu.models.dots3_note.Dots3Note``, and which the
program keeps joined: an expert layer's gate and up matrices are ONE
``[held, D, 2 F]`` stack there (gate columns, then up).  Both sides hold the
same share of the experts (``experts_first``, ``n_routed_experts`` of the
router's ``router_width``) and the same slice of the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import dots3_note as dots

# program path inside a block -> the reference's leaf of that layer
_ATTENTION = {
    ("norm_in", "scale"): "norm_in", ("norm_ff", "scale"): "norm_ff",
    ("latent", "q_a", "kernel"): "q_a", ("latent", "q_norm", "scale"): "q_norm",
    ("latent", "q_b", "kernel"): "q_b", ("latent", "kv_a", "kernel"): "kv_a",
    ("latent", "kv_norm", "scale"): "kv_norm", ("latent", "kv_b"): "kv_b",
    ("latent", "o", "kernel"): "o", ("latent", "gate", "kernel"): "gate",
    ("latent", "index_q", "kernel"): "index_q",
    ("latent", "index_k", "kernel"): "index_k",
    ("latent", "index_k_norm", "scale"): "index_k_gain",
    ("latent", "index_k_norm", "bias"): "index_k_bias",
    ("latent", "index_w", "kernel"): "index_w",
}
_MLP = {(n, "kernel"): n for n in ("gate", "up", "down")}


ref_config = dots.Dots3NoteConfig.from_file


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


def layer_to_program(layer: dict, block_tree):
  """One block of the program's tree filled from the reference's weights
  of that layer (``init_layer``); every weight the reference made must
  find its place."""
  att, ff = layer["att"], layer["ff"]
  used = set()

  def pick(path, leaf):
    keys = _keys(path)
    if keys in _ATTENTION:
      used.add(_ATTENTION[keys])
      return _place(keys, att[_ATTENTION[keys]], leaf)
    if keys[0] == "mlp":
      return _place(keys, ff[_MLP[keys[1:]]], leaf)
    if keys[0] == "moe":
      return _place(keys, _moe_leaf(keys[1:], ff), leaf)
    raise KeyError(f"the program has a parameter the reference lacks: "
                   f"{keys}")

  out = jax.tree_util.tree_map_with_path(pick, block_tree)
  if used != set(att):
    raise KeyError(f"the reference has weights the program lacks: "
                   f"{sorted(set(att) - used)}")
  return out


def program_params(ref_cfg, key, shell):
  """The program's ``params`` (``shell``: any tree shaped like them, boxed
  or not, arrays or shapes) filled with the seeded weights, made ONE
  LAYER AT A TIME from the same per-layer keys as ``dots.init_params``
  (4.09B parameters twice do not fit a chip), one small program a layer
  kind (type x dense-or-expert)."""
  k_embed, k_head, k_norm = dots.top_keys(key)
  make = {}
  out = {}
  for i, layer_type in enumerate(ref_cfg.layer_types):
    kind = (layer_type, ref_cfg.is_dense(i))
    if kind not in make:
      # ``i`` only selects the layer's kind here; the keys are handed in.
      make[kind] = jax.jit(
          lambda k_att, k_ff, i=i, tree=shell[f"block_{i}"]:
          layer_to_program(
              {"att": dots.init_attention(ref_cfg, k_att,
                                          ref_cfg.layer_types[i]),
               "ff": (dots.init_dense_ff if ref_cfg.is_dense(i)
                      else dots.init_moe_ff)(ref_cfg, k_ff)}, tree))
    out[f"block_{i}"] = make[kind](*dots.layer_keys(key, i))
  fill = lambda name, make_value: jax.tree_util.tree_map(
      lambda leaf: _place(name, make_value(), leaf), shell[name])
  out.update(jax.jit(lambda: {
      "embed": fill("embed", lambda: dots.init_embedding(ref_cfg, k_embed)),
      "lm_head": fill("lm_head", lambda: dots.init_head(ref_cfg, k_head)),
      "norm_f": fill("norm_f", lambda: dots._gain(
          k_norm, ref_cfg.hidden_size, ref_cfg.initializer_range))})())
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
  """The program's ``Dots3NoteConfig`` at the configuration's widths and
  this chip's share."""
  from easyparallellibrary_tpu.models.dots3_note import Dots3NoteConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  full, swa = ref_cfg.full, ref_cfg.swa
  held = (None if ref_cfg.n_routed_experts == ref_cfg.router_width
          else (ref_cfg.experts_first, ref_cfg.n_routed_experts))
  return Dots3NoteConfig(
      vocab_size=ref_cfg.vocab_size, layer_types=ref_cfg.layer_types,
      d_model=ref_cfg.hidden_size, d_ff=ref_cfg.intermediate_size,
      moe_d_ff=ref_cfg.moe_intermediate_size,
      num_heads=full.heads, q_lora_rank=full.q_rank,
      kv_lora_rank=full.kv_rank, qk_nope_head_dim=full.nope,
      qk_rope_head_dim=full.rope, v_head_dim=full.value,
      rope_theta=full.theta, index_n_heads=ref_cfg.index_n_heads,
      index_head_dim=ref_cfg.index_head_dim, index_topk=ref_cfg.index_topk,
      sliding_window=ref_cfg.sliding_window_size,
      swa_num_heads=swa.heads, swa_q_lora_rank=swa.q_rank,
      swa_kv_lora_rank=swa.kv_rank, swa_qk_nope_head_dim=swa.nope,
      swa_qk_rope_head_dim=swa.rope, swa_v_head_dim=swa.value,
      swa_rope_theta=swa.theta, n_routed_experts=ref_cfg.router_width,
      experts_held=held, n_shared_experts=ref_cfg.n_shared_experts,
      num_experts_per_tok=ref_cfg.num_experts_per_tok,
      first_k_dense=ref_cfg.first_k_dense_replace,
      routed_scaling_factor=ref_cfg.routed_scaling_factor,
      norm_topk_prob=ref_cfg.norm_topk_prob,
      rms_norm_eps=ref_cfg.rms_norm_eps, max_seq_len=ref_cfg.n_positions,
      **opts)


def build_model(ref_cfg, model_opts: dict):
  """``(model, ids -> params shell)`` of the program under test; the
  shell holds shapes only (nothing is initialised)."""
  from easyparallellibrary_tpu.models.dots3_note import Dots3Note
  model = Dots3Note(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
