"""Glue between the benchmark's GLM-5 weights and the program's ``GlmMoe``
with its indexer on, on one chip or on a mesh that divides the host's
experts over its ``expert`` axis.

The benchmark makes the weights (``perfbench/reference/glm_moe_dsa.py``,
from the seed, a layer's from the seed and its index alone, an expert's
from its layer's key and its own index alone); this module only says where
each of them sits in the tree of
``easyparallellibrary_tpu.models.glm_moe.GlmMoe``, which the program keeps
joined (an expert layer's gate and up matrices are ONE ``[held, D, 2 F]``
stack there, gate columns, then up), and WHERE each lies: with the cell's
``epl_config`` naming a mesh whose ``expert`` axis holds several chips
(``cluster.mesh_shape: expert:4``), everything is whole on every chip but
the routed experts' stacks, of which chip ``j`` draws and keeps the
``j``-th run (``experts_first + 16 j ..``: the stacks of a layer never
exist whole anywhere).  Building that mesh here is also what makes the
serving engine adopt it (it takes the ambient mesh once one is built).

The reference HOLDS a layer's experts as their key, so the checksum by
which a run shows that both started from the same weights draws them again
(:func:`sum_of_squares` on the reference's tree; on the program's it is the
sum over its leaves, wherever they lie).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench.reference import glm_moe_dsa as glm
# How a leaf is placed and checked is the expert decoders' glue's.
from perfbench.runners.epl_glm4_moe_lite import _keys, _place

AXIS = "expert"
STACKS = (("moe", "experts_gate_up"), ("moe", "experts_down"))

# program path inside a block -> the reference's leaf of that layer
_ATTENTION = {
    ("norm_in", "scale"): "norm_in", ("norm_ff", "scale"): "norm_ff",
    ("latent", "q_a", "kernel"): "q_a", ("latent", "q_norm", "scale"): "q_norm",
    ("latent", "q_b", "kernel"): "q_b", ("latent", "kv_a", "kernel"): "kv_a",
    ("latent", "kv_norm", "scale"): "kv_norm", ("latent", "kv_b"): "kv_b",
    ("latent", "o", "kernel"): "o",
    ("latent", "index_q", "kernel"): "index_q",
    ("latent", "index_k", "kernel"): "index_k",
    ("latent", "index_k_norm", "scale"): "index_k_gain",
    ("latent", "index_k_norm", "bias"): "index_k_bias",
    ("latent", "index_w", "kernel"): "index_w",
}
_MLP = {(n, "kernel"): n for n in ("gate", "up", "down")}

ref_config = glm.GlmMoeDsaConfig.from_file


def expert_mesh():
  """The ambient mesh where the run's ``epl_config`` divides the host over
  an ``expert`` axis of several chips (built here if nothing has built it),
  else ``None``: one chip holds everything."""
  from easyparallellibrary_tpu.env import Env
  cluster = Env.get().cluster
  if cluster is None or not Env.get().config.cluster.mesh_shape:
    return None
  mesh = cluster.mesh
  return mesh if dict(zip(mesh.axis_names, mesh.devices.shape)).get(
      AXIS, 1) > 1 else None


def _stacks(ref_cfg, experts: glm.HeldExperts, first, count: int) -> dict:
  """The two stacks of the ``count`` experts from ``first``, each drawn
  once from the layer's experts key."""
  ex = jax.lax.map(experts.expert, first + jnp.arange(count))
  return {STACKS[0]: jnp.concatenate([ex["gate"], ex["up"]], -1),
          STACKS[1]: ex["down"]}


def _held_stacks(ref_cfg, experts: glm.HeldExperts, mesh) -> dict:
  """The host's stacks; over a mesh each chip draws its own run of them."""
  first, count = ref_cfg.experts_held
  if mesh is None:
    return _stacks(ref_cfg, experts, first, count)
  chips = dict(zip(mesh.axis_names, mesh.devices.shape))[AXIS]
  if count % chips:
    raise ValueError(f"{count} held experts over {chips} chips")
  per = count // chips
  draw = lambda key_data: _stacks(
      ref_cfg, glm.HeldExperts(key_data, ref_cfg),
      first + per * jax.lax.axis_index(AXIS), per)
  return jax.shard_map(draw, mesh=mesh, in_specs=P(),
                       out_specs={k: P(AXIS) for k in STACKS},
                       check_vma=False)(experts.key_data)


def layer_to_program(ref_cfg, att: dict, ff: dict, block_tree, mesh=None):
  """One block of the program's tree filled from the reference's weights
  of that layer; every weight the reference made must find its place."""
  used = set()
  stacks = None if "experts" not in ff else _held_stacks(
      ref_cfg, ff["experts"], mesh)

  def moe_leaf(keys):
    if keys[1] == "shared":
      return ff["shared"][_MLP[keys[2:]]]
    if keys in STACKS:
      return stacks[keys]
    return {"router_kernel": ff["router"],
            "e_score_correction_bias": ff["bias"]}[keys[1]]

  def pick(path, leaf):
    keys = _keys(path)
    if keys in _ATTENTION:
      used.add(_ATTENTION[keys])
      return _place(keys, att[_ATTENTION[keys]], leaf)
    if keys[0] == "mlp":
      return _place(keys, ff[_MLP[keys[1:]]], leaf)
    if keys[0] == "moe":
      return _place(keys, moe_leaf(keys), leaf)
    raise KeyError(f"the program has a parameter the reference lacks: "
                   f"{keys}")

  out = jax.tree_util.tree_map_with_path(pick, block_tree)
  if used != set(att):
    raise KeyError(f"the reference has weights the program lacks: "
                   f"{sorted(set(att) - used)}")
  return out


def _placement(tree, mesh):
  """Where each leaf of a (sub)tree of the program's parameters lies on
  ``mesh``: the routed experts' stacks divided, all else whole."""
  return jax.tree_util.tree_map_with_path(
      lambda path, leaf: NamedSharding(
          mesh, P(AXIS) if _keys(path)[-2:] in STACKS else P()), tree)


def program_params(ref_cfg, key, shell):
  """The program's ``params`` (``shell``: a tree shaped like them, arrays
  or shapes) filled with the seeded weights, made ONE LAYER AT A TIME from
  the same per-layer keys as ``glm.init_params``, one small program a
  layer kind (dense or expert), each leaf made where it will lie
  (:func:`expert_mesh`)."""
  from flax import linen as nn
  shell = nn.meta.unbox(shell)
  mesh = expert_mesh()
  jit = lambda fn, tree: jax.jit(fn) if mesh is None else jax.jit(
      fn, out_shardings=_placement(tree, mesh))
  k_embed, k_head, k_norm = glm.top_keys(key)
  make, out = {}, {}
  for i in range(ref_cfg.num_hidden_layers):
    dense = ref_cfg.is_dense(i)
    if dense not in make:
      tree = shell[f"block_{i}"]
      make[dense] = jit(
          lambda k_att, k_ff, dense=dense, tree=tree: layer_to_program(
              ref_cfg, glm.init_attention(ref_cfg, k_att),
              (glm.init_dense_ff if dense else glm.init_moe_ff)(
                  ref_cfg, k_ff), tree, mesh), tree)
    out[f"block_{i}"] = make[dense](*glm.layer_keys(key, i))
  fill = lambda name, make_value: jax.tree_util.tree_map(
      lambda leaf: _place(name, make_value(), leaf), shell[name])
  top = {name: shell[name] for name in ("embed", "lm_head", "norm_f")}
  out.update(jit(lambda: {
      "embed": fill("embed", lambda: glm.init_embedding(ref_cfg, k_embed)),
      "lm_head": fill("lm_head", lambda: glm.init_head(ref_cfg, k_head)),
      "norm_f": fill("norm_f", lambda: glm._gain(
          k_norm, ref_cfg.hidden_size, ref_cfg.initializer_range))}, top)())
  if set(out) != set(shell):
    raise KeyError(f"the program's tree has {sorted(set(shell) - set(out))} "
                   "beyond what the reference fills")
  return out


def sum_of_squares(tree):
  """Sum of squares over every weight, float32 accumulation: the checksum
  by which a run shows that program and reference started from the same
  weights (joining gate and up does not enter it).  The reference's tree
  holds a layer's experts as their key (``glm.HeldExperts``), which draws
  the host's share again for its part of the sum."""
  held = lambda x: isinstance(x, glm.HeldExperts)
  return sum(leaf.sum_of_squares() if held(leaf)
             else jnp.sum(jnp.square(leaf.astype(jnp.float32)))
             for leaf in jax.tree_util.tree_leaves(tree, is_leaf=held))


def model_config(ref_cfg, model_opts: dict):
  """The program's ``GlmMoeConfig`` at the configuration's widths, its
  indexer on, holding the host's share of the experts."""
  from easyparallellibrary_tpu.models.glm_moe import GlmMoeConfig
  opts = dict(model_opts)
  for key in ("dtype", "param_dtype"):
    if key in opts:
      opts[key] = jnp.dtype(opts[key]).type
  held = (None if ref_cfg.n_routed_experts == ref_cfg.router_width
          else ref_cfg.experts_held)
  return GlmMoeConfig(
      vocab_size=ref_cfg.vocab_size, num_layers=ref_cfg.num_hidden_layers,
      d_model=ref_cfg.hidden_size, d_ff=ref_cfg.intermediate_size,
      moe_d_ff=ref_cfg.moe_intermediate_size, num_heads=ref_cfg.heads,
      q_lora_rank=ref_cfg.q_rank, kv_lora_rank=ref_cfg.kv_rank,
      qk_nope_head_dim=ref_cfg.nope, qk_rope_head_dim=ref_cfg.rope,
      v_head_dim=ref_cfg.value, rope_theta=ref_cfg.theta,
      index_topk=ref_cfg.index_topk, index_n_heads=ref_cfg.index_n_heads,
      index_head_dim=ref_cfg.index_head_dim,
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
  from easyparallellibrary_tpu.models.glm_moe import GlmMoe
  model = GlmMoe(model_config(ref_cfg, model_opts))
  return model, lambda ids: jax.eval_shape(
      lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
