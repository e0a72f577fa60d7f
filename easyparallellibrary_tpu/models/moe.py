"""Mixture-of-Experts layer — expert parallelism over the ``expert`` axis.

TPU-native redesign of the reference's MoE support: the reference hooks
``tf.einsum`` inside a ``split`` scope and injects NCCL AllToAll around
every 3rd einsum (the dispatch/combine pair;
epl/parallel/hooks.py:758-794, NUM_EINSUM_IN_SPLIT_FOR_MOE=3 in
epl/utils/constant.py:106) — an implicit pattern-match the survey calls
out as a hack.  Here the layer contract is explicit:

  * router → top-1 (Switch) or top-2 gating with a capacity bound,
  * dispatch/combine expressed as einsums against a [tokens, E, C]
    dispatch mask; with expert-dim tensors sharded ``P("expert", ...)``,
    GSPMD lowers those einsums into exactly the all-to-alls the reference
    inserts by hand (the `jax.lax.all_to_all` analog of its NCCL kernels,
    csrc/communicators/nccl_all_to_all.cc),
  * expert weights [E, d_model, d_ff] are sharded over the expert axis
    (and their inner dims over the model axis when tensor_parallel),
  * overflow tokens beyond capacity are dropped (standard Switch
    semantics); a load-balancing auxiliary loss is sown into the
    ``losses`` collection.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants


from easyparallellibrary_tpu.utils.sharding import constrain as _constrain  # noqa: E402

# Once-per-process latch for the einsum-MoE perf-cliff advisory below.
_EINSUM_CLIFF_WARNED = [False]


def _expert_token_sharding(x) -> "bool | None":
  """Inspect ``x``'s committed sharding: True = token dims (everything
  but the trailing feature dim) are positively NOT split over the expert
  axis (replicated over the expert group); False = they ARE
  expert-split; None = uninspectable (a tracer without a committed
  sharding — the common case under jit on older jax)."""
  sharding = getattr(x, "sharding", None)
  spec = getattr(sharding, "spec", None)
  if spec is None:
    return None
  for entry in tuple(spec)[:max(getattr(x, "ndim", 1) - 1, 0)]:
    axes = entry if isinstance(entry, (tuple, list)) else (entry,)
    if constants.EXPERT_AXIS in axes:
      return False
  return True


def _top_k_dispatch(probs, top_k: int, E: int, capacity: int, dtype):
  """Shared top-k routing -> (dispatch [T,E,C], combine [T,E,C], assign).

  `assign` is the PRE-capacity router choice mask (for the aux loss:
  with post-drop counts, the worse the overflow, the weaker the penalty
  would look)."""
  dispatch_list, combine_list, assign_list = [], [], []
  remaining = probs
  fill = jnp.zeros((E,), jnp.int32)
  for _ in range(top_k):
    gate = jnp.max(remaining, axis=-1)                   # [T]
    idx = jnp.argmax(remaining, axis=-1)                 # [T]
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)     # [T, E]
    assign_list.append(onehot)
    # Position of each token within its expert queue (0-based), offset
    # by tokens already placed in earlier choices.
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot + fill[None, :]
    keep = (pos < capacity) * onehot                     # [T, E]
    pos_in_cap = jnp.sum(pos * keep, axis=-1)            # [T]
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos_in_cap, capacity, dtype=jnp.int32)[:, None, :]  # [T, E, C]
    dispatch_list.append(dispatch)
    combine_list.append(dispatch.astype(jnp.float32) *
                        gate[:, None, None])
    fill = fill + jnp.sum(keep, axis=0)
    remaining = remaining * (1 - jax.nn.one_hot(idx, E))
  return (sum(dispatch_list).astype(dtype),
          sum(combine_list).astype(dtype),
          sum(assign_list))


class MoEMLP(nn.Module):
  """Drop-in replacement for the dense MLP block (same in/out shape).

  ``impl``:
    * "einsum" (default) — dispatch/combine as einsums against the
      [T, E, C] mask with expert-sharded tensors; GSPMD chooses the
      collectives (on token-replicated expert groups it picks
      local-compute + reductions, no all-to-all needed).
    * "a2a" — EXPLICIT expert-parallel dispatch: tokens sharded over the
      expert axis, routed locally, exchanged with two
      ``jax.lax.all_to_all`` rounds (dispatch + combine) inside a
      partial-manual shard_map.  This is the reference's M6-style EP
      dataflow (NCCL AllToAll around the expert einsums,
      epl/parallel/hooks.py:758-794 + csrc/communicators/
      nccl_all_to_all.cc) — use it when tokens live distributed across
      the expert group; capacity is enforced per SOURCE device
      (ceil(cf * T_local / E) each), so drops can differ from the
      einsum path's global bound under cross-device routing imbalance.
  """

  cfg: Any                       # GPTConfig
  top_k: int = 1
  impl: str = "einsum"

  @nn.compact
  def __call__(self, x):
    if self.impl not in ("einsum", "a2a"):
      raise ValueError(f"MoEMLP.impl must be einsum|a2a: {self.impl!r}")
    if self.impl == "a2a":
      return self._a2a_path(x)
    return self._einsum_path(x)

  def _einsum_path(self, x):
    cfg = self.cfg
    B, S, D = x.shape
    E = cfg.num_experts
    F = cfg.d_ff
    T = B * S

    # Perf-cliff flag (docs/parallelism.md "Expert parallelism"): with
    # tokens replicated over the expert group, GSPMD lowers the
    # dispatch/combine einsums to local-compute + reductions, NOT
    # all-to-alls: every expert-group member touches every token, so EP
    # stops scaling compute with the expert axis.  moe_impl="a2a"
    # enforces distributed tokens.
    # Fires ONCE per process.  The ACTUAL token sharding is inspected
    # first: a batch genuinely sharded over the expert axis suppresses
    # the advisory entirely; a positively-replicated sharding fires the
    # definite message; an uninspectable tracer (jit without committed
    # input shardings) fires the hedged "IF" form once — never the old
    # per-layer/per-trace spam.
    from easyparallellibrary_tpu.env import Env
    env = Env.get()
    if not _EINSUM_CLIFF_WARNED[0] and env.cluster is not None \
        and env.cluster._mesh is not None:
      sizes = dict(zip(env.cluster.mesh.axis_names,
                       env.cluster.mesh.devices.shape))
      replicated = _expert_token_sharding(x)
      if sizes.get(constants.EXPERT_AXIS, 1) > 1 and replicated is not False:
        _EINSUM_CLIFF_WARNED[0] = True
        from easyparallellibrary_tpu.utils.logging import get_logger
        get_logger().info(
            "MoE impl='einsum' on an expert axis of size %d: %s "
            "GSPMD local-computes dispatch/combine with no all-to-all — "
            "every expert-group member touches every token.  Shard the "
            "batch over ('data','expert') or use moe_impl='a2a' for "
            "distributed-token expert parallelism.  See "
            "docs/parallelism.md.  (Logged once per process.)",
            sizes[constants.EXPERT_AXIS],
            "tokens are replicated over the expert group:" if replicated
            else "IF tokens are replicated over the expert group "
                 "(the default when the batch shards over 'data' alone),")
    capacity = max(self.top_k, int(
        math.ceil(T / E * cfg.capacity_factor)))

    tokens = x.reshape(T, D)

    # --- Router (fp32 for stable softmax) --------------------------------
    router_kernel = self.param(
        "router_kernel",
        nn.with_partitioning(nn.initializers.normal(stddev=0.02),
                             (None, None)),
        (D, E), jnp.float32)
    router_logits = jnp.matmul(tokens.astype(jnp.float32),
                               router_kernel)              # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)

    # --- Top-k dispatch mask with capacity -------------------------------
    dispatch_mask, combine_mask, assign = _top_k_dispatch(
        probs, self.top_k, E, capacity, x.dtype)            # [T, E, C]

    # --- Dispatch: [T,D] x [T,E,C] -> [E,C,D] (GSPMD: all-to-all) --------
    expert_in = jnp.einsum("td,tec->ecd", tokens, dispatch_mask)
    expert_in = _constrain(
        expert_in, P(constants.EXPERT_AXIS, None, None))

    # --- Expert FFN ------------------------------------------------------
    model_axis = constants.MODEL_AXIS if cfg.tensor_parallel else None
    wi = self.param(
        "wi", nn.with_partitioning(nn.initializers.lecun_normal(),
                                   (constants.EXPERT_AXIS, None, model_axis)),
        (E, D, F), cfg.param_dtype)
    wo = self.param(
        "wo", nn.with_partitioning(nn.initializers.lecun_normal(),
                                   (constants.EXPERT_AXIS, model_axis, None)),
        (E, F, D), cfg.param_dtype)
    h = jnp.einsum("ecd,edf->ecf", expert_in, jnp.asarray(wi, x.dtype))
    h = nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, jnp.asarray(wo, x.dtype))
    expert_out = _constrain(
        expert_out, P(constants.EXPERT_AXIS, None, None))

    # --- Combine: [E,C,D] x [T,E,C] -> [T,D] (GSPMD: all-to-all back) ----
    out = jnp.einsum("ecd,tec->td", expert_out, combine_mask)

    # --- Load-balancing aux loss (Switch eq. 4) --------------------------
    # Uses the router's PRE-capacity assignments: with post-drop counts,
    # the worse the overflow, the weaker the penalty would look.
    frac_tokens = jnp.mean(assign.astype(jnp.float32), axis=0)    # [E]
    frac_probs = jnp.mean(probs, axis=0)                          # [E]
    aux = E * jnp.sum(frac_tokens * frac_probs)
    self.sow("losses", "moe_aux_loss", aux,
             init_fn=lambda: jnp.float32(0),
             reduce_fn=lambda a, b: a + b)

    return out.reshape(B, S, D)

  def _a2a_path(self, x):
    """Explicit expert-parallel dispatch via two all_to_all rounds."""
    from easyparallellibrary_tpu.env import Env

    cfg = self.cfg
    B, S, D = x.shape
    E = cfg.num_experts
    F = cfg.d_ff
    T = B * S
    mesh = Env.get().cluster.mesh
    if constants.EXPERT_AXIS not in mesh.axis_names:
      raise ValueError(
          f"moe_impl='a2a' requires a mesh with an "
          f"{constants.EXPERT_AXIS!r} axis (got {mesh.axis_names}); "
          f"build it via Cluster.build_mesh(expert=N)")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = sizes[constants.EXPERT_AXIS]
    if E % ep:
      raise ValueError(f"num_experts {E} must divide the expert axis {ep}")
    if T % ep:
      raise ValueError(f"tokens per step {T} must divide the expert axis "
                       f"{ep} (a2a dispatch shards tokens over it)")
    t_loc = T // ep
    E_loc = E // ep
    # Per-SOURCE-device capacity; total receive buffer per expert is
    # ep * C ~= capacity_factor * T / E (the einsum path's global bound).
    C = max(self.top_k, int(math.ceil(t_loc / E * cfg.capacity_factor)))

    router_kernel = self.param(
        "router_kernel",
        nn.with_partitioning(nn.initializers.normal(stddev=0.02),
                             (None, None)),
        (D, E), jnp.float32)
    model_axis = constants.MODEL_AXIS if cfg.tensor_parallel else None
    wi = self.param(
        "wi", nn.with_partitioning(
            nn.initializers.lecun_normal(),
            (constants.EXPERT_AXIS, None, model_axis)),
        (E, D, F), cfg.param_dtype)
    wo = self.param(
        "wo", nn.with_partitioning(
            nn.initializers.lecun_normal(),
            (constants.EXPERT_AXIS, model_axis, None)),
        (E, F, D), cfg.param_dtype)

    top_k, dtype = self.top_k, x.dtype

    def local_moe(x_loc, rk, wi_loc, wo_loc):
      # x_loc: [t_loc, D] this device's token shard; wi/wo: local expert
      # slices [E_loc, D, F] / [E_loc, F, D].
      probs = jax.nn.softmax(
          jnp.matmul(x_loc.astype(jnp.float32), rk), axis=-1)
      dispatch, combine, assign = _top_k_dispatch(
          probs, top_k, E, C, dtype)                       # [t_loc, E, C]

      # Dispatch round: pack per-destination-expert buffers and exchange.
      buf = jnp.einsum("td,tec->ecd", x_loc, dispatch)     # [E, C, D]
      buf = buf.reshape(ep, E_loc, C, D)
      recv = jax.lax.all_to_all(buf, constants.EXPERT_AXIS, 0, 0,
                                tiled=False)               # [ep, E_loc, C, D]
      # Local experts over all peers' tokens: [E_loc, ep*C, D].
      h = jnp.einsum("egd,edf->egf",
                     recv.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, D),
                     jnp.asarray(wi_loc, dtype))
      h = nn.gelu(h)
      y = jnp.einsum("egf,efd->egd", h, jnp.asarray(wo_loc, dtype))
      # Combine round: send results back to the source devices.
      y = y.reshape(E_loc, ep, C, D).transpose(1, 0, 2, 3)
      back = jax.lax.all_to_all(y, constants.EXPERT_AXIS, 0, 0,
                                tiled=False)               # [ep, E_loc, C, D]
      out = jnp.einsum("ecd,tec->td", back.reshape(E, C, D), combine)

      # Aux loss over GLOBAL routing statistics: pmean the fractions
      # FIRST, then form the product — mean-of-products would diverge
      # from the einsum path whenever routing varies across the token
      # shards (equal token counts make the pmean the exact global mean).
      frac_tokens = jax.lax.pmean(
          jnp.mean(assign.astype(jnp.float32), axis=0),
          constants.EXPERT_AXIS)
      frac_probs = jax.lax.pmean(jnp.mean(probs, axis=0),
                                 constants.EXPERT_AXIS)
      aux = E * jnp.sum(frac_tokens * frac_probs)
      return out, aux

    # Inside a manual region (the smap pipeline engines) the nested map
    # must be built against the ABSTRACT context mesh — the concrete
    # Mesh has no Manual axis types and shard_map rejects the mismatch.
    # The engines run stage compute branch-uniformly for this
    # composition (models/gpt.py), so the nested map's whole-mesh
    # collective channels are never gated.
    from easyparallellibrary_tpu.utils.compat import shard_map
    from easyparallellibrary_tpu.utils.sharding import manual_axes
    get_abstract_mesh = getattr(jax.sharding, "get_abstract_mesh", None)
    smap_mesh = (get_abstract_mesh()
                 if manual_axes() and get_abstract_mesh is not None
                 else mesh)
    mapped = shard_map(
        local_moe, mesh=smap_mesh,
        in_specs=(P(constants.EXPERT_AXIS), P(),
                  P(constants.EXPERT_AXIS), P(constants.EXPERT_AXIS)),
        out_specs=(P(constants.EXPERT_AXIS), P()),
        manual_axes=frozenset({constants.EXPERT_AXIS}),
        check=False)
    # jit here is inlined under an outer jit; it also makes EAGER
    # evaluation (flax init) work — jax 0.9's eager shard_map
    # mis-validates out_specs when axis_names is a subset of the mesh.
    # epl-lint: disable=recompile-hazard — inlined under the outer jit
    # (traced once per outer compile); the eager path is init-only
    out, aux = jax.jit(mapped)(x.reshape(T, D), router_kernel, wi, wo)
    self.sow("losses", "moe_aux_loss", aux,
             init_fn=lambda: jnp.float32(0),
             reduce_fn=lambda a, b: a + b)
    return out.reshape(B, S, D)
