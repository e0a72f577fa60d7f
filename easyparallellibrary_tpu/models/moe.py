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

That is :class:`MoEMLP`, the TRAINING layer.  The SERVING layer is
:class:`DroplessMoE` at the end of this module (models/glm_moe.py and
models/lfm2_moe.py use it): routed experts beside shared ones, if any, no
capacity and no dropped token.  A step's positions are flattened,
each live one is assigned its ``top_k`` experts (:func:`noaux_tc_route`:
sigmoid scores, a selection bias that enters the choice and not the
weights), the assignments are sorted by expert (:func:`sort_by_expert`),
and two grouped matmuls a layer (kernels/moe_gmm.py: gate and up as one,
then down) multiply each expert's rows by its own matrices; the results
are weighted and gathered back.  Every shape is fixed by ``positions x
top_k``, so the fused step compiles once; a dead position (beyond a
slot's ``num_valid``, an idle slot) is assigned to NO expert: its rows
sort behind the last group and are not multiplied.  On a serving engine
divided over the ``expert`` axis (``DroplessMoE(expert_axis=)``) the same
layer exchanges rows between the chips that hold its experts, still
without capacity (:func:`exchanged_experts`).
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.models.blocks import GatedMLP, boxed
from easyparallellibrary_tpu.ops.layers import HeldParams


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


# ------------------------------------------------------ dropless serving --


def noaux_tc_route(x, router_kernel, bias, top_k: int, scale: float,
                   norm: bool = True, norm_eps: float = 1e-20):
  """The ``noaux_tc`` router with one group (DeepSeek-V3's, as GLM-4.7
  configures it: ``n_group`` 1, ``topk_group`` 1; LFM2's
  ``use_expert_bias`` router is the same rule): ``s = sigmoid(x W_g)``
  in float32 whatever ``x``'s dtype; the ``top_k`` largest of ``s + bias``
  are CHOSEN (the bias steers the choice only); their weights are the
  unbiased ``s``, normalised over the chosen (``norm``: divided by their
  sum plus ``norm_eps``, GLM's 1e-20, LFM2's 1e-6) and times ``scale``.
  ``x`` ``[N, D]`` -> ``(chosen int32 [N, top_k], weights float32 [N,
  top_k])``."""
  scores = jax.nn.sigmoid(jnp.matmul(
      x.astype(jnp.float32), router_kernel.astype(jnp.float32),
      precision=jax.lax.Precision.HIGHEST))
  _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
  weights = jnp.take_along_axis(scores, chosen, axis=-1)
  if norm:
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + norm_eps)
  return chosen.astype(jnp.int32), weights * scale


def softmax_topk_route(x, router_kernel, top_k: int):
  """A softmax router with no bias (models/smallthinker.py): ``r = x W_r``
  in float32 whatever ``x``'s dtype; the ``top_k`` largest LOGITS are
  chosen; their weights are the softmax over the chosen logits alone
  (equal to the softmax over all of them renormalised over the chosen).
  ``x`` ``[N, D]`` -> ``(chosen int32 [N, top_k], weights float32 [N,
  top_k])``."""
  logits = jnp.matmul(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
  top, chosen = jax.lax.top_k(logits, top_k)
  return chosen.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def sort_by_expert(chosen, live, num_experts: int, first: int = 0):
  """Sort a step's ``N x top_k`` assignments by expert.  ``chosen`` int32
  ``[N, top_k]``, ``live`` bool ``[N]`` (``None``: every position).  A
  dead position's assignments go to expert ``num_experts``, which does
  not exist: they sort behind the last group and count in no group size.
  ``num_experts`` counts the experts HELD here, the router's experts
  ``[first, first + num_experts)``: an assignment to an expert outside
  them is dead in the same way (another chip's work).
  Returns ``(order, group_sizes)``: ``order`` int32 ``[N * top_k]``, the
  flat assignment (position ``// top_k``, choice ``% top_k``) at each
  sorted row, stable; ``group_sizes`` int32 ``[num_experts]``, summing to
  the live assignments that fell on held experts ((live positions) x
  ``top_k`` where all are held)."""
  flat = chosen.reshape(-1)
  if first:
    flat = flat - first
  if live is not None:
    flat = jnp.where(jnp.repeat(live, chosen.shape[1]), flat, num_experts)
  if first:
    # An expert below the held range; one above it already sorts behind
    # the last group and counts in no group size.
    flat = jnp.where(flat < 0, num_experts, flat)
  order = jnp.argsort(flat, stable=True).astype(jnp.int32)
  # Rows up to and including each expert's: one fused compare-and-count
  # (a binary search would be a serial loop of scalar steps on a TPU).
  ends = jnp.sum(flat[None, :] <= jnp.arange(num_experts)[:, None], axis=1,
                 dtype=jnp.int32)
  return order, jnp.diff(ends, prepend=0)


def _up(up, h):
  """An expert's up projection through what the model does to it before
  the product (``cfg.expert_up``; ``None``: nothing)."""
  return h if up is None else up(h)


def dropless_experts(x, chosen, weights, live, w_gate_up, w_down,
                     impl: Optional[str] = None, first: int = 0,
                     gate=jax.nn.silu, up=None):
  """``sum_i weights[n, i] * Expert_{chosen[n, i]}(x[n])`` for the live
  positions of ``x`` ``[N, D]``, each expert a gated MLP, ``W_down
  (gate(x W_gate) * up(x W_up))`` (``gate``: SiLU; models/smallthinker.py's
  is ReLU; ``up``: nothing; models/gigachat.py clamps both): ``w_gate_up``
  ``[E, D, 2 F]`` (gate columns, then up),
  ``w_down`` ``[E, F, D]``.  The stacks hold the router's experts ``[first, first + E)``;
  the sum runs over the chosen experts among them (an absent expert's
  term is another chip's: nothing stands in for it).  Returns ``(y [N,
  D]`` in ``x``'s dtype, zeros at dead positions, ``group_sizes [E])``.
  ``impl`` names the grouped matmul's
  lowering (kernels/moe_gmm.py; ``None`` resolves it from the shapes)."""
  from easyparallellibrary_tpu.kernels.moe_gmm import moe_gmm
  N, k = chosen.shape
  E, _, F2 = w_gate_up.shape
  order, sizes = sort_by_expert(chosen, live, E, first)
  rows = x[order // k]                                    # [N k, D]
  h = moe_gmm(rows, w_gate_up, sizes, impl=impl)
  h = gate(h[:, :F2 // 2]) * _up(up, h[:, F2 // 2:])
  out = moe_gmm(h, w_down, sizes, impl=impl)              # [N k, D]
  # Back to position order: the inverse of a permutation is its argsort
  # (a second small sort; a scatter is a serial loop on a TPU), then a
  # gather of the rows.
  out = out[jnp.argsort(order)].reshape(N, k, -1).astype(jnp.float32)
  y = jnp.sum(out * weights[..., None], axis=1)
  if live is not None:
    y = jnp.where(live[:, None], y, 0.0)
  return y.astype(x.dtype), sizes


def exchange_rows(positions: int, top_k: int, experts_here: int,
                  router_width: int) -> int:
  """Rows a chip SENDS each chip of the axis in one round of
  :func:`exchanged_experts`: half again what ``positions`` live positions
  send it on average (``top_k x experts_here / router_width`` of their
  assignments), up to a multiple of the grouped matmul's 128-row tile.  A
  size, not a capacity: what exceeds it goes in a further round."""
  mean = positions * top_k * experts_here / router_width
  return max(128, -(-int(1.5 * mean) // 128) * 128)


def exchanged_experts(x, chosen, weights, live, w_gate_up, w_down,
                      axis: str, rows: int, impl: Optional[str] = None,
                      first: int = 0, gate=jax.nn.silu, up=None):
  """:func:`dropless_experts` with the held experts DIVIDED over the mesh
  axis ``axis`` (called inside a ``shard_map`` over it): chip ``j`` of
  ``n`` holds the router's experts ``[first + j E_l, first + (j + 1)
  E_l)`` (its ``w_gate_up`` ``[E_l, D, 2 F]``, ``w_down`` ``[E_l, F, D]``)
  and its OWN positions ``x`` ``[N, D]`` with their choices.  An
  assignment's row goes to the chip that holds its expert, every chip runs
  the grouped matmuls over the rows it received from all ``n``, and each
  term goes back to its position's chip, which weighs and sums its
  positions' terms.  Assignments to experts outside the ``n E_l`` held on
  the axis are another host's (nothing stands in for them).

  The dataflow, with static shapes and no capacity: a chip sorts its
  assignments by expert (so by destination), and a ROUND moves ``rows`` of
  them to each chip (``all_to_all`` of ``[n, rows, D]``, under the named
  scope ``moe_dispatch``), which brings what it received into expert order
  (the counts every chip sends every chip are gathered first, so the
  order is arithmetic on them and one small sort), multiplies, and
  returns the products by the same road (``moe_combine``).  Rounds repeat
  while any pair of chips has rows left (a ``while_loop`` whose trip
  count every chip derives from the same gathered counts): one round
  unless a chip is sent more than ``rows`` by one other, and under ANY
  imbalance every assignment to a held expert is computed.

  Returns ``(y [N, D], sizes [E_l]`` the rows this chip's experts were
  sent, ``sent`` how many of this chip's assignments fell on held
  experts, ``out`` how many of those left the chip, ``rounds)``."""
  from easyparallellibrary_tpu.kernels.moe_gmm import moe_gmm
  n = jax.lax.psum(1, axis)
  me = jax.lax.axis_index(axis)
  N, k = chosen.shape
  E_l, _, F2 = w_gate_up.shape
  D = x.shape[-1]
  C, i32 = rows, jnp.int32
  order, sizes = sort_by_expert(chosen, live, n * E_l, first)
  inv = jnp.argsort(order).astype(i32)          # sorted row of assignment
  per_dst = sizes.reshape(n, E_l)
  seg_count = jnp.sum(per_dst, axis=1)                          # [n]
  seg_start = jnp.cumsum(seg_count) - seg_count
  # What every chip sends every chip, by expert: [source, dest, E_l].
  counts = jax.lax.all_gather(per_dst, axis)
  mine = counts[:, me]                                          # [n, E_l]
  src_ends = jnp.cumsum(mine, axis=1)
  src_total = src_ends[:, -1]
  rounds = jnp.max(-(-jnp.sum(counts, axis=2) // C))
  slot = jnp.arange(C, dtype=i32)
  # Each flat assignment's destination and place in that chip's segment.
  rel = chosen.reshape(-1) - first
  held = (rel >= 0) & (rel < n * E_l)
  if live is not None:
    held &= jnp.repeat(live, k)
  dst = jnp.clip(rel // E_l, 0, n - 1)
  place = inv - jnp.take(seg_start, dst)
  w_flat = jnp.where(held, weights.reshape(-1), 0.0)

  def one_round(carry):
    r, y = carry
    lo = r * C
    # The rows sent: sorted rows [seg_start + lo, + C) of each segment.
    at = jnp.clip(seg_start[:, None] + lo + slot[None], 0, N * k - 1)
    send = jnp.take(x, jnp.take(order, at.reshape(-1)) // k, axis=0)
    with jax.named_scope("moe_dispatch"):
      got = jax.lax.all_to_all(send.reshape(n, C, D), axis, 0, 0)
    # Row ``c`` of source ``s`` is row ``lo + c`` of what ``s`` sends
    # here: its expert is how many of that source's experts end at or
    # before it; beyond the source's total it is no row (expert E_l).
    nth = lo + slot
    expert = jnp.sum(src_ends[:, None, :] <= nth[None, :, None], axis=2,
                     dtype=i32)
    expert = jnp.where(nth[None] < src_total[:, None], expert, E_l)
    order2 = jnp.argsort(expert.reshape(-1), stable=True).astype(i32)
    sizes2 = jnp.sum(
        jnp.clip(src_ends, lo, lo + C)
        - jnp.clip(src_ends - mine, lo, lo + C), axis=0).astype(i32)
    rows2 = jnp.take(got.reshape(n * C, D), order2, axis=0)
    h = moe_gmm(rows2, w_gate_up, sizes2, impl=impl)
    h = gate(h[:, :F2 // 2]) * _up(up, h[:, F2 // 2:])
    out = moe_gmm(h, w_down, sizes2, impl=impl)                 # [n C, D]
    back = jnp.take(out, jnp.argsort(order2), axis=0)
    with jax.named_scope("moe_combine"):
      back = jax.lax.all_to_all(back.reshape(n, C, D), axis, 0, 0)
    # Each assignment's term, where this round carried it.
    off = place - lo
    here = held & (off >= 0) & (off < C)
    term = jnp.take(back.reshape(n * C, D),
                    jnp.clip(dst * C + off, 0, n * C - 1), axis=0)
    w = jnp.where(here, w_flat, 0.0)
    y = y + jnp.sum(term.reshape(N, k, D).astype(jnp.float32)
                    * w.reshape(N, k, 1), axis=1)
    return r + 1, y

  _, y = jax.lax.while_loop(lambda c: c[0] < rounds, one_round,
                            (jnp.zeros((), i32), jnp.zeros((N, D),
                                                           jnp.float32)))
  sent = jnp.sum(seg_count)
  return (y.astype(x.dtype), jnp.sum(mine, axis=0).astype(i32), sent,
          sent - jnp.take(seg_count, me), rounds)


class DroplessMoE(HeldParams, nn.Module):
  """Routed experts without capacity beside shared ones, if any:
  ``Shared(x) + sum_i w_i Expert_i(x)``, the routed sum alone where
  ``n_shared_experts`` is 0 (no ``shared`` in the tree then; module
  docstring).  ``cfg`` gives ``d_model``, ``n_routed_experts``,
  ``num_experts_per_tok``, ``moe_d_ff``, ``n_shared_experts``,
  ``routed_scaling_factor``, ``norm_topk_prob``, ``route_norm_eps`` (what
  the normalisation adds to the chosen scores' sum) and the dtypes.  ``live`` bool ``[..]``
  over ``x``'s leading axes says which positions are routed (``None``:
  all); a shared expert runs for every position (fixed shapes), and what
  it gives a dead one nothing reads.

  What the model's config decides beyond the sizes, each absent from a
  config that takes the default: ``cfg.expert_route`` (a function ``(x,
  router_kernel, top_k) -> (chosen, weights)``, e.g.
  :func:`softmax_topk_route`; absent: :func:`noaux_tc_route`, whose bias
  is then in the tree and which reads ``routed_scaling_factor``,
  ``norm_topk_prob`` and ``route_norm_eps``), ``cfg.expert_gate`` (the
  gate's activation; absent: SiLU), ``cfg.expert_up`` (what the up
  projection passes through before the product; absent: nothing) and
  ``cfg.swiglu_limit`` (the shared expert's clamp of both,
  models/blocks.py ``clamp_gate_up``; absent: none).  ``router_in``
  (``None``: ``x``) is what
  the ROUTER reads where that is not what the experts read
  (models/smallthinker.py routes from the layer's input, before its
  attention).

  ``cfg.experts_held = (first, count)`` (absent or ``None``: all) TELLS
  the layer which of the router's experts it holds, one chip's share of
  a layer divided over several: the router keeps its width and its
  ``num_experts_per_tok``, the weights are normalised over ALL the chosen,
  held or not, the stacks are ``[count, D, 2 F]`` and ``[count, F, D]``,
  and the result is ``Shared(x)`` plus the held experts' terms of the sum.
  No code stands in for the absent chips.

  ``expert_axis`` (a field, ``None``: one chip) names the mesh axis the
  HELD experts are divided over when the layer stands inside a
  ``shard_map`` over it (a serving engine on such a mesh): the stacks are
  then a chip's run of them, ``[count / chips, ..]``, and the routed sum
  is :func:`exchanged_experts`': rows go to the chips that hold their
  experts and the terms come back, no capacity, a round sized by
  :func:`exchange_rows`.  It then also sows
  ``exchange_rows_out`` / ``exchange_rows_in`` (this chip's assignments
  that left it, and those that arrived from others) and
  ``exchange_rounds``; ``held_assignments`` counts this chip's positions'
  assignments to the host's experts, ``expert_load`` and
  ``experts_touched`` what this chip's experts were sent.

  Sows into the ``stats`` collection ``expert_load``, the busiest
  expert's assignments over the mean (1.0 = even; 0 when nothing is
  live), and ``experts_touched``, how many experts have at least one live
  assignment (the step streams those experts' weights and no others')."""

  cfg: Any
  moe_gmm_impl: Optional[str] = None
  expert_axis: Optional[str] = None

  @nn.compact
  def __call__(self, x, live=None, router_in=None):
    cfg = self.cfg
    k, F, D = cfg.num_experts_per_tok, cfg.moe_d_ff, cfg.d_model
    held = getattr(cfg, "experts_held", None)
    first, E = held if held is not None else (0, cfg.n_routed_experts)
    axis = self.expert_axis
    if axis is not None:
      # Inside a ``shard_map`` over ``axis`` the stacks are a chip's share.
      chips = jax.lax.psum(1, axis)
      if E % chips:
        raise ValueError(f"{E} held experts do not divide over the "
                         f"{chips} chips of axis {axis!r}")
      E //= chips
    normal = nn.initializers.normal(stddev=0.02)
    router = self.param("router_kernel", boxed(normal, 2),
                        (D, cfg.n_routed_experts), jnp.float32)
    route = getattr(cfg, "expert_route", None)
    if route is None:
      bias = self.param("e_score_correction_bias",
                        boxed(nn.initializers.zeros_init(), 1),
                        (cfg.n_routed_experts,), jnp.float32)
    w_gate_up = self.param("experts_gate_up", boxed(normal, 3),
                           (E, D, 2 * F), cfg.param_dtype)
    w_down = self.param("experts_down", boxed(normal, 3), (E, F, D),
                        cfg.param_dtype)
    flat = x.reshape(-1, D)
    flat_live = None if live is None else live.reshape(-1)
    routed = flat if router_in is None else router_in.reshape(-1, D)
    if route is None:
      chosen, weights = noaux_tc_route(
          routed, router, bias, k, cfg.routed_scaling_factor,
          cfg.norm_topk_prob, cfg.route_norm_eps)
    else:
      chosen, weights = route(routed, router, k)
    stacks = (jnp.asarray(w_gate_up, cfg.dtype),
              jnp.asarray(w_down, cfg.dtype))
    gate = getattr(cfg, "expert_gate", jax.nn.silu)
    up = getattr(cfg, "expert_up", None)
    if axis is None:
      y, sizes = dropless_experts(
          flat, chosen, weights, flat_live, *stacks, impl=self.moe_gmm_impl,
          first=first, gate=gate, up=up)
      total = jnp.sum(sizes).astype(jnp.float32)
      if held is not None:
        self.sow("stats", "held_assignments", total)
    else:
      y, sizes, sent, left, rounds = exchanged_experts(
          flat, chosen, weights, flat_live, *stacks, axis=axis,
          rows=exchange_rows(flat.shape[0], k, E, cfg.n_routed_experts),
          impl=self.moe_gmm_impl, first=first, gate=gate, up=up)
      # ``sizes``: the rows this chip's experts were SENT, by all chips.
      total = jnp.sum(sizes).astype(jnp.float32)
      self.sow("stats", "held_assignments", sent.astype(jnp.float32))
      self.sow("stats", "exchange_rows_out", left.astype(jnp.float32))
      self.sow("stats", "exchange_rows_in", total - (sent - left).astype(
          jnp.float32))
      self.sow("stats", "exchange_rounds", rounds.astype(jnp.float32))
    self.sow("stats", "expert_load",
             jnp.max(sizes).astype(jnp.float32) * E
             / jnp.maximum(total, 1.0))
    self.sow("stats", "experts_touched",
             jnp.sum(sizes > 0).astype(jnp.float32))
    if not cfg.n_shared_experts:
      return y.reshape(x.shape)
    shared = GatedMLP(cfg, d_ff=cfg.n_shared_experts * F,
                      limit=getattr(cfg, "swiglu_limit", None),
                      name="shared")(x)
    return shared + y.reshape(x.shape)
