"""Split-scope-aware layers — the tensor-parallel op library.

TPU-native redesign of the reference's distributed op library
(epl/ops/distributed_dense.py, and the hook that swaps ``tf.layers.dense``
for it inside a ``split`` scope, epl/parallel/hooks.py:710-828).  Two
deliberate differences:

  * No monkey-patching: these are ordinary flax modules that *consult the
    ambient strategy scope at trace time*.  Because JAX traces the model
    function as Python, a ``with epl.split(...):`` around the layer call in
    ``__call__`` plays exactly the role the reference's graph-construction
    scope plays in TF1 graph mode.
  * No uneven shards: the reference gives shard 0 the remainder
    (epl/ops/distributed_dense.py:102-109, parallel/ops.py:507-523);
    GSPMD wants even tiling, so uneven feature dims are zero-padded to an
    even tiling (init at the logical shape for exact fan statistics,
    outputs sliced back) instead of remainder logic.

Sharding layouts (Megatron-style, expressed as GSPMD metadata):
  * column parallel: kernel P(None, "model") → activations sharded on the
    feature dim; the reference's ``distributed_dense`` kernel
    ``[in, units/num_shards]`` per device (:139-143).
  * row parallel: kernel P("model", None) → XLA inserts the psum the
    reference would build by hand.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax import errors, struct
from jax.sharding import PartitionSpec as P

from easyparallellibrary_tpu import constants
from easyparallellibrary_tpu.env import Env

Dtype = Any
default_kernel_init = nn.initializers.lecun_normal()


def _active_split():
  """The innermost active split scope, if any (trace-time lookup)."""
  strat = Env.get().strategy_context.current
  if strat is not None and strat.kind == "split":
    return strat
  return None


from easyparallellibrary_tpu.utils.sharding import constrain as _constraint  # noqa: E402


def _model_axis_size() -> int:
  env = Env.get()
  if env.cluster is None or env.cluster._mesh is None:
    return 1
  return env.cluster.axis_size(constants.MODEL_AXIS)


def _row_overlap_chunks(x, padded_in: int, out_features: int) -> int:
  """Ring chunk count for a row-parallel Dense matmul under the
  ``communication.overlap`` policy; 1 = keep the fused GSPMD program.

  The ring runs as an explicit shard_map over the model axis, so it
  engages only where that region is well-defined:

    * not already inside a manual region (the smap engines own their
      schedule; a nested ring's whole-mesh permute channels would
      deadlock against their gated ticks);
    * every mesh axis except ``model`` has size 1 (the region is
      full-manual; pure-TP meshes are exactly the shape the explicit
      ``split`` library targets);
    * the flattened activation rows divide the model axis (the scatter
      grain).
  """
  env = Env.get()
  if env.cluster is None or env.cluster._mesh is None:
    return 1
  mesh = env.cluster._mesh
  sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
  n = sizes.get(constants.MODEL_AXIS, 1)
  if n <= 1:
    return 1
  if any(s > 1 for a, s in sizes.items() if a != constants.MODEL_AXIS):
    return 1
  from easyparallellibrary_tpu.utils.compat import ambient_manual_axes
  if ambient_manual_axes():
    return 1
  rows = 1
  for s in x.shape[:-1]:
    rows *= int(s)
  if rows % n:
    return 1
  from easyparallellibrary_tpu.communicators import overlap as _overlap
  from easyparallellibrary_tpu.parallel.planner import SITE_ROW_DENSE
  return _overlap.resolve_num_chunks(
      "matmul_reduce_scatter", n, m=rows, k=padded_in // n,
      n_out=out_features, dtype=x.dtype, site=SITE_ROW_DENSE)


def _row_overlap_matmul(x, kernel, dtype, num_chunks: int):
  """Row-parallel matmul + reduction as an explicit collective-matmul:
  ``matmul -> ring reduce_scatter`` (compute-overlapped,
  communicators/overlap.py) then an all-gather rebuilding the replicated
  activation — together the same bytes as the fused all-reduce GSPMD
  inserts, with the scatter half hidden under the matmul."""
  from easyparallellibrary_tpu.communicators import overlap as _overlap
  from easyparallellibrary_tpu.utils.compat import shard_map
  mesh = Env.get().cluster.mesh
  lead = x.shape[:-1]
  rows = 1
  for s in lead:
    rows *= int(s)
  n_out = kernel.shape[-1]

  def body(xl, wl):
    xf = xl.astype(dtype).reshape(rows, xl.shape[-1])
    y = _overlap.matmul_reduce_scatter(xf, jnp.asarray(wl, dtype),
                                       constants.MODEL_AXIS,
                                       num_chunks=num_chunks)
    y = jax.lax.all_gather(y, constants.MODEL_AXIS, axis=0, tiled=True)
    return y.reshape(lead + (n_out,))

  # Full-manual region: _row_overlap_chunks engages only where every
  # other mesh axis has size 1, so leaving them out of the specs is the
  # same program — and a partial-manual region evaluated eagerly (flax
  # `init` outside jit) is refused by jax.shard_map's out-spec check.
  nd = len(lead)
  f = shard_map(
      body, mesh,
      in_specs=(P(*([None] * nd), constants.MODEL_AXIS),
                P(constants.MODEL_AXIS, None)),
      out_specs=P(*([None] * nd), None))
  return f(x, kernel)


def _round_up(dim: int, multiple: int) -> int:
  return ((dim + multiple - 1) // multiple) * multiple


def _padded_init(init: Callable, logical_shape: Sequence[int]):
  """Initialize at the logical shape, zero-pad to the padded shape.

  Keeps init statistics (fan) exact for uneven tensor-parallel dims: the
  reference gives shard 0 the remainder (epl/ops/distributed_dense.py:
  102-109); GSPMD wants even tiles, so we pad the weight and mask/slice
  at the edges instead (SURVEY §7 hard parts)."""

  def wrapped(key, shape, dtype=jnp.float32):
    logical = tuple(logical_shape)
    value = init(key, logical, dtype)
    pad = [(0, s - l) for s, l in zip(shape, logical)]
    if any(p != (0, 0) for p in pad):
      value = jnp.pad(value, pad)
    return value

  return wrapped


class PaddedPartitioned(nn.Partitioned):
  """Partitioned box that remembers the param's LOGICAL (unpadded) shape.

  Checkpoint-layout portability (VERDICT r2 item 5; reference analog:
  ShardingLoader's reshard-at-load, epl/runtime/saver.py:46-128): the
  saver slices attested pad regions off before writing — checkpoints
  always hold logical shapes — and zero-pads back to whatever padded
  shape the LOADING configuration uses.  Without the attestation a shape
  mismatch at load stays a hard error (padding may only reconstruct
  regions this box guarantees are zero).
  """
  logical_shape: Optional[Tuple[int, ...]] = struct.field(
      pytree_node=False, default=None)


def _with_padded_partitioning(init: Callable, names,
                              logical_shape: Sequence[int]):
  """`nn.with_partitioning`, but boxing into PaddedPartitioned with the
  logical shape recorded (only called for possibly-padded params)."""

  def wrapped(*args, **kw):
    value = _padded_init(init, logical_shape)(*args, **kw)
    return PaddedPartitioned(value, names,
                             logical_shape=tuple(logical_shape))

  return wrapped


class HeldParams:
  """Mixin before ``nn.Module`` for a module that declares parameters
  (``self.param(name, init, shape, ...)``): a parameter that already
  exists (every ``apply`` on a trained or seeded tree) is read and its
  shape compared with the one asked for, directly.

  flax's own ``param`` makes that check by evaluating the initializer
  abstractly (``jax.eval_shape`` of a fresh closure, a trace of its own)
  at EVERY access, about 6 ms each on the chip's host: a serving step of
  28 layers reads ~330 parameters, and with its position-wise layers at
  two widths more, seconds of a warm start (PERF.md, PR 41, has them with
  and without).  Everything else is flax's: the same bookkeeping and the
  same errors (a name in use, a shape that differs, a parameter declared
  outside ``setup`` or a compact method), and flax's own way wherever this
  one does not apply: a parameter that does not exist yet (``init``), a
  shape that is not the initializer's first argument.  The emitted program
  is the same to the byte.  tests/test_held_params.py holds every family's
  serving step to it, so a new module that declares parameters without the
  mixin is named there."""

  def param(self, name, init_fn, *init_args, unbox: bool = True,
            **init_kwargs):
    shape = init_args[0] if init_args else None
    if (not isinstance(shape, (tuple, list)) or self.scope is None
        or not self.has_variable("params", name)):
      return super().param(name, init_fn, *init_args, unbox=unbox,
                           **init_kwargs)
    # flax.linen.Module.param, then flax.core.Scope.param, but for the
    # abstract evaluation
    if not self._initialization_allowed:
      raise ValueError("Parameters must be initialized in `setup()` or in a "
                       "method wrapped in `@compact`")
    if self._name_taken(name, collection="params"):
      raise errors.NameInUseError("param", name, self.__class__.__name__)
    self.scope.reserve(name, "params")
    value = self.scope.get_variable("params", name)
    held = nn.meta.unbox(value)
    if tuple(shape) != jnp.shape(held):
      raise errors.ScopeParamShapeError(
          name, self.scope.path_text, jnp.shape(held), tuple(shape))
    self._state.children[name] = "params"
    return held if unbox else value


class Dense(HeldParams, nn.Module):
  """Dense layer; tensor-parallel when called under a ``split`` scope.

  ``parallel``: "auto" (from ambient scope → column), "column", "row", or
  "none".  Column-parallel output stays sharded on the feature dim (use a
  row-parallel layer next, or ``split_to_replica`` to gather), mirroring
  the reference where consumers see the sharded dense output
  (epl/ops/distributed_dense.py:146-193).
  """

  features: int
  use_bias: bool = True
  parallel: str = "auto"
  dtype: Optional[Dtype] = None
  param_dtype: Dtype = jnp.float32
  kernel_init: Callable = default_kernel_init
  bias_init: Callable = nn.initializers.zeros_init()

  @nn.compact
  def __call__(self, x):
    mode = self.parallel
    if mode == "auto":
      mode = "column" if _active_split() is not None else "none"
      if mode == "column" and Env.get().config.auto.tensor_split:
        # Auto tensor-split (reference TODO, epl/ir/graph.py:124):
        # alternate column -> row across auto-named sibling Dense layers
        # (flax names them Dense_0, Dense_1, ... within a parent), the
        # Megatron pairing — an MLP's up-projection shards the feature
        # dim and the down-projection contracts it with one psum, no
        # activation gather between them.  The flax auto-name is the
        # trace-stable key (a per-scope counter would drift across
        # init/eval_shape/jit retraces).  Explicitly named layers keep
        # column; explicit `parallel=` never reaches this branch.
        m = re.fullmatch(r"Dense_(\d+)", self.name or "")
        if m and int(m.group(1)) % 2 == 1:
          mode = "row"
    if mode not in ("none", "column", "row", "stage_column"):
      raise ValueError(f"Dense.parallel must be auto/none/column/row/"
                       f"stage_column, got {self.parallel!r}")
    in_features = x.shape[-1]
    model = _model_axis_size()
    out_features = self.features
    kshape = (in_features, out_features)

    if mode == "column":
      # Uneven feature dims are zero-padded to an even tiling; the output
      # is sliced back to the logical width.
      padded_out = _round_up(out_features, model)
      kshape = (in_features, padded_out)
      kernel_init = _with_padded_partitioning(
          self.kernel_init, (None, constants.MODEL_AXIS),
          (in_features, out_features))
      bias_spec: Tuple = (constants.MODEL_AXIS,)
    elif mode == "row":
      # Uneven contraction dims: pad the input with zeros so the padded
      # kernel rows contribute nothing.
      padded_in = _round_up(in_features, model)
      if padded_in != in_features:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                    + [(0, padded_in - in_features)])
      kshape = (padded_in, out_features)
      kernel_init = _with_padded_partitioning(
          self.kernel_init, (constants.MODEL_AXIS, None),
          (in_features, out_features))
      bias_spec = (None,)
    elif mode == "stage_column":
      # Stage-resident head for the smap pipeline engine: the feature
      # (vocab) dim is committed over the stage axis ([in, V/S] per
      # stage group), the compute is the plain matmul — stage collectives
      # are the engine's job, not this layer's.
      kernel_init = nn.with_partitioning(
          self.kernel_init, (None, constants.STAGE_AXIS))
      bias_spec = (constants.STAGE_AXIS,)
    else:
      # Box even unsharded params (all-None spec): lifted transforms like
      # the pipeline's nn.vmap extend metadata with the stage axis, which
      # only exists on boxed params.
      kernel_init = nn.with_partitioning(self.kernel_init, (None, None))
      bias_spec = (None,)

    kernel = self.param("kernel", kernel_init, kshape, self.param_dtype)
    dtype = self.dtype or x.dtype
    row_chunks = (_row_overlap_chunks(x, kshape[0], out_features)
                  if mode == "row" else 1)
    if row_chunks >= 2:
      # Latency-hiding collective-matmul: the fused matmul+psum becomes
      # matmul -> ring reduce_scatter (overlapped) -> all_gather.  Same
      # wire bytes as the all-reduce, scatter half hidden under the MXU.
      y = _row_overlap_matmul(x, kernel, dtype, row_chunks)
    else:
      y = jnp.matmul(x.astype(dtype), jnp.asarray(kernel, dtype))
    if mode == "column":
      # Leading dims UNCONSTRAINED: only the feature dim is pinned to the
      # model axis (None would force batch/seq to gather here).
      y = _constraint(y, P(*([P.UNCONSTRAINED] * (y.ndim - 1)),
                           constants.MODEL_AXIS))
    elif mode == "row" and row_chunks < 2:
      # The contraction over the model-sharded dim makes XLA insert the
      # psum from dataflow; pin only the feature dim off the model axis.
      y = _constraint(y, P(*([P.UNCONSTRAINED] * (y.ndim - 1)), None))
    if self.use_bias:
      bias = self.param(
          "bias", _with_padded_partitioning(
              self.bias_init, bias_spec, (out_features,))
          if mode == "column" else
          nn.with_partitioning(self.bias_init, bias_spec),
          (kshape[1] if mode == "column" else out_features,),
          self.param_dtype)
      y = y + jnp.asarray(bias, dtype)
    if mode == "column" and y.shape[-1] != out_features:
      y = y[..., :out_features]
    return y


class LayerNorm(HeldParams, nn.LayerNorm):
  """LayerNorm with boxed (metadata-carrying) scale/bias, so pipeline
  stacking can shard them over the stage axis."""
  scale_init: Callable = nn.with_partitioning(
      nn.initializers.ones_init(), (None,))
  bias_init: Callable = nn.with_partitioning(
      nn.initializers.zeros_init(), (None,))


class Embedding(HeldParams, nn.Module):
  """Token embedding; vocab-sharded under a ``split`` scope.

  The reference has no embedding op in its split library (embeddings stay
  replicated there); vocab sharding is the TPU-idiomatic extension that
  makes large-vocab GPT heads tensor-parallel end-to-end.
  """

  num_embeddings: int
  features: int
  parallel: str = "auto"
  param_dtype: Dtype = jnp.float32
  embedding_init: Callable = nn.initializers.normal(stddev=0.02)

  @nn.compact
  def __call__(self, ids):
    tp = self.parallel == "vocab" or (
        self.parallel == "auto" and _active_split() is not None)
    if tp:
      padded = _round_up(self.num_embeddings, _model_axis_size())
      init = _with_padded_partitioning(
          self.embedding_init, (constants.MODEL_AXIS, None),
          (self.num_embeddings, self.features))
      shape = (padded, self.features)
    elif self.parallel == "stage_vocab":
      # Stage-resident table for the smap pipeline engine: committed at
      # [V/S, D] per stage group (vocab must divide the stage axis — the
      # engine validates).  Lookups outside the engine (eval/generate)
      # still work: GSPMD gathers across the stage axis.
      init = nn.with_partitioning(self.embedding_init,
                                  (constants.STAGE_AXIS, None))
      shape = (self.num_embeddings, self.features)
    else:
      init = nn.with_partitioning(self.embedding_init, (None, None))
      shape = (self.num_embeddings, self.features)
    table = self.param("embedding", init, shape, self.param_dtype)
    return jnp.take(jnp.asarray(table), ids, axis=0)

  def attend(self, x):
    """Tied-softmax logits: x @ table.T (logits sharded on vocab if TP;
    padded vocab rows are sliced off)."""
    table = self.get_variable("params", "embedding")
    while hasattr(table, "value"):
      table = table.value
    logits = jnp.matmul(x, jnp.asarray(table).T.astype(x.dtype))
    logits = _constraint(
        logits, P(*([P.UNCONSTRAINED] * (logits.ndim - 1)),
                  constants.MODEL_AXIS))
    if logits.shape[-1] != self.num_embeddings:
      logits = logits[..., :self.num_embeddings]
    return logits
