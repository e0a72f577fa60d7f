"""Chunked selective scan — Pallas TPU kernel + the ``lax.scan`` reference.

One Mamba layer's step of the serving engine's fused call
(``models/jamba.py`` ``MambaMixer``): every slot advances its recurrent
state by the ``num_valid`` tokens of its chunk,

    s_t = exp(delta_t * A) * s_{t-1} + (delta_t * u_t) * B_t
    y_t = sum_n (s_t * C_t) + D * u_t,        out_t = y_t * silu(z_t)

per channel ``d`` and state ``n``, all in float32.  Contract, shared by the
two lowerings:

* ``state`` float32 ``[B, N, Di]`` — the carried state, STATE-MAJOR:
  channels ride the 128 lanes, the ``N`` states the sublanes (``[B, Di,
  N]`` would pad 16 lanes to 128); ``u``, ``z`` ``[B, C, Di]`` in the
  compute dtype (``u`` after the convolution and its SiLU, ``z`` the
  gate); ``delta`` float32 ``[B, C, Di]`` AFTER its projection, bias and
  softplus; ``Bm``, ``Cm`` float32 ``[B, C, N]``; ``A`` float32 ``[N,
  Di]`` (negative: ``-exp(A_log)``); ``D`` float32 ``[Di]``;
  ``num_valid`` int32 ``[B]``; ``reset`` bool ``[B]``.
* A slot with ``reset`` starts from zero state (a new request: stale state
  is masked by nothing, unlike stale K/V).  Positions at or beyond
  ``num_valid`` leave the state exactly as it was (``delta = 0`` is the
  identity of the recurrence) and give ``out = 0``; an idle slot
  (``num_valid = 0``) keeps its state bit for bit.
* Returns ``(out [B, C, Di] in u's dtype — GATED, ready for the output
  projection — , new_state)``.

Lowerings, behind one dispatcher as ``kernels/kv_write.py`` has them:

* **reference** — ``lax.scan`` over the chunk's positions on ``[B, N,
  Di]`` values.  Left to XLA on a TPU it materialises ``exp(delta A)``
  and ``delta B u`` for the whole chunk in HBM; correct everywhere.
* **pallas** — one launch a layer named ``ssm_scan``, grid over the
  slots (and channel tiles where ``[N, Di]`` outgrows the VMEM budget),
  the state block aliased onto its output: HBM sees the state once in
  and once out and each activation once.  The chunk's positions are
  unrolled, each under ``pl.when(t < num_valid)``, so a decode slot pays
  one position of arithmetic and an idle one none.

Dispatch rule (:func:`resolve_ssm_scan_impl`): the kernel when the
backend is a TPU, the state sits whole on one chip and the shapes fit its
tiles (:func:`ssm_scan_fits`); the reference everywhere else.  It reads
what it is handed and the backend — no configuration field, environment
variable or setter; ``interpret`` runs the kernel in Pallas interpreter
mode (the CPU parity tests, by name or by patching
:func:`_backend_impl`).  The engine resolves it once when it builds its
step and records it (``engine.lowerings["ssm_scan_impl"]``, trace metadata
``serving/ssm_scan_impl``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from easyparallellibrary_tpu.env import Env

# The kernel's name in a device trace; the benchmark reads it (PERF.md
# section 3).
SSM_SCAN = "ssm_scan"

IMPLS = ("pallas", "reference", "interpret")

LANES = 128
# The chunk's positions are unrolled in the kernel body.
MAX_CHUNK = 32
# One state tile ``[N, TD]`` float32 may take this much VMEM: the kernel
# holds it four times (in and out, double-buffered) beside the chunk's
# activations.  Jamba's [16, 5120] is 320 KiB.
_STATE_TILE_BYTES = 512 * 1024


def _backend_impl() -> str:
  """The lowering this backend takes when the shapes allow it.  The CPU
  parity tests patch it to ``interpret``."""
  return "pallas" if jax.default_backend() == "tpu" else "reference"


def _channel_tile(n_state: int, d_inner: int) -> int:
  """Widest tile of channels, a multiple of 128 dividing ``d_inner``,
  whose float32 state block stays within the budget; 0 if none."""
  if d_inner % LANES:
    return 0
  cols = d_inner // LANES
  for parts in range(1, cols + 1):
    if cols % parts == 0 and \
        n_state * (d_inner // parts) * 4 <= _STATE_TILE_BYTES:
      return d_inner // parts
  return 0


def ssm_scan_fits(state_shape, dtype, chunk: int) -> bool:
  """Whether the kernel can tile a ``[B, N, Di]`` state for chunks of
  ``chunk`` positions whose activations are ``dtype``: whole sublane
  tiles of states, whole lane tiles of channels, a chunk short enough to
  unroll, a 32-bit or 16-bit float activation."""
  _, N, Di = state_shape
  if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                              jnp.dtype(jnp.float32)):
    return False
  if N % 8 or not 1 <= chunk <= MAX_CHUNK:
    return False
  return _channel_tile(N, Di) > 0


def resolve_ssm_scan_impl(state_shape, dtype, chunk: int,
                          sharded: bool = False) -> str:
  """The dispatch rule: the backend's lowering, and ``reference``
  whenever the state lives on a multi-device mesh (``sharded``: the SPMD
  partitioner cannot split a Mosaic call) or the shapes do not fit."""
  impl = _backend_impl()
  if impl != "reference" and (
      sharded or not ssm_scan_fits(state_shape, dtype, chunk)):
    return "reference"
  return impl


# -------------------------------------------------------------- reference --


def ssm_scan_reference(state, u, delta, Bm, Cm, z, A, D, num_valid, reset):
  """``lax.scan`` over the chunk's positions (module docstring)."""
  C = u.shape[1]
  f32 = jnp.float32
  s0 = jnp.where(reset[:, None, None], jnp.zeros((), f32), state)
  live = jnp.arange(C)[None, :] < num_valid[:, None]            # [B, C]

  def step(s, xs):
    u_t, d_t, b_t, c_t, z_t, live_t = xs
    u_t, z_t = u_t.astype(f32), z_t.astype(f32)
    d_t = jnp.where(live_t[:, None], d_t, 0.0)
    s = (jnp.exp(d_t[:, None, :] * A) * s
         + (d_t * u_t)[:, None, :] * b_t[:, :, None])
    y = jnp.sum(s * c_t[:, :, None], axis=1) + D * u_t
    out = jnp.where(live_t[:, None], y * jax.nn.silu(z_t), 0.0)
    return s, out.astype(u.dtype)

  t_major = lambda x: jnp.moveaxis(x, 1, 0)
  s, out = jax.lax.scan(step, s0, tuple(
      t_major(x) for x in (u, delta, Bm, Cm, z, live)))
  return t_major(out), s


# ----------------------------------------------------------------- pallas --


def _ssm_scan_kernel(nv_ref, reset_ref, s_ref, u_ref, d_ref, b_ref, c_ref,
                     z_ref, a_ref, dd_ref, out_ref, s_out_ref, acc_ref, *,
                     chunk: int):
  """One (slot, channel tile) grid step.  Values keep ``[N, TD]``: the
  channels on lanes, the states on sublanes; a position's ``B_t`` and
  ``C_t`` are columns ``[N, 1]`` broadcast along the lanes, its ``delta``,
  ``u`` and ``z`` rows ``[1, TD]`` broadcast along the sublanes.  The
  state lives in the output block between positions (aliased onto the
  input in HBM); the outputs gather in a float32 scratch and leave as one
  whole block, so no 16-bit row is ever stored alone."""
  b = pl.program_id(0)
  nv = nv_ref[b]
  f32 = jnp.float32
  s_out_ref[0] = jnp.where(reset_ref[b] != 0, jnp.zeros((), f32), s_ref[0])
  acc_ref[...] = jnp.zeros_like(acc_ref)
  u = u_ref[0].astype(f32)                                  # [C, TD]
  z = z_ref[0].astype(f32)
  delta = d_ref[0]
  A = a_ref[...]                                            # [N, TD]
  D = dd_ref[...]                                           # [1, TD]
  for t in range(chunk):
    @pl.when(t < nv)
    def _(t=t):
      u_t, d_t, z_t = u[t:t + 1], delta[t:t + 1], z[t:t + 1]
      b_t = b_ref[0, :, t:t + 1]                            # [N, 1]
      c_t = c_ref[0, :, t:t + 1]
      s = jnp.exp(d_t * A) * s_out_ref[0] + (d_t * u_t) * b_t
      s_out_ref[0] = s
      y = jnp.sum(s * c_t, axis=0, keepdims=True) + D * u_t
      acc_ref[t:t + 1, :] = y * jax.nn.silu(z_t)
  out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan_pallas(state, u, delta, Bm, Cm, z, A, D, num_valid, reset,
                    interpret: bool = False):
  """The kernel (module docstring); ``interpret`` runs it in Pallas
  interpreter mode on any backend.  Jitted, so that the layers of one
  step share one trace and one Mosaic lowering."""
  B, N, Di = state.shape
  C = u.shape[1]
  if not ssm_scan_fits(state.shape, u.dtype, C):
    raise ValueError(f"ssm_scan kernel does not fit state {state.shape}, "
                     f"chunk {C}, {u.dtype} (ssm_scan_fits)")
  TD = _channel_tile(N, Di)
  f32 = jnp.float32
  # Position-minor [B, N, C]: a position's B_t, C_t are then columns.
  cols = lambda x: jnp.swapaxes(x.astype(f32), 1, 2)
  row = lambda b, d, nv, rs: (b, 0, d)
  act = pl.BlockSpec((1, C, TD), row)
  st = pl.BlockSpec((1, N, TD), row)
  col = pl.BlockSpec((1, N, C), lambda b, d, nv, rs: (b, 0, 0))
  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=2,
      grid=(B, Di // TD),
      in_specs=[st, act, act, col, col, act,
                pl.BlockSpec((N, TD), lambda b, d, nv, rs: (0, d)),
                pl.BlockSpec((1, TD), lambda b, d, nv, rs: (0, d))],
      out_specs=[act, st],
      scratch_shapes=[pltpu.VMEM((C, TD), f32)],
  )
  kwargs = {}
  if not interpret:
    kwargs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))
  out, new_state = pl.pallas_call(
      functools.partial(_ssm_scan_kernel, chunk=C),
      grid_spec=grid_spec,
      out_shape=[jax.ShapeDtypeStruct((B, C, Di), u.dtype),
                 jax.ShapeDtypeStruct((B, N, Di), f32)],
      # Operands count the two scalar-prefetch vectors: 2 is the state.
      input_output_aliases={2: 1},
      interpret=interpret,
      name=SSM_SCAN,
      **kwargs,
  )(num_valid.astype(jnp.int32), reset.astype(jnp.int32),
    state.astype(f32), u, delta.astype(f32), cols(Bm), cols(Cm),
    z.astype(u.dtype), A.astype(f32), D.astype(f32).reshape(1, Di))
  return out, new_state


# --------------------------------------------------------------- dispatch --


def ssm_scan(state, u, delta, Bm, Cm, z, A, D, num_valid=None, reset=None,
             impl: Optional[str] = None):
  """Advance every slot's state over its chunk (module docstring);
  returns ``(out, new_state)``.  ``num_valid=None`` takes every position
  as live, ``reset=None`` none as new; ``impl=None`` applies the dispatch
  rule to the shapes at hand, and takes the state as spread over chips
  whenever a multi-device mesh has been built (the serving engine
  resolves the impl from its own mesh and passes it)."""
  B, C = u.shape[:2]
  if num_valid is None:
    num_valid = jnp.full((B,), C, jnp.int32)
  if reset is None:
    reset = jnp.zeros((B,), bool)
  if impl is None:
    impl = resolve_ssm_scan_impl(state.shape, u.dtype, C,
                                 sharded=Env.get().mesh_built())
  if impl not in IMPLS:
    raise ValueError(f"impl must be one of {IMPLS} or None; got {impl!r}")
  if impl == "reference":
    return ssm_scan_reference(state, u, delta, Bm, Cm, z, A, D, num_valid,
                              reset)
  return ssm_scan_pallas(state, u, delta, Bm, Cm, z, A, D, num_valid,
                         reset, interpret=impl == "interpret")
