"""Jamba — the hybrid decoder: Mamba layers with one attention layer a
period, a gated MLP after every mixer.

Lieber et al. 2024 as ``transformers`` runs ``model_type: jamba`` with one
expert: pre-RMSNorm residual layers; layer ``i`` mixes by attention where
``i % attn_layer_period == attn_layer_offset`` (grouped K/V heads, NO
positional encoding of any kind) and by a Mamba-1 block elsewhere (causal
depthwise convolution, input-dependent ``dt``/``B``/``C`` each through an
RMSNorm of its own, selective scan, gate); a final RMSNorm; the head tied
to the embedding.  ``perfbench/reference/jamba.py`` holds the same
equations in plain float32 and the tests compare the two.

Serving only.  The model exposes the surface the continuous-batching
engine steps through (``model.cfg``, ``model.apply(..., decode=True,
slot_cursors=..., mutable=["cache"])``, ``models.slot_core.slot_step_logits``)
and keeps TWO kinds of per-slot state in the ``cache`` collection, which
``serving/kv_cache.py`` allocates from :meth:`JambaConfig.layer_kinds`:

* attention layers: ``cached_key`` / ``cached_value`` under a cursor,
  through ``models.slot_core.slot_cache_attend``: ``[slots, Lc, H_kv x hd]``,
  kept in rows, where that width fills whole lane tiles (the published
  one head of 128 does), ``[slots, Lc, H_kv, hd]`` elsewhere
  (serving/kv_cache.py, order note);
* Mamba layers: ``conv_state`` ``[slots, d_conv - 1, d_inner]`` (the last
  inputs of the convolution) and ``ssm_state`` float32 ``[slots, d_state,
  d_inner]`` (state-major: the channels ride the lanes).

A K/V cache tolerates a partly valid chunk — garbage beyond the cursor is
masked and later overwritten.  A recurrence does not: both Mamba states
advance by exactly ``num_valid`` tokens a slot (0 leaves them bit for
bit), and a slot that starts a request (``reset``) starts from zero state.
Rolling a request back by moving a cursor cannot roll a recurrence back:
the paged layout, prefix caching, speculation and the guarded retry refuse
this model (``serving/_capabilities.py``).  Training (the scan's backward)
is not built.

Precision: the residual stream and the matmuls in ``cfg.dtype``; norms,
the convolution, ``softplus``, the scan and the states in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from easyparallellibrary_tpu.models.blocks import (
    GatedMLP, RMSNorm, advance_window, boxed, dense, gqa_causal_attention,
    uniform)
from easyparallellibrary_tpu.models.layer_kinds import ATTENTION, MAMBA
from easyparallellibrary_tpu.models.slot_core import (
    SplitLayer, child_of, flat_ids, missing_slot_cache, slot_cache_attend,
    slot_layers)
from easyparallellibrary_tpu.ops import Embedding
from easyparallellibrary_tpu.ops.layers import HeldParams


@dataclasses.dataclass(frozen=True)
class JambaConfig:
  vocab_size: int = 65536
  num_layers: int = 28
  d_model: int = 2560
  d_ff: int = 8192
  num_heads: int = 20
  num_kv_heads: int = 1
  attn_layer_period: int = 14
  attn_layer_offset: int = 7
  mamba_d_state: int = 16
  mamba_d_conv: int = 4
  mamba_expand: int = 2
  mamba_dt_rank: int = 160
  rms_norm_eps: float = 1e-6
  max_seq_len: int = 8192            # served context; the cache's length
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.bfloat16

  @property
  def head_dim(self) -> int:
    return self.d_model // self.num_heads

  @property
  def d_inner(self) -> int:
    return self.mamba_expand * self.d_model

  def layer_kinds(self) -> tuple:
    """Per layer, which state it keeps: :data:`ATTENTION` where ``i %
    attn_layer_period == attn_layer_offset``, :data:`MAMBA` elsewhere."""
    return tuple(
        ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
        else MAMBA for i in range(self.num_layers))


class AttentionMixer(nn.Module):
  cfg: JambaConfig
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None

  @nn.compact
  def __call__(self, h, slot_cursors=None, num_valid=None, rows=None,
               part=None):
    cfg = self.cfg
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    out_proj = lambda: dense(cfg, cfg.d_model, "o")
    # In slot mode the whole call is its three parts in turn
    # (models/slot_core.py:SplitLayer), ``h`` from the second on the carry.
    if part in (None, "pre"):
      B, S, _ = h.shape
      q = dense(cfg, H * hd, "q")(h).reshape(B, S, H, hd)
      k = dense(cfg, Hkv * hd, "k")(h).reshape(B, S, Hkv, hd)
      v = dense(cfg, Hkv * hd, "v")(h).reshape(B, S, Hkv, hd)
      if not self.decode:
        return out_proj()(gqa_causal_attention(q, k, v, cfg.dtype).reshape(
            B, S, H * hd))
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows); the window write and the attend take
      # their operands as [slots, C, ...].
      h = (), tuple(rows.to_slots(t[:, 0]) for t in (q, k, v))
      if part == "pre":
        return h
    if part in (None, "mix"):
      ck = self.variable("cache", "cached_key", missing_slot_cache)
      cv = self.variable("cache", "cached_value", missing_slot_cache)
      out, ck.value, cv.value = slot_cache_attend(
          *h[1], ck.value, cv.value, slot_cursors, cfg.dtype,
          write_impl=self.kv_write_impl, attn_impl=self.slot_attn_impl,
          num_valid=num_valid)
      h = (), out
      if part == "mix":
        return h
    return out_proj()(rows.to_flat(h[1]).reshape(-1, 1, H * hd))


def _dt_bias_init(key, shape, dtype=jnp.float32):
  """Mamba's own: the inverse softplus of a step drawn log-uniform in
  [1e-3, 1e-1]."""
  dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
               * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
  dt = jnp.maximum(dt, 1e-4)
  return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
  """``log(1..N)`` down the state axis of ``[N, Di]``."""
  n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
  return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)


class MambaMixer(HeldParams, nn.Module):
  """The Mamba-1 mixer with Jamba's three inner norms.  Parameters the
  recurrence depends on are float32 (``A_log`` and ``D`` state-major
  ``[d_state, d_inner]`` / ``[d_inner]``, the ``dt`` bias); the
  convolution's taps are ``[d_conv, d_inner]``, tap ``d_conv - 1`` on the
  current token."""
  cfg: JambaConfig
  decode: bool = False
  ssm_scan_impl: Optional[str] = None

  @nn.compact
  def __call__(self, h, num_valid=None, reset=None, rows=None):
    from easyparallellibrary_tpu.kernels.ssm_scan import ssm_scan
    cfg = self.cfg
    Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    f32 = jnp.float32
    uz = dense(cfg, 2 * Di, "in_proj")(h)
    if self.decode:
      # ``h`` is the step's token-flat batch [T, 1, D]
      # (models/slot_core.py:SlotRows): the convolution over a slot's window
      # and the scan over its state run as [slots, C, ...], between the
      # two projections.
      uz = rows.to_slots(uz[:, 0])
    B, C, _ = uz.shape
    u, z = uz[..., :Di], uz[..., Di:]
    conv_w = self.param("conv_w", boxed(uniform(K ** -0.5), 2), (K, Di),
                        cfg.param_dtype)
    conv_b = self.param("conv_b", boxed(uniform(K ** -0.5), 1), (Di,),
                        cfg.param_dtype)
    if self.decode:
      conv_var = self.variable("cache", "conv_state", missing_slot_cache)
      ssm_var = self.variable("cache", "ssm_state", missing_slot_cache)
      window, state = conv_var.value, ssm_var.value
      if reset is not None:
        window = jnp.where(reset[:, None, None],
                           jnp.zeros((), window.dtype), window)
    else:
      window = jnp.zeros((B, K - 1, Di), u.dtype)
      state = jnp.zeros((B, N, Di), f32)
    full = jnp.concatenate([window.astype(u.dtype), u], axis=1)
    w32 = jnp.asarray(conv_w, f32)
    conv = sum(full[:, j:j + C].astype(f32) * w32[j] for j in range(K)) \
        + jnp.asarray(conv_b, f32)
    u = jax.nn.silu(conv).astype(cfg.dtype)
    if self.decode:
      conv_var.value = advance_window(full, num_valid, K - 1)

    dbc = dense(cfg, R + 2 * N, "x_proj")(u)
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, f32, name=name)
    dt = norm("dt_norm")(dbc[..., :R])
    Bm = norm("b_norm")(dbc[..., R:R + N])
    Cm = norm("c_norm")(dbc[..., R + N:])
    dt_w = self.param("dt_proj", boxed(uniform(R ** -0.5), 2), (R, Di),
                      cfg.param_dtype)
    dt_b = self.param("dt_bias", boxed(_dt_bias_init, 1), (Di,), f32)
    delta = jax.nn.softplus(
        jnp.matmul(dt.astype(cfg.dtype), jnp.asarray(dt_w, cfg.dtype),
                   preferred_element_type=f32) + dt_b)
    a_log = self.param("A_log", boxed(_a_log_init, 2), (N, Di), f32)
    d_skip = self.param("D", boxed(nn.initializers.ones_init(), 1), (Di,),
                        f32)
    y, state = ssm_scan(state, u, delta, Bm, Cm, z, -jnp.exp(a_log), d_skip,
                        num_valid=num_valid, reset=reset,
                        impl=self.ssm_scan_impl)
    if self.decode:
      ssm_var.value = state
      y = rows.to_flat(y)[:, None]
    return dense(cfg, cfg.d_model, "out_proj")(y)


class JambaBlock(nn.Module):
  cfg: JambaConfig
  kind: str
  decode: bool = False
  kv_write_impl: Optional[str] = None
  slot_attn_impl: Optional[str] = None
  ssm_scan_impl: Optional[str] = None

  @nn.compact
  def __call__(self, x, slot_cursors=None, num_valid=None, reset=None,
               rows=None, part=None, carry=None):
    cfg = self.cfg
    norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
    if self.kind == ATTENTION:
      # In three parts where the step asks (models/slot_core.py:SplitLayer).
      mixer = AttentionMixer(cfg, decode=self.decode,
                             kv_write_impl=self.kv_write_impl,
                             slot_attn_impl=self.slot_attn_impl, name="attn")
      if part == "mix":
        return mixer(carry, slot_cursors, num_valid, rows, part)
      mixed = mixer(carry if part == "post" else norm("norm_in")(x),
                    slot_cursors, num_valid, rows, part)
      if part == "pre":
        return mixed
    else:
      mixed = MambaMixer(cfg, decode=self.decode,
                         ssm_scan_impl=self.ssm_scan_impl,
                         name="mamba")(norm("norm_in")(x), num_valid, reset,
                                       rows)
    x = x + mixed
    return x + GatedMLP(cfg, name="mlp")(norm("norm_ff")(x))


class Jamba(nn.Module):
  """Decoder-only hybrid LM.  ``__call__(ids) -> logits`` is the full
  forward from zero state; ``decode=True`` with ``slot_cursors`` is the
  serving engine's slot mode (module docstring): ``num_valid`` int32
  ``[slots]`` says how many of the chunk's positions each slot's
  recurrent state takes (``None``: all), ``reset`` bool ``[slots]`` which
  slots start from zero state.  In slot mode the position-wise layers run
  on the token-flat batch ``rows`` describes (models/slot_core.py:SlotRows;
  every position of every slot when none is handed in) and the logits
  are those of the rows it names."""

  cfg: JambaConfig

  @nn.compact
  def __call__(self, ids, decode: bool = False, return_hidden: bool = False,
               slot_cursors=None, num_valid=None, reset=None,
               kv_write_impl=None, slot_attn_impl=None,
               ssm_scan_impl=None, rows=None):
    cfg = self.cfg
    if decode and slot_cursors is None:
      raise ValueError(
          "Jamba decodes in slot mode only: pass slot_cursors= and a slot "
          "cache from serving.kv_cache.allocate_kv_cache (the serving "
          "engine does)")
    if slot_cursors is not None and not decode:
      raise ValueError("slot_cursors is a decode-mode argument (serving "
                       "engine); pass decode=True")
    tok = Embedding(cfg.vocab_size, cfg.d_model, parallel="none",
                    param_dtype=cfg.param_dtype, name="embed")
    if decode:
      rows, ids = flat_ids(ids, slot_cursors, num_valid, rows)
    x = tok(ids).astype(cfg.dtype)
    def layer(i, kind):
      block = child_of(lambda parent: JambaBlock(
          cfg, kind, decode=decode, kv_write_impl=kv_write_impl,
          slot_attn_impl=slot_attn_impl, ssm_scan_impl=ssm_scan_impl,
          name=f"block_{i}", parent=parent))
      call = lambda mdl, rows, x, **part: block(mdl)(
          x, slot_cursors, num_valid, reset, rows, **part)
      # An attention layer's K/V window stays outside a two-width step's
      # conditionals; a Mamba layer's state is a few MB and stands inside.
      return SplitLayer(call) if kind == ATTENTION else call
    layers = [layer(i, kind) for i, kind in enumerate(cfg.layer_kinds())]
    x = slot_layers(self, rows, x, layers)
    if decode:
      # The last norm and the head run on the rows that are read.
      x = rows.head_rows(x)
    x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
    if return_hidden:
      return x
    return tok.attend(x)
