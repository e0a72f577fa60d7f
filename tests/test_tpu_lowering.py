"""The Pallas entry points must LOWER for the TPU, checked without one.

``lower(lowering_platforms=("tpu",))`` runs the Pallas TPU lowering —
block-shape rules included — on any host.  The CPU tests run the kernels
interpreted, where those rules do not apply, so a kernel Mosaic would
refuse stayed green here until it met a chip (the paged kernel did).
Shapes are the serving engine's and chip_smoke.py's; only tracing happens, so
these take seconds.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.kernels import (
    flash_attention, paged_attention_pallas)

fa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.flash_attention")


def _lowers_for_tpu(fn, *args) -> str:
  return jax.jit(fn).trace(*args).lower(
      lowering_platforms=("tpu",)).as_text()


def _flash_loss(q, k, v):
  return jnp.sum(flash_attention(q, k, v, causal=True)
                 .astype(jnp.float32) ** 2)


FLASH_NAMES = ("flash_fwd", "flash_dkv", "flash_dq")


def _kernel_names(text):
  return sorted(re.findall(r'kernel_name = "([^"]+)"', text))


@pytest.mark.parametrize("B,S,H,D,dtype", [
    (2, 1024, 16, 64, jnp.bfloat16),      # chip_smoke.py's: resident
    (8, 1024, 20, 64, jnp.bfloat16),      # gpt2l-train-zero1-4chip, a chip
    (1, 16384, 2, 64, jnp.bfloat16),      # past _RESIDENT_MAX_BYTES
    (1, 1024, 16, 64, jnp.float32),
])
def test_flash_fwd_and_grad_lower_for_tpu(monkeypatch, B, S, H, D, dtype):
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  x = jax.ShapeDtypeStruct((B, S, H, D), dtype)
  text = _lowers_for_tpu(jax.value_and_grad(_flash_loss, (0, 1, 2)),
                         x, x, x)
  assert text.count("tpu_custom_call") == 3     # fwd, dk/dv, dq
  # The benchmark's readers find the kernels by these names.
  assert _kernel_names(text) == sorted(FLASH_NAMES)


def test_flash_on_a_mesh_lowers_per_shard(monkeypatch):
  """On a multi-device mesh jax refuses to lower a Mosaic call outside a
  manual region; the kernel entry wraps itself in one, over batch and
  heads."""
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  x = jax.ShapeDtypeStruct(
      (8, 1024, 16, 64), jnp.bfloat16,
      sharding=NamedSharding(mesh, P("data", None, "model", None)))
  text = _lowers_for_tpu(jax.value_and_grad(_flash_loss, (0, 1, 2)),
                         x, x, x)
  assert text.count("tpu_custom_call") == 3
  # Each call sees its chip's shard, in rows: batch 8/4, heads 16/2 of 64
  # side by side; nothing is head-major.
  assert "tensor<2x1024x512xbf16>" in text
  assert "tensor<2x8x1024x64xbf16>" not in text


def test_heads_a_chip_that_do_not_fill_lane_tiles_stay_head_major(
    monkeypatch):
  """The rule reads the heads ONE chip holds: 6 heads of 64 over
  ``model:2`` are 3 a chip, no whole pairs, so each call keeps its chip's
  ``[B, H, S, D]`` shard behind the transposes."""
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4,model:2"}))
  mesh = epl.Env.get().cluster.build_mesh()
  x = jax.ShapeDtypeStruct(
      (8, 1024, 6, 64), jnp.bfloat16,
      sharding=NamedSharding(mesh, P("data", None, "model", None)))
  text = _lowers_for_tpu(jax.value_and_grad(_flash_loss, (0, 1, 2)),
                         x, x, x)
  assert _kernel_names(text) == sorted(FLASH_NAMES)
  assert "tensor<2x3x1024x64xbf16>" in text


def test_the_train_cell_lowers_per_shard_under_its_three_names(monkeypatch):
  """`gpt2l-train-zero1-4chip`: a global batch of 32 x 1024 over
  ``data:4``, 20 heads of 64 in bfloat16.  Forward and gradient lower
  with the three custom calls under the names the benchmark reads, each
  on its chip's ``[8, 20, 1024, 64]``."""
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4"}),
           devices=jax.devices()[:4])
  mesh = epl.Env.get().cluster.build_mesh()
  x = jax.ShapeDtypeStruct(
      (32, 1024, 20, 64), jnp.bfloat16,
      sharding=NamedSharding(mesh, P("data", None, None, None)))
  text = _lowers_for_tpu(jax.value_and_grad(_flash_loss, (0, 1, 2)),
                         x, x, x)
  assert _kernel_names(text) == sorted(FLASH_NAMES)
  assert "tensor<8x1024x1280xbf16>" in text
  assert "tensor<8x20x1024x64xbf16>" not in text


def test_the_train_cell_reads_q_k_v_from_the_one_projection(monkeypatch):
  """As ``models/gpt.py`` calls it: the fused projection's ``[32, 1024,
  3 x 1280]`` goes in whole, each chip's kernels take their ``[8, 1024,
  3840]`` three times (the column blocks are ``in_specs``, not slices) and
  write ``[8, 1024, 1280]``; no array of rank 4 with a 64-wide minor
  dimension is left for XLA to relay out (lse and delta, ``[8, 20, 8,
  1024]`` float32, are the only head-major ones)."""
  monkeypatch.setattr(fa, "_interpret", lambda: False)
  epl.init(epl.Config({"cluster.mesh_shape": "data:4"}),
           devices=jax.devices()[:4])
  mesh = epl.Env.get().cluster.build_mesh()
  qkv = jax.ShapeDtypeStruct(
      (32, 1024, 3840), jnp.bfloat16,
      sharding=NamedSharding(mesh, P("data", None, None)))
  loss = lambda qkv: jnp.sum(fa.flash_attention_qkv(
      qkv, 20, causal=True).astype(jnp.float32) ** 2)
  text = _lowers_for_tpu(jax.value_and_grad(loss), qkv)
  assert _kernel_names(text) == sorted(FLASH_NAMES)
  assert "tensor<8x1024x3840xbf16>" in text
  assert not re.findall(r"tensor<[\dx]*x64xbf16>", text)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_lowers_for_tpu(dtype):
  T, H, hd, bs, NB, MB = 16, 16, 64, 16, 65, 64   # the engine's shapes
  text = _lowers_for_tpu(
      lambda *a: paged_attention_pallas(*a, interpret=False),
      jax.ShapeDtypeStruct((T, H, hd), dtype),
      jax.ShapeDtypeStruct((NB, bs, H, hd), dtype),
      jax.ShapeDtypeStruct((NB, bs, H, hd), dtype),
      jax.ShapeDtypeStruct((T, MB), jnp.int32),
      jax.ShapeDtypeStruct((T,), jnp.int32))
  assert text.count("tpu_custom_call") == 1
