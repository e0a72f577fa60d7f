"""ResNet — image model family (the reference's configs 1 and 3).

The reference repo has no in-tree model zoo (README.md:18 points at
FastNN); the benchmark matrix needs ResNet-50 for the pure-DP config and
the `split(8)` large-vocab-head config.

TPU notes:
  * Default norm is GroupNorm: batch-size independent and purely
    functional (no mutable batch-stats collection), the common TPU
    substitution.  ``norm="batch"`` selects true BatchNorm — pair it
    with :class:`parallel.MutableTrainState` /
    :func:`parallel.make_mutable_train_step` (pass ``train=True`` and
    ``mutable=["batch_stats"]`` through ``model.apply``).  Under GSPMD
    the batch is one global (data-sharded) array, so the batch
    statistics are computed over the GLOBAL batch — XLA inserts the
    cross-replica reduction the reference would hand-build.
  * The classifier head is an `ops.Dense`, so a ``with epl.split():``
    around model application makes a huge-vocab head column-parallel —
    the reference's README flagship example (README.md:58-70).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Sequence, Tuple

import jax.numpy as jnp
from flax import linen as nn

from easyparallellibrary_tpu.ops import Dense


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
  stage_sizes: Tuple[int, ...] = (3, 4, 6, 3)   # ResNet-50
  num_filters: int = 64
  num_classes: int = 1000
  dtype: Any = jnp.bfloat16
  param_dtype: Any = jnp.float32
  norm_groups: int = 32
  norm: str = "group"                           # group | batch


def resnet18_config(**kw):
  return ResNetConfig(stage_sizes=(2, 2, 2, 2), **kw)


def resnet50_config(**kw):
  return ResNetConfig(stage_sizes=(3, 4, 6, 3), **kw)


def _norm_factory(cfg: ResNetConfig, filters: int, train: bool):
  if cfg.norm == "batch":
    return partial(nn.BatchNorm, use_running_average=not train,
                   momentum=0.9, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype)
  if cfg.norm == "group":
    return partial(nn.GroupNorm, num_groups=min(cfg.norm_groups, filters),
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)
  raise ValueError(f"norm must be 'group' or 'batch'; got {cfg.norm!r}")


class BottleneckBlock(nn.Module):
  cfg: ResNetConfig
  filters: int
  strides: int = 1
  train: bool = False

  @nn.compact
  def __call__(self, x):
    cfg = self.cfg
    conv = partial(nn.Conv, use_bias=False, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype)
    norm = _norm_factory(cfg, self.filters, self.train)
    residual = x
    y = conv(self.filters, (1, 1))(x)
    y = nn.relu(norm()(y))
    y = conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
    y = nn.relu(norm()(y))
    y = conv(self.filters * 4, (1, 1))(y)
    y = norm()(y)
    if residual.shape != y.shape:
      residual = conv(self.filters * 4, (1, 1),
                      strides=(self.strides, self.strides),
                      name="proj")(residual)
      residual = norm(name="proj_norm")(residual)
    return nn.relu(residual + y)


class ResNet(nn.Module):
  cfg: ResNetConfig

  @nn.compact
  def __call__(self, x, train: bool = False):
    from easyparallellibrary_tpu.runtime.amp import resolve_model_dtypes
    cfg = resolve_model_dtypes(self.cfg)
    x = x.astype(cfg.dtype)
    x = nn.Conv(cfg.num_filters, (7, 7), strides=(2, 2), use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="conv_init")(x)
    x = nn.relu(_norm_factory(cfg, cfg.num_filters, train)()(x))
    x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
    for i, n_blocks in enumerate(cfg.stage_sizes):
      for j in range(n_blocks):
        strides = 2 if i > 0 and j == 0 else 1
        x = BottleneckBlock(cfg, cfg.num_filters * 2 ** i, strides,
                            train=train, name=f"stage{i}_block{j}")(x)
    x = jnp.mean(x, axis=(1, 2))
    # Classifier head: column-parallel under an active `split` scope.
    logits = Dense(cfg.num_classes, dtype=jnp.float32,
                   param_dtype=cfg.param_dtype, name="head")(x)
    return logits
