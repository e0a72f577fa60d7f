"""Single-chip MFU for the non-GPT BASELINE models: ResNet-50 and
BERT-Large.

BASELINE.md's matrix rows 1 (ResNet DP) and 2 (BERT pipeline) are
multi-chip configurations; this measures their *models* at realistic
sizes on the one real chip so the matrix has hardware numbers for the
compute side (the multi-chip scaling is validated functionally on the
virtual CPU mesh).  Prints one JSON line per model:

  python benchmarks/single_chip_models.py            # both
  python benchmarks/single_chip_models.py resnet50   # one

Timing forces execution via scalar fetch minus the measured null
round-trip (benchmarks/_common.py).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmarks._common import force, null_round_trip  # noqa: E402
from bench import peak_flops_per_chip  # noqa: E402

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu import ops  # noqa: E402
from easyparallellibrary_tpu.parallel import (  # noqa: E402
    TrainState, create_sharded_train_state, make_train_step, parallelize)


def _train_throughput(model, loss_fn, batch, init_arg, steps=10, warmup=2):
  epl.init()
  mesh = epl.current_plan().build_mesh()
  rng = jax.random.PRNGKey(0)

  def init_fn(r):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(r, init_arg)["params"],
                             tx=optax.adamw(1e-3))

  state, shardings = create_sharded_train_state(init_fn, mesh, rng)
  step = parallelize(make_train_step(loss_fn), mesh, shardings)
  for _ in range(warmup):
    state, m = step(state, batch, rng)
  force(m["loss"])
  null = null_round_trip()
  t0 = time.perf_counter()
  for _ in range(steps):
    state, m = step(state, batch, rng)
  force(m["loss"])
  dt = max(time.perf_counter() - t0 - null, 1e-9) / steps
  return dt, float(m["loss"])


def _bench_resnet(metric: str, on_tpu: bool, B: int, hw: int,
                  classes: int):
  """Shared ResNet-50 measurement scaffold (plain row 1 and the
  large-vocab-head row 3 differ only in shape and the head-flops term)."""
  from easyparallellibrary_tpu.models import ResNet, resnet50_config
  cfg = resnet50_config(num_classes=classes,
                        dtype=jnp.bfloat16 if on_tpu else jnp.float32)
  model = ResNet(cfg)
  r = np.random.RandomState(0)
  x = jnp.asarray(r.randn(B, hw, hw, 3),
                  jnp.bfloat16 if on_tpu else jnp.float32)
  y = jnp.asarray(r.randint(0, classes, (B,)), jnp.int32)

  def loss_fn(p, b, rng):
    logits = model.apply({"params": p}, b["x"])
    return jnp.mean(
        ops.distributed_sparse_softmax_cross_entropy_with_logits(
            b["y"], logits)), {}

  dt, loss = _train_throughput(model, loss_fn, {"x": x, "y": y}, x[:1])
  # ResNet-50 at 224x224: ~4.09 GFLOP forward per image (backbone);
  # + the classifier head matmul (2*feat*classes, negligible at 1000
  # classes, dominant term of row 3's 131k-class head); train ~3x fwd.
  fwd_flops = 4.09e9 * (hw / 224.0) ** 2 + 2.0 * 2048 * classes
  mfu = 3 * fwd_flops * B / dt / peak_flops_per_chip() if on_tpu else 0.0
  return {"metric": metric, "value": round(mfu, 4), "unit": "mfu",
          "detail": {"batch": B, "image": hw, "classes": classes,
                     "step_ms": round(dt * 1e3, 2),
                     "images_per_sec": round(B / dt, 1),
                     "loss": round(loss, 4)}}


def bench_resnet50(on_tpu: bool):
  B, hw, classes = (64, 224, 1000) if on_tpu else (8, 32, 64)
  return _bench_resnet("resnet50_train_mfu", on_tpu, B, hw, classes)


def bench_bert_large(on_tpu: bool):
  from easyparallellibrary_tpu.models import Bert, bert_large_config
  from easyparallellibrary_tpu.models.bert import bert_mlm_loss
  if on_tpu:
    B, S = 8, 512
    cfg = bert_large_config(max_seq_len=S, dtype=jnp.bfloat16, remat=True,
                            attn_impl="pallas_flash")
  else:
    B, S = 4, 32
    cfg = bert_large_config(num_layers=2, num_heads=4, d_model=64,
                            d_ff=128, vocab_size=256, max_seq_len=S,
                            dtype=jnp.float32)
  model = Bert(cfg)
  r = np.random.RandomState(0)
  ids = jnp.asarray(r.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
  batch = {"ids": ids, "labels": ids,
           "mask": jnp.asarray(r.rand(B, S) < 0.15, jnp.float32)}

  dt, loss = _train_throughput(
      model, lambda p, b, rng: bert_mlm_loss(model, p, b, rng),
      batch, ids)
  D, F, L, V = cfg.d_model, cfg.d_ff, cfg.num_layers, cfg.vocab_size
  per_tok = 6.0 * (L * (4 * D * D + 2 * D * F) + D * V) + 6.0 * L * 2 * D * S
  mfu = per_tok * B * S / dt / peak_flops_per_chip() if on_tpu else 0.0
  return {"metric": "bert_large_train_mfu", "value": round(mfu, 4),
          "unit": "mfu",
          "detail": {"batch": B, "seq": S, "step_ms": round(dt * 1e3, 2),
                     "tokens_per_sec": round(B * S / dt, 1),
                     "loss": round(loss, 4)}}


def bench_tp_head(on_tpu: bool):
  """BASELINE row 3's model on one chip: ResNet backbone + large-vocab
  classifier head trained with the distributed CE.  The split(8) tensor
  parallelism is validated functionally on the virtual mesh
  (tests/test_split_tp.py); this measures the model's compute side so
  the row has a hardware number."""
  B, hw, classes = (32, 224, 131072) if on_tpu else (4, 32, 512)
  return _bench_resnet("resnet_tp_head_train_mfu", on_tpu, B, hw, classes)


def bench_gpt_moe(on_tpu: bool):
  """BASELINE row 5's model on one chip: GPT-MoE (Switch-style top-1,
  experts every 2nd block).  The expert-axis all-to-all time share is
  measured separately on the virtual mesh
  (benchmarks/moe_a2a_share.py); this captures samples/sec/chip + MFU
  for the compute side."""
  from easyparallellibrary_tpu.models import GPT, GPTConfig
  from easyparallellibrary_tpu.models.gpt import (gpt_flops_per_token,
                                                  gpt_loss)
  if on_tpu:
    cfg = GPTConfig(vocab_size=32768, num_layers=12, num_heads=16,
                    d_model=1024, d_ff=4096, max_seq_len=1024,
                    dtype=jnp.bfloat16, remat=True,
                    remat_policy="dots_flash", attn_impl="pallas_flash",
                    num_experts=8, moe_every=2, loss_chunk=256)
    B = 8
  else:
    cfg = GPTConfig(vocab_size=512, num_layers=2, num_heads=4,
                    d_model=64, d_ff=128, max_seq_len=32,
                    dtype=jnp.float32, num_experts=4, moe_every=2)
    B = 4
  model = GPT(cfg)
  r = np.random.RandomState(0)
  ids = jnp.asarray(r.randint(0, cfg.vocab_size,
                              (B, cfg.max_seq_len + 1)), jnp.int32)

  dt, loss = _train_throughput(
      model, lambda p, b, rng: gpt_loss(model, p, b, rng),
      {"ids": ids}, ids[:, :-1])
  S = cfg.max_seq_len
  mfu = (gpt_flops_per_token(cfg, S) * B * S / dt /
         peak_flops_per_chip()) if on_tpu else 0.0
  return {"metric": "gpt_moe_train_mfu", "value": round(mfu, 4),
          "unit": "mfu",
          "detail": {"batch": B, "seq": S, "experts": cfg.num_experts,
                     "step_ms": round(dt * 1e3, 2),
                     "tokens_per_sec": round(B * S / dt, 1),
                     "loss": round(loss, 4)}}


def main():
  which = sys.argv[1:] or ["resnet50", "bert_large", "tp_head", "gpt_moe"]
  on_tpu = jax.devices()[0].platform == "tpu"
  benches = {"resnet50": bench_resnet50, "bert_large": bench_bert_large,
             "tp_head": bench_tp_head, "gpt_moe": bench_gpt_moe}
  for name in which:
    out = benches[name](on_tpu)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
  main()
