"""Benchmark harness — flagship GPT-350M training step on a TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": ...}

It measures on a TPU or exits non-zero saying why: no accelerator, an
unknown ``device_kind`` (no peak FLOP/s on record), or any error in the
measurement itself.  There is no smaller run on another platform and no
carried-forward number.

The reference publishes no numeric baselines (BASELINE.md: published == {});
its north star for this framework is >=40% MFU on GPT-family training
(BASELINE.json).  `vs_baseline` is therefore achieved_MFU / 0.40.

Timing rule: host clock around a chain of steps that ends in
``jax.block_until_ready`` on the last step's outputs (chip_smoke.py
prints the observation this rests on).  Every successful measurement is
appended to BENCH_EVIDENCE.json with its raw chain timings.
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

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import (
    gpt_flops_per_token, make_gpt_train_step)
from easyparallellibrary_tpu.parallel import (
    TrainState, create_sharded_train_state, parallelize)
# Single source of truth for MFU denominators; peak_flops_per_chip is
# re-exported because benchmarks/ import it from bench.
from easyparallellibrary_tpu.profiler.flops import (
    peak_flops_info, peak_flops_per_chip)  # noqa: F401
from easyparallellibrary_tpu.utils import bench_evidence, compile_cache

METRIC = "gpt350m_train_mfu"

# Largest batch first; the smaller ones are tried only when the larger
# one is refused for memory (RESOURCE_EXHAUSTED), never on another error.
BATCH_CANDIDATES = (16, 12, 8)


def gpt350m_config(**overrides) -> GPTConfig:
  """GPT-350M as benchmarked: 24L, d 1024, 16 heads, d_ff 4096, vocab
  32768, S 1024, bf16.

  loss_chunk: the vocab-32k LM head was the round-1 memory bottleneck —
  chunked CE keeps the [B,S,V] logits out of HBM (tested equal to the
  full loss).  pallas_flash + dots_flash: the 512-block flash kernel
  removes the [B,H,S,S] score temps, and the dots_flash remat policy
  saves the kernel outputs so the backward never re-runs the forward
  kernel."""
  kw = dict(vocab_size=32768, num_layers=24, num_heads=16, d_model=1024,
            d_ff=4096, max_seq_len=1024, dtype=jnp.bfloat16, remat=True,
            attn_impl="pallas_flash", remat_policy="dots_flash",
            loss_chunk=256)
  kw.update(overrides)
  return GPTConfig(**kw)


def seeded_batch(cfg: GPTConfig, batch_size: int, seed: int = 0):
  ids = np.random.RandomState(seed).randint(
      0, cfg.vocab_size, (batch_size, cfg.max_seq_len + 1))
  return {"ids": jnp.asarray(ids, jnp.int32)}


def build_trainer(model: GPT, mesh, batch, seed: int = 0):
  """``(state, step)`` through the library's normal entry points: a
  sharded AdamW train state and the config-dispatched GPT train step
  compiled over ``mesh`` (what examples/train_gpt.py does)."""
  tx = optax.adamw(3e-4, weight_decay=0.01)

  def init_fn(r):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(r, batch["ids"][:, :-1])["params"], tx=tx)

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(seed))
  return state, parallelize(make_gpt_train_step(model), mesh, shardings)


def largest_batch_trainer(model: GPT, mesh, candidates=BATCH_CANDIDATES,
                          per_replica: int = 1):
  """Build the trainer at the first candidate batch that fits and take
  its first step (compile and first execution are where a batch too
  large for the chip is refused); ``per_replica`` scales the candidates
  to a global batch.  Returns ``(state, step, batch, first_metrics)``."""
  rng = jax.random.PRNGKey(0)
  for i, cand in enumerate(candidates):
    batch = seeded_batch(model.cfg, cand * per_replica)
    state = step = None
    try:
      state, step = build_trainer(model, mesh, batch)
      state, metrics = step(state, batch, rng)
      return state, step, batch, jax.block_until_ready(metrics)
    except jax.errors.JaxRuntimeError as e:
      if "RESOURCE_EXHAUSTED" not in str(e) or i == len(candidates) - 1:
        raise
      print(f"bench: batch {cand} out of device memory, trying "
            f"{candidates[i + 1]}", file=sys.stderr)
  raise ValueError("no batch candidates")


def require_tpu() -> jax.Device:
  """The first device, which must be a TPU: a measurement path that
  finds no chip fails, it does not fall back."""
  dev = jax.devices()[0]
  if dev.platform != "tpu":
    raise SystemExit(
        f"no TPU: jax {jax.__version__} found platform {dev.platform!r} "
        f"({dev.device_kind!r}, {len(jax.devices())} device(s)); this "
        "program measures on a TPU only")
  return dev


def measure() -> dict:
  dev = require_tpu()
  n_chips = len(jax.devices())
  peak, _ = peak_flops_info(dev)
  steps, warmup, chains = 10, 2, 3

  # Sweep overrides (benchmarks/mfu_sweep.sh).  A typo here must fail
  # loudly, not silently measure a different configuration than the
  # label claims.
  attn = os.environ.get("EPL_BENCH_ATTN", "pallas_flash")
  remat_policy = os.environ.get("EPL_BENCH_REMAT", "dots_flash")
  if attn not in ("xla", "pallas_flash"):
    raise ValueError(f"EPL_BENCH_ATTN must be xla|pallas_flash: {attn}")
  if remat_policy not in ("nothing", "dots", "dots_flash", "everything"):
    raise ValueError(f"EPL_BENCH_REMAT invalid: {remat_policy}")
  cfg = gpt350m_config(
      attn_impl=attn, remat_policy=remat_policy,
      loss_chunk=int(os.environ.get("EPL_BENCH_LOSS_CHUNK", "256")))
  candidates = tuple(int(b) for b in os.environ.get(
      "EPL_BENCH_BATCH", ",".join(map(str, BATCH_CANDIDATES))).split(","))

  epl.init()
  with epl.replicate(1):
    model = GPT(cfg)
  mesh = epl.current_plan().build_mesh()

  t0 = time.perf_counter()
  state, step, batch, metrics = largest_batch_trainer(
      model, mesh, candidates=candidates, per_replica=n_chips)
  setup_s = time.perf_counter() - t0
  batch_size = batch["ids"].shape[0]
  rng = jax.random.PRNGKey(0)
  for _ in range(warmup - 1):
    state, metrics = step(state, batch, rng)

  chain_times = []
  for _ in range(chains):
    t0 = time.perf_counter()
    for _ in range(steps):
      state, metrics = step(state, batch, rng)
    jax.block_until_ready((state, metrics))
    chain_times.append(time.perf_counter() - t0)
  dt = float(np.median(chain_times))

  seq = cfg.max_seq_len
  tokens_per_step = batch_size * seq
  tokens_per_sec = tokens_per_step * steps / dt
  flops_per_token = gpt_flops_per_token(cfg, seq)
  mfu = tokens_per_sec * flops_per_token / n_chips / peak
  # Resident buffers, and what the backend reserved to run programs
  # (the step's temporaries); their sum is the peak.
  mem = dev.memory_stats()

  result = {
      "metric": METRIC,
      "value": round(mfu, 4),
      "unit": "mfu",
      "vs_baseline": round(mfu / 0.40, 4),
      "detail": {
          "device": {"platform": dev.platform, "kind": dev.device_kind,
                     "count": n_chips},
          "tokens_per_sec_per_chip": round(tokens_per_sec / n_chips, 1),
          "step_time_ms": round(1000 * dt / steps, 2),
          "chain_times_s": [round(t, 4) for t in chain_times],
          "setup_s": round(setup_s, 1),
          "compile_cache": os.environ[compile_cache.ENV_VAR],
          "peak_flops_denominator": peak,
          "loss": round(float(metrics["loss"]), 4),
          "peak_hbm_gb": round((mem["peak_bytes_in_use"]
                                + mem["peak_bytes_reserved"]) / 2 ** 30, 2),
          "peak_bytes_in_use_gb": round(
              mem["peak_bytes_in_use"] / 2 ** 30, 2),
          "batch_size": batch_size,
          "loss_chunk": cfg.loss_chunk,
      },
  }
  bench_evidence.append_record({
      "metric": METRIC,
      "value": result["value"],
      "unit": "mfu",
      "device": dev.device_kind,
      "raw": {
          "chain_times_s": [round(t, 6) for t in chain_times],
          "steps_per_chain": steps,
          "tokens_per_step": tokens_per_step,
          "flops_per_token": flops_per_token,
          "peak_flops_per_chip": peak,
      },
      "config": {
          "model": "gpt350m", "batch": batch_size, "seq": seq,
          "attn": cfg.attn_impl, "remat_policy": cfg.remat_policy,
          "loss_chunk": cfg.loss_chunk, "dtype": "bfloat16",
      },
  })
  return result


def main():
  compile_cache.configure()
  print(json.dumps(measure()), flush=True)


if __name__ == "__main__":
  main()
