"""ZeRO memory benchmark — measured per-device bytes, off vs v0 vs v1.

Runs on the 8-virtual-device CPU mesh (dp=8) so the deltas are real
sharding effects, not estimates.  Prints one JSON line:

  {"zero_off": {...}, "zero_v0": {...}, "zero_v1": {...}}

with per-config argument (resident state) and temp bytes from XLA's
memory_analysis — the artifact VERDICT item 6 asks for.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# The dp=8 virtual CPU mesh is the measurement: pin it before jax loads.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if "xla_force_host_platform_device_count" not in f]
    + ["--xla_force_host_platform_device_count=8"])

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.parallel import (
    TrainState, create_sharded_train_state, make_train_step, parallelize)
from easyparallellibrary_tpu.runtime.zero import make_zero1_train_step


class Net(nn.Module):
  width: int = 2048

  @nn.compact
  def __call__(self, x):
    x = nn.Dense(self.width)(x)
    x = jnp.tanh(x)
    return nn.Dense(64)(x)


def measure(zero_level: str):
  env = epl.init(epl.Config({"zero.level": zero_level} if zero_level
                            else {}))
  with epl.replicate(1):
    model = Net()
  mesh = epl.current_plan().build_mesh()
  x = jnp.ones((32, 512))
  y = jnp.ones((32, 64))
  tx = optax.adam(1e-3)

  def init_fn(rng):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(rng, x)["params"], tx=tx)

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0), zero_level=zero_level)

  def loss_fn(params, batch, rng):
    pred = model.apply({"params": params}, batch["x"])
    return jnp.mean((pred - batch["y"]) ** 2), {}

  batch = {"x": x, "y": y}
  rng = jax.random.PRNGKey(1)
  if zero_level == "v1":
    step = make_zero1_train_step(loss_fn, mesh)
    step(state, batch, rng)                      # builds step.jitted
    state2, _ = create_sharded_train_state(
        init_fn, mesh, jax.random.PRNGKey(0), zero_level=zero_level)
    mem = step.jitted.lower(state2, batch, rng).compile().memory_analysis()
  else:
    step = parallelize(make_train_step(loss_fn), mesh, shardings)
    mem = step.jitted.lower(state, batch, rng).compile().memory_analysis()
  return {
      "argument_bytes": int(mem.argument_size_in_bytes),
      "temp_bytes": int(mem.temp_size_in_bytes),
      "output_bytes": int(mem.output_size_in_bytes),
  }


def measure_smap(zero_level: str):
  """ZeRO x smap pipeline engine (VERDICT r4 item 5): GPT on a
  stage2 x data4 mesh through the config-dispatched engine; with
  zero.level="v1" the engine's grad reduction is the explicit
  reduce-scatter-to-owner and opt state is owner-sharded."""
  from easyparallellibrary_tpu.models import GPT, GPTConfig
  from easyparallellibrary_tpu.models.gpt import make_gpt_train_step

  conf = {"pipeline.engine": "smap"}
  if zero_level:
    conf["zero.level"] = zero_level
  env = epl.init(epl.Config(conf))
  cfg = GPTConfig(vocab_size=512, num_layers=4, num_heads=8, d_model=256,
                  d_ff=1024, max_seq_len=64, dtype=jnp.float32,
                  pipeline_stages=2, num_micro_batch=2)
  with epl.replicate(1):
    model = GPT(cfg)
  mesh = env.cluster.build_mesh(stage=2)
  ids = jnp.asarray(np.random.RandomState(0).randint(
      0, cfg.vocab_size, (8, cfg.max_seq_len + 1)), jnp.int32)

  def init_fn(rng):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(rng, ids[:, :-1])["params"],
                             tx=optax.adam(1e-3))

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0), zero_level=zero_level)
  step = parallelize(make_gpt_train_step(model), mesh, shardings)
  mem = step.jitted.lower(
      state, {"ids": ids}, jax.random.PRNGKey(1)).compile(
      ).memory_analysis()
  return {
      "argument_bytes": int(mem.argument_size_in_bytes),
      "temp_bytes": int(mem.temp_size_in_bytes),
      "output_bytes": int(mem.output_size_in_bytes),
  }


def main():
  out = {}
  for name, level in [("zero_off", ""), ("zero_v0", "v0"),
                      ("zero_v1", "v1")]:
    out[name] = measure(level)
  off = out["zero_off"]["argument_bytes"]
  v1 = out["zero_v1"]["argument_bytes"]
  out["v1_vs_off_argument_ratio"] = round(v1 / off, 4)
  for name, level in [("smap_zero_off", ""), ("smap_zero_v1", "v1")]:
    out[name] = measure_smap(level)
  s_off = out["smap_zero_off"]["argument_bytes"]
  s_v1 = out["smap_zero_v1"]["argument_bytes"]
  out["smap_v1_vs_off_argument_ratio"] = round(s_v1 / s_off, 4)
  print(json.dumps(out))


if __name__ == "__main__":
  main()
