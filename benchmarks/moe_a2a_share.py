"""MoE all-to-all time share — BASELINE row 5's named metric.

METHOD (clearly labeled, per VERDICT r3 item 4): no multi-chip hardware
is available, so this is a COMPILED-PROGRAM DECOMPOSITION on the
8-device virtual CPU mesh plus a hardware model — not a trace
measurement.  The expert-parallel train step (explicit a2a dispatch,
moe_impl="a2a", tokens sharded over data x expert) is compiled for an
expert=4 x data=2 mesh; the lowered HLO's `all-to-all` ops are summed by
byte volume (these are exactly the dispatch/combine collectives GSPMD
inserts for the expert-sharded einsums — the role of the reference's
NCCL AllToAll kernels, /root/reference/csrc/communicators/
nccl_all_to_all.cc:22-77), and the program's total FLOPs come from XLA
cost analysis.  The time share then follows from the chip model

    t_a2a  = a2a_bytes / ICI_BW        (per-chip effective a2a GB/s)
    t_flop = flops     / (MFU * peak)  (compute at an assumed MFU)
    share  = t_a2a / (t_a2a + t_flop)

reported for TPU v5e defaults (peak 197 bf16 TFLOP/s, 45 GB/s effective
per-chip a2a bandwidth, 0.4 MFU) — swap via env vars EPL_A2A_BW_GBS /
EPL_A2A_MFU / EPL_A2A_PEAK_TFLOPS.  A four-chip profiler trace should
replace this model (the reference gets it implicitly from its
comm kernels' profiler visibility).

Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.profiler import flops as flops_mod  # noqa: E402
from easyparallellibrary_tpu.models import GPT, GPTConfig  # noqa: E402
from easyparallellibrary_tpu.models.gpt import gpt_loss  # noqa: E402
from easyparallellibrary_tpu.parallel import (  # noqa: E402
    TrainState, create_sharded_train_state, make_train_step, parallelize)

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "f64": 8, "s8": 1, "u8": 1, "pred": 1}


def _hlo_a2a_bytes(hlo_text: str) -> int:
  """Sum output-byte volume of all all-to-all ops in lowered HLO.

  Handles both array results (`= f32[...] all-to-all(`) and the
  tuple-of-per-peer-buffers form (`= (f32[...], ...) all-to-all(`)."""
  total = 0
  for line in hlo_text.splitlines():
    if " all-to-all(" not in line:
      continue
    result = line.split(" all-to-all(")[0]
    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", result):
      n = 1
      for d in dims.split(","):
        if d:
          n *= int(d)
      total += n * _DTYPE_BYTES.get(dt, 4)
  return total


def main():
  env = epl.init()
  mesh = env.cluster.build_mesh(expert=4)
  sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
  cfg = GPTConfig(vocab_size=2048, num_layers=4, num_heads=8,
                  d_model=512, d_ff=2048, max_seq_len=256,
                  dtype=jnp.bfloat16, num_experts=4, moe_every=2,
                  moe_impl="a2a")
  model = GPT(cfg)
  B = 8
  ids = jnp.asarray(np.random.RandomState(0).randint(
      0, cfg.vocab_size, (B, cfg.max_seq_len + 1)), jnp.int32)

  def init_fn(rng):
    return TrainState.create(
        apply_fn=model.apply,
        params=model.init(rng, ids[:, :-1])["params"],
        tx=optax.adamw(1e-3))

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0))
  from jax.sharding import PartitionSpec as P
  step = parallelize(
      make_train_step(lambda p, b, r: gpt_loss(model, p, b, r)),
      mesh, shardings,
      batch_spec=P(("data", "expert")))
  lowered = step.jitted.lower(state, {"ids": ids},
                            jax.random.PRNGKey(1))
  compiled = lowered.compile()
  hlo = compiled.as_text()
  cost = compiled.cost_analysis() or {}
  flops = float(cost.get("flops", 0.0))
  n_chips = len(jax.devices())
  a2a_bytes = _hlo_a2a_bytes(hlo)

  bw = float(os.environ.get("EPL_A2A_BW_GBS", "45")) * 1e9
  mfu = float(os.environ.get("EPL_A2A_MFU", "0.4"))
  peak = float(os.environ.get(
      "EPL_A2A_PEAK_TFLOPS",
      flops_mod.PEAK_FLOPS["TPU v5e"] / 1e12)) * 1e12
  # Per-chip quantities: HLO is the per-device SPMD program, so its
  # all-to-all shapes and cost flops are already per-chip.
  t_a2a = a2a_bytes / bw
  t_flop = flops / (mfu * peak)
  share = t_a2a / max(t_a2a + t_flop, 1e-30)

  print(json.dumps({
      "metric": "moe_a2a_time_share",
      "value": round(share, 4),
      "unit": "fraction_of_step",
      "method": "compiled-HLO byte/FLOP decomposition on the virtual "
                "mesh + v5e hardware model (NOT a trace measurement)",
      "detail": {
          "mesh": sizes,
          "model": {"d_model": cfg.d_model, "layers": cfg.num_layers,
                    "experts": cfg.num_experts, "moe_every": cfg.moe_every,
                    "seq": cfg.max_seq_len, "batch": B},
          "a2a_bytes_per_step_per_chip": a2a_bytes,
          "n_a2a_ops": len(re.findall(r"\s+all-to-all\(", hlo)),
          "flops_per_step_per_chip": flops,
          "assumed": {"ici_gbs": bw / 1e9, "mfu": mfu,
                      "peak_tflops": peak / 1e12},
          "t_a2a_us": round(t_a2a * 1e6, 1),
          "t_flop_us": round(t_flop * 1e6, 1),
      },
  }), flush=True)


if __name__ == "__main__":
  main()
