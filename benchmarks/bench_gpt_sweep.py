"""GPT training-step sweep across attention impls / remat / batch sizes.

Companion to bench.py for tuning the headline number on real hardware.
Timing forces execution with a scalar fetch and subtracts the measured
null round-trip (benchmarks/_common.py has the recipe).
"""
import os, sys, time, json
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np, optax
import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.models.gpt import gpt_flops_per_token, gpt_loss
from easyparallellibrary_tpu.profiler.flops import peak_flops_per_chip
from easyparallellibrary_tpu.parallel import (
    TrainState, create_sharded_train_state, make_train_step, parallelize)

def run(attn, remat, batch=8):
    epl.Env._instance = None
    env = epl.init()
    cfg = GPTConfig(vocab_size=32768, num_layers=24, num_heads=16,
                    d_model=1024, d_ff=4096, max_seq_len=1024,
                    dtype=jnp.bfloat16, remat=remat, remat_policy="dots",
                    attn_impl=attn)
    mesh = epl.current_plan().build_mesh()
    ids = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, 1025)), jnp.int32)
    batch_d = {"ids": ids}
    tx = optax.adamw(3e-4)
    model = GPT(cfg)
    def init_fn(r):
        return TrainState.create(apply_fn=model.apply,
                                 params=model.init(r, ids[:, :-1])["params"], tx=tx)
    rng = jax.random.PRNGKey(0)
    state, sh = create_sharded_train_state(init_fn, mesh, rng)
    step = parallelize(make_train_step(lambda p,b,r: gpt_loss(model,p,b,r)), mesh, sh)
    for _ in range(2):
        state, m = step(state, batch_d, rng)
    float(jax.device_get(m["loss"]))
    tiny = jax.jit(lambda v: v+1); float(jax.device_get(tiny(jnp.float32(0))))
    t0=time.perf_counter(); float(jax.device_get(tiny(jnp.float32(1)))); null=time.perf_counter()-t0
    steps=10
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch_d, rng)
    float(jax.device_get(m["loss"]))
    dt = (time.perf_counter()-t0-null)/steps
    toks = batch*1024/dt
    mfu = toks*gpt_flops_per_token(cfg,1024)/peak_flops_per_chip()
    print(f"attn={attn} remat={remat} batch={batch}: {dt*1e3:.1f}ms/step {toks:.0f} tok/s MFU={mfu:.3f}")
    return mfu

import traceback
for attn, remat, b in [("xla", True, 8), ("pallas_flash", True, 8), ("pallas_flash", False, 8)]:
    try:
        run(attn, remat, b)
    except Exception as e:
        print(f"attn={attn} remat={remat} batch={b}: FAILED {type(e).__name__}: {str(e)[:200]}")
