"""Record the golden chaos-heal episode for simulator replay fidelity.

Drives a REAL two-replica in-process fleet (tiny GPT, compiled fused
steps, the full SLO monitor + autotuner + autoscaler stack) through
ONE deterministic overload episode — burst above capacity, breach,
scale-up, autotune escalation, recovery, drain-back — and writes
everything the simulator needs to reproduce it to
``sim_chaos_heal.json`` beside this file:

* the exact config knobs, fleet geometry and request shapes;
* the arrival times (seeded xorshift, stored verbatim);
* the virtual-clock discipline (``fixed_dt`` per sweep, ``idle_dt``
  per settle sweep) — the episode advances a FIXED virtual dt per
  router sweep instead of measured wall time, which is what makes the
  real episode itself deterministic and step-comparable to the sim;
* the real fleet's actuation sequence (``sim.fleet.
  actuation_sequence``: actuator, rule, knob transitions, order) and
  its breach/recovery counters.

The episode loop is ``sim.fleet.drive_episode`` — the SAME function
the simulator runs — so the replay pin (tests/test_sim_replay.py)
compares policy behavior, not two hand-written harnesses.
``autoscale.sync_spawn`` is pinned on so the real scale-up takes the
synchronous ``Router.add_replica`` path the simulator's replica factory
mirrors.

Run: ``make sim-golden`` (on the CPU; re-run only when a policy change
legitimately changes the actuation story — the diff of the golden file
then documents exactly what changed).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
  jax.config.update("jax_platforms", "cpu")

import easyparallellibrary_tpu as epl  # noqa: E402
from easyparallellibrary_tpu.models import GPT, GPTConfig  # noqa: E402
from easyparallellibrary_tpu.observability import slo as slo_lib  # noqa: E402
from easyparallellibrary_tpu.observability.registry import (  # noqa: E402
    MetricRegistry)
from easyparallellibrary_tpu.serving import Router  # noqa: E402
from easyparallellibrary_tpu.sim.arrivals import (  # noqa: E402
    Workload, overload_times)
from easyparallellibrary_tpu.sim.engine import SimClock, XorShift  # noqa: E402
from easyparallellibrary_tpu.sim.fleet import (  # noqa: E402
    actuation_sequence, drive_episode, warm_fleet)

GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "sim_chaos_heal.json")

# Episode geometry.  All of it lands in the golden file; the comments
# explain the choices, the FILE is the contract.
NUM_REPLICAS = 2
NUM_SLOTS = 4
CHUNK = 4
QUEUE_LIMIT = 6
MAX_SEQ_LEN = 64
PLEN = 6
MAX_NEW = 8
WARM_MAX_NEW = 2
FIXED_DT = 2e-3      # virtual seconds per busy sweep
IDLE_DT = 5e-3       # virtual seconds per settle sweep
SETTLE_STEPS = 400
ARRIVAL_SEED = 11
N_BURST = 120
N_RECOVER = 40
OVERLOAD_FACTOR = 3.0

# Fleet capacity in VIRTUAL time is analytic, not probed: each request
# takes ceil(plen/chunk) + max_new - 1 engine steps, a sweep advances
# FIXED_DT, and the base fleet serves NUM_REPLICAS * NUM_SLOTS
# requests concurrently.
STEPS_PER_REQUEST = -(-PLEN // CHUNK) + MAX_NEW - 1
CAPACITY_RPS = (NUM_REPLICAS * NUM_SLOTS) / (STEPS_PER_REQUEST * FIXED_DT)


def _config_dict() -> dict:
  return {
      "serving": {
          "num_slots": NUM_SLOTS, "prefill_chunk": CHUNK,
          "resilience": {"enabled": True, "queue_limit": QUEUE_LIMIT},
          "router": {"heartbeat_s": 0.002},
          "autotune": {"enabled": True, "hold_steps": 20},
          # sync_spawn: scale-up must take the deterministic in-sweep
          # add_replica path on BOTH sides of the replay contract.
          "autoscale": {"enabled": True, "min_replicas": 2,
                        "max_replicas": 4,
                        "scale_up_cooldown_s": 0.05,
                        "scale_down_cooldown_s": 0.3,
                        "flap_window_s": 1.0,
                        "sync_spawn": True},
      },
      "observability": {"slo": {
          "enabled": True, "shed_objective": 0.9,
          "fast_window": 3, "slow_window": 6,
          "fast_burn": 1.0, "slow_burn": 1.0}},
  }


def record(path: str = GOLDEN_PATH) -> dict:
  slo_lib.reset()
  config_dict = _config_dict()
  config = epl.Config(config_dict)
  epl.init(config)
  cfg = GPTConfig(vocab_size=256, num_layers=2, num_heads=8,
                  d_model=128, d_ff=512, max_seq_len=MAX_SEQ_LEN,
                  dtype=jnp.float32)
  model = GPT(cfg)
  params = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, PLEN), jnp.int32))["params"]
  # One shared prompt: token values do not steer any actuation signal
  # (sim/replica.py module docstring), and one prompt keeps the golden
  # file small and the affinity keys identical on both sides.
  prompt = np.arange(1, PLEN + 1, dtype=np.int32)
  arrivals = overload_times(CAPACITY_RPS, N_BURST, N_RECOVER,
                            OVERLOAD_FACTOR, XorShift(ARRIVAL_SEED))
  n = len(arrivals)
  clock = SimClock()
  registry = MetricRegistry()
  router = Router(model, params, num_replicas=NUM_REPLICAS,
                  config=config, registry=registry, clock=clock,
                  num_slots=NUM_SLOTS, prefill_chunk=CHUNK)
  warm_fleet(router, clock, prompt, WARM_MAX_NEW)
  workload = Workload(times=arrivals, prompts=[prompt] * n,
                      max_new=[MAX_NEW] * n)
  loop = drive_episode(router, clock, workload, fixed_dt=FIXED_DT,
                       idle_dt=IDLE_DT, settle_steps=SETTLE_STEPS)
  sequence = actuation_sequence()
  monitor = slo_lib.get_monitor()
  shed = [u for u in range(n)
          if u in router.finished
          and router.finished[u].finish_reason == "shed"]
  golden = {
      "description": "chaos-heal episode recorded from a REAL "
                     "2-replica fleet on a fixed-dt virtual clock; "
                     "the simulator must replay the same actuation "
                     "sequence (tests/golden/"
                     "record_sim_chaos_heal.py)",
      "config": config_dict,
      "num_replicas": NUM_REPLICAS,
      "num_slots": NUM_SLOTS,
      "chunk": CHUNK,
      "max_seq_len": MAX_SEQ_LEN,
      "prompt": [int(t) for t in prompt],
      "max_new": MAX_NEW,
      "warm_max_new": WARM_MAX_NEW,
      "fixed_dt": FIXED_DT,
      "idle_dt": IDLE_DT,
      "settle_steps": SETTLE_STEPS,
      "capacity_rps": CAPACITY_RPS,
      "overload_factor": OVERLOAD_FACTOR,
      "arrival_seed": ARRIVAL_SEED,
      "arrivals": [float(t) for t in arrivals],
      "sequence": sequence,
      "counters": {
          "requests": n,
          "shed": len(shed),
          "busy_sweeps": loop["busy_sweeps"],
          "idle_jumps": loop["idle_jumps"],
          "replicas_peak": loop["replicas_peak"],
          "breaches": monitor.breaches if monitor else 0,
          "recoveries": monitor.recoveries if monitor else 0,
          "actuations": monitor.actuations if monitor else 0,
      },
  }
  os.makedirs(os.path.dirname(path), exist_ok=True)
  with open(path, "w") as f:
    json.dump(golden, f, indent=1)
    f.write("\n")
  print(f"golden episode -> {path}")
  print(json.dumps(golden["counters"], indent=1))
  print(f"actuation sequence: {len(sequence)} event(s)")
  for ev in sequence:
    print(f"  {ev.get('actuator')}: {ev.get('rule')} "
          f"{ev.get('knobs')}")
  return golden


if __name__ == "__main__":
  record()
