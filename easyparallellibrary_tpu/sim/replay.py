"""Golden-episode replay: run a recorded real-fleet chaos episode
through the simulator and return the simulated actuation sequence.

The fidelity contract (ISSUE: "the simulator must be trustworthy
enough to search policy space"): a chaos-heal episode recorded from
the REAL fleet — overload burst, breach, autotune escalation,
scale-up, recovery, drain-back — replayed in the simulator must
produce the SAME actuation sequence: same actuators, same knob
transitions, same order.  ``tests/golden/record_sim_chaos_heal.py``
records the golden file (tests/golden/sim_chaos_heal.json) by driving
a real two-replica fleet on a fixed-dt virtual clock; this module
replays it sim-side; ``tests/test_sim_replay.py`` pins the equality quick.

What makes equality achievable rather than aspirational: both sides
run the identical policy objects over the identical per-step record
schema, the episode clock is virtual and fixed-dt on BOTH sides, and
with ``itl_slo_s = 0`` every actuation signal is count- or
clock-driven (sim/replica.py module docstring) — so the only degrees
of freedom left are the ones the golden file pins (config knobs,
arrival times, request shapes, dt).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.observability import slo as slo_lib
from easyparallellibrary_tpu.sim.arrivals import Workload
from easyparallellibrary_tpu.sim.fleet import (
    SimFleet, actuation_sequence, warm_fleet)

GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "tests", "golden", "sim_chaos_heal.json")


def load_golden(path: str = GOLDEN_PATH) -> Dict[str, Any]:
  with open(path) as f:
    return json.load(f)


def replay(golden: Dict[str, Any]) -> Dict[str, Any]:
  """Replay ``golden`` in the simulator; returns the episode summary
  plus ``sequence`` (the simulated actuation sequence, normalized the
  same way the recorder normalized the real one).

  Resets the ambient SLO monitor: a replay is a fresh episode and its
  breach/actuation log must start empty.
  """
  slo_lib.reset()
  config = epl.Config(golden["config"])
  epl.init(config)
  prompt = np.asarray(golden["prompt"], dtype=np.int32)
  fleet = SimFleet(
      num_replicas=int(golden["num_replicas"]), config=config,
      num_slots=int(golden["num_slots"]),
      prefill_chunk=int(golden["chunk"]),
      max_seq_len=int(golden["max_seq_len"]))
  # Warm phase, exactly as recorded: the real fleet needed its compiled
  # steps warmed outside the timed episode; the recorded step/record
  # counts include those steps, so the replay performs the same
  # submits and drain (the simulator has nothing to compile — the
  # point is record-stream parity, not the compile itself).
  warm_fleet(fleet.router, fleet.clock, prompt,
             int(golden["warm_max_new"]))
  n = len(golden["arrivals"])
  workload = Workload(
      times=[float(t) for t in golden["arrivals"]],
      prompts=[prompt] * n,
      max_new=[int(golden["max_new"])] * n)
  summary = fleet.run(
      workload, fixed_dt=float(golden["fixed_dt"]),
      idle_dt=float(golden["idle_dt"]),
      settle_steps=int(golden["settle_steps"]))
  summary["sequence"] = actuation_sequence()
  monitor = slo_lib.get_monitor()
  summary["breaches"] = monitor.breaches if monitor else 0
  summary["recoveries"] = monitor.recoveries if monitor else 0
  return summary
