"""Golden replay-fidelity pin — the simulator subsystem's anchor.

The recorded REAL-fleet chaos-heal episode
(tests/golden/record_sim_chaos_heal.py -> tests/golden/
sim_chaos_heal.json) must replay in the simulator to
the IDENTICAL actuation sequence: same actuators, same knob
transitions, same order.  This is what licenses using the simulator
for policy search at 100-1000-replica scale (docs/simulator.md) —
the policies are the real objects, and this pin proves the modeled
physics feeds them the same decision stream the real fleet produced.
"""

import pytest

from easyparallellibrary_tpu.sim import replay as replay_lib


@pytest.mark.quick
def test_replay_matches_recorded_chaos_heal_episode():
  """The simulator replays the recorded real-fleet chaos-heal episode
  to the identical actuation sequence — and the same shed / sweep /
  breach counts, which pins the record streams the decisions were made
  FROM, not just the decisions."""
  golden = replay_lib.load_golden()
  out = replay_lib.replay(golden)
  assert out["sequence"] == golden["sequence"]
  assert out["shed"] == golden["counters"]["shed"]
  assert out["busy_sweeps"] == golden["counters"]["busy_sweeps"]
  assert out["breaches"] == golden["counters"]["breaches"]
  assert out["recoveries"] == golden["counters"]["recoveries"]
  assert out["replicas_peak"] == golden["counters"]["replicas_peak"]


def test_golden_episode_is_nontrivial():
  """Guard against the golden file degrading into a no-op episode: the
  fidelity claim is only interesting if the recorded episode actually
  exercised breach -> escalate -> scale -> recover -> de-escalate."""
  golden = replay_lib.load_golden()
  seq = golden["sequence"]
  actuators = {e["actuator"] for e in seq}
  assert {"autoscale", "autotune"} <= actuators
  assert golden["counters"]["shed"] > 0
  assert golden["counters"]["breaches"] > 0
  assert golden["counters"]["recoveries"] > 0
  assert golden["counters"]["replicas_peak"] > golden["num_replicas"]


def test_replay_is_itself_deterministic():
  golden = replay_lib.load_golden()
  a = replay_lib.replay(golden)
  b = replay_lib.replay(golden)
  assert a["sequence"] == b["sequence"]
  assert a["shed"] == b["shed"]


def test_replay_unaffected_by_reactor_knob():
  """ISSUE 19 regression: the simulator drives the fleet through the
  sweep-compat ``router.step()`` path, so turning on the reactor
  (``serving.router.reactor`` — the readiness-driven run()/front-door
  driver, serving/reactor.py) must not perturb the golden episode:
  the actuation sequence replays event-for-event identical."""
  golden = replay_lib.load_golden()
  baseline = replay_lib.replay(golden)
  reactored = dict(golden)
  reactored["config"] = {**golden["config"]}
  serving = dict(reactored["config"].get("serving", {}))
  serving["router"] = {**serving.get("router", {}), "reactor": True}
  reactored["config"]["serving"] = serving
  out = replay_lib.replay(reactored)
  assert out["sequence"] == baseline["sequence"] == golden["sequence"]
  assert out["shed"] == baseline["shed"]
  assert out["busy_sweeps"] == baseline["busy_sweeps"]
  assert out["breaches"] == baseline["breaches"]
  assert out["recoveries"] == baseline["recoveries"]
  assert out["replicas_peak"] == baseline["replicas_peak"]
