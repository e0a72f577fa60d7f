"""Cost-card fleet simulator: deterministic core, replica engine-mirror
contracts, fault injection, and full-fleet episode behavior.

The replay-fidelity anchor (golden chaos-heal episode) lives in
tests/test_sim_replay.py.
"""

import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.observability import slo as slo_lib
from easyparallellibrary_tpu.serving.scheduler import Request
from easyparallellibrary_tpu.sim import (
    CostModel, EventQueue, FaultEvent, FaultInjector, SimClock,
    SimFleet, SimReplica, SimReplicaDead, Workload, XorShift,
    actuation_sequence, death_and_recovery, make_workload)
from easyparallellibrary_tpu.sim.arrivals import (
    diurnal_times, overload_times, poisson_times, zipf_prompts)
from easyparallellibrary_tpu.utils import vclock


# ------------------------------------------------------------ sim core


def test_xorshift_deterministic_and_uniform_range():
  a, b = XorShift(42), XorShift(42)
  seq_a = [a.next_u64() for _ in range(100)]
  seq_b = [b.next_u64() for _ in range(100)]
  assert seq_a == seq_b
  assert seq_a != [XorShift(43).next_u64() for _ in range(100)]
  us = [XorShift(7).uniform() for _ in range(1)]
  rng = XorShift(7)
  us = [rng.uniform() for _ in range(1000)]
  assert all(0.0 <= u < 1.0 for u in us)
  # Seed 0 must not collapse to the xorshift fixed point.
  z = XorShift(0)
  assert len({z.next_u64() for _ in range(10)}) == 10


def test_simclock_monotone_and_jump():
  clk = SimClock()
  assert clk() == 0.0
  clk.advance(1.5)
  assert clk() == 1.5
  clk.advance_to(1.0)          # past target: no-op, never backwards
  assert clk() == 1.5
  clk.advance_to(3.0)
  assert clk() == 3.0
  with pytest.raises(ValueError):
    clk.advance(-0.1)


def test_event_queue_orders_by_time_then_insertion():
  q = EventQueue()
  q.push(2.0, "late")
  q.push(1.0, "early-a")
  q.push(1.0, "early-b")
  assert q.peek_time() == 1.0
  assert q.pop_due(1.0) == ["early-a", "early-b"]
  assert q.pop_due(5.0) == ["late"]
  assert not q


# ----------------------------------------------------------- arrivals


def test_arrival_processes_deterministic_and_ascending():
  for make in (lambda r: poisson_times(50.0, 2.0, r),
               lambda r: diurnal_times(10.0, 80.0, 2.0, 2.0, r),
               lambda r: overload_times(100.0, 30, 10, 3.0, r)):
    t1, t2 = make(XorShift(5)), make(XorShift(5))
    assert t1 == t2
    assert t1 == sorted(t1)
    assert len(t1) > 0
  assert poisson_times(50.0, 2.0, XorShift(5)) != poisson_times(
      50.0, 2.0, XorShift(6))


def test_overload_times_burst_faster_than_tail():
  times = overload_times(100.0, 200, 100, 3.0, XorShift(1))
  assert len(times) == 300
  burst = np.diff(times[:200]).mean()
  tail = np.diff(times[200:]).mean()
  assert burst < tail  # 3x capacity vs 0.4x capacity


def test_zipf_prompts_share_templates():
  prompts = zipf_prompts(200, XorShift(3), num_templates=8, plen=6)
  uniq = {p.tobytes() for p in prompts}
  assert len(uniq) <= 8
  assert all(p.shape == (6,) and p.dtype == np.int32 for p in prompts)


def test_make_workload_kinds_and_unknown():
  for kind in ("poisson", "diurnal", "overload"):
    wl = make_workload(kind, XorShift(2), duration_s=1.0,
                       rate_rps=50.0)
    assert len(wl.times) == len(wl.prompts) == len(wl.max_new)
  with pytest.raises(ValueError):
    make_workload("bogus", XorShift(2), duration_s=1.0, rate_rps=1.0)


# ---------------------------------------------------------- cost model


@pytest.mark.parametrize("sim,source,prefill,decode", [
    ({}, "default", None, None),
    ({"prefill_token_cost_s": 1e-3, "decode_token_cost_s": 2e-3},
     "config", 1e-3, 2e-3),
    ({"decode_token_cost_s": 2e-3}, "default+config", None, 2e-3),
])
def test_cost_model_from_config(sim, source, prefill, decode):
  """A step is priced from ``sim.*`` alone: a cost the configuration
  leaves at 0 is the default constant, whatever files lie around."""
  from easyparallellibrary_tpu.sim.replica import _DEFAULT_TOKEN_COST_S
  cm = CostModel.from_config(epl.Config({"sim": sim}))
  assert cm.source == source
  assert cm.prefill_token_cost_s == (prefill or _DEFAULT_TOKEN_COST_S)
  assert cm.decode_token_cost_s == (decode or _DEFAULT_TOKEN_COST_S)
  assert cm.step_overhead_s == 5e-5


def test_cost_model_step_time_linear():
  cm = CostModel(prefill_token_cost_s=1e-3, decode_token_cost_s=2e-3,
                 step_overhead_s=1e-4)
  assert cm.step_time(4, 3) == pytest.approx(1e-4 + 4e-3 + 6e-3)


# -------------------------------------------------- replica / fleet


def _sim_config(**over):
  conf = {
      "serving": {
          "num_slots": 4, "prefill_chunk": 4,
          "resilience": {"enabled": True, "queue_limit": 6},
          "router": {"heartbeat_s": 0.002},
      },
  }
  conf.update(over)
  return epl.Config(conf)


class _CaptureRegistry:
  def __init__(self):
    self.records = []

  def publish(self, step, metrics, namespace="train"):
    self.records.append((step, dict(metrics), namespace))


def test_sim_replica_serves_request_in_expected_steps():
  slo_lib.reset()
  config = _sim_config()
  epl.init(config)
  clk = SimClock()
  cost = CostModel(1e-3, 1e-3, 1e-4)
  reg = _CaptureRegistry()
  rep = SimReplica(0, config=config, registry=reg, clock=clk,
                   cost=cost, max_seq_len=64)
  assert rep.submit(Request(uid="r0", prompt=np.arange(6, dtype=np.int32),
                            max_new_tokens=8))
  steps = 0
  fins = []
  while rep.has_work:
    fins.extend(rep.step())
    steps += 1
  # ceil(6/4) prefill + (8 - 1) decode steps, then one idle-free drain.
  assert steps == 2 + 7
  assert [f.uid for f in fins] == ["r0"]
  assert rep.finished["r0"].finish_reason == "length"
  # Modeled time accrued, never wall time.
  assert rep.last_step_cost > 0
  # Per-step records landed under this replica's namespace with the
  # engine's resilient-record schema (the keys the SLO burn rules and
  # report.py consume).
  assert all(ns == "serving/replica0" for _, _, ns in reg.records)
  rec = reg.records[0][1]
  for key in ("active_slots", "slot_occupancy", "prefill_tokens",
              "decode_tokens", "step_time_s", "queue_depth",
              "degraded_level", "shed", "finished_requests"):
    assert key in rec, key


def test_sim_replica_idle_step_publishes_nothing():
  slo_lib.reset()
  config = _sim_config()
  epl.init(config)
  reg = _CaptureRegistry()
  rep = SimReplica(0, config=config, registry=reg, clock=SimClock(),
                   cost=CostModel(1e-3, 1e-3, 1e-4), max_seq_len=64)
  rep.step()
  # Engine contract: an idle plan returns without a record publish and
  # without advancing the publish step index.
  assert reg.records == []
  assert rep.last_step_cost == 0.0


def test_sim_replica_sheds_past_queue_limit():
  slo_lib.reset()
  config = _sim_config()
  epl.init(config)
  rep = SimReplica(0, config=config, clock=SimClock(),
                   cost=CostModel(1e-3, 1e-3, 1e-4), max_seq_len=64)
  admitted = sum(
      rep.submit(Request(uid=i, prompt=np.arange(6, dtype=np.int32),
                         max_new_tokens=4))
      for i in range(40))
  assert admitted < 40
  shed = [f for f in rep.finished.values() if f.finish_reason == "shed"]
  assert len(shed) == 40 - admitted
  assert rep.stats.shed_requests == len(shed)


def test_fault_injector_kill_revive_stall():
  slo_lib.reset()
  config = _sim_config()
  epl.init(config)
  clk = SimClock()
  cost = CostModel(1e-3, 1e-3, 1e-4)
  rep = SimReplica(0, config=config, clock=clk, cost=cost,
                   max_seq_len=64)
  inj = FaultInjector(death_and_recovery(1.0, 0, 2.0)
                      + [FaultEvent(at=4.0, kind="stall", replica=0,
                                    value=0.25)])
  assert inj.next_time() == 1.0
  inj.fire_due(0.5, [rep])
  rep.step()                      # still alive before the kill
  inj.fire_due(1.0, [rep])
  with pytest.raises(SimReplicaDead):
    rep.step()
  inj.fire_due(3.0, [rep])        # revive fired (due at 3.0)
  rep.step()
  inj.fire_due(4.0, [rep])        # stall: next busy step pays extra
  rep.submit(Request(uid="s", prompt=np.arange(6, dtype=np.int32),
                     max_new_tokens=2))
  rep.step()
  assert rep.last_step_cost > 0.25
  assert inj.pending == 0
  with pytest.raises(ValueError):
    FaultInjector([FaultEvent(at=0.0, kind="meteor", replica=0)])


def test_sim_fleet_overload_scales_up_and_back(tmp_path):
  slo_lib.reset()
  config = epl.Config({
      "serving": {
          "num_slots": 4, "prefill_chunk": 4,
          "resilience": {"enabled": True, "queue_limit": 6},
          "router": {"heartbeat_s": 0.002},
          "autotune": {"enabled": True, "hold_steps": 20},
          "autoscale": {"enabled": True, "min_replicas": 2,
                        "max_replicas": 4,
                        "scale_up_cooldown_s": 0.05,
                        "scale_down_cooldown_s": 0.3,
                        "flap_window_s": 1.0, "sync_spawn": True},
      },
      "observability": {"slo": {
          "enabled": True, "shed_objective": 0.9,
          "fast_window": 3, "slow_window": 6,
          "fast_burn": 1.0, "slow_burn": 1.0}},
  })
  epl.init(config)
  fleet = SimFleet(num_replicas=2, config=config, num_slots=4,
                   prefill_chunk=4, max_seq_len=64,
                   cost=CostModel(1e-3, 1e-3, 1e-4))
  wl = make_workload("overload", XorShift(9), duration_s=1.0,
                     rate_rps=300.0, plen=6, max_new=8)
  summary = fleet.run(wl)
  assert summary["served"] + summary["shed"] == summary["requests"]
  assert summary["scale_ups"] >= 1
  assert summary["replicas_peak"] > 2
  assert summary["replicas_final_live"] == 2   # drained back down
  assert summary["slo_breaches"] >= 1
  seq = actuation_sequence()
  actuators = {e["actuator"] for e in seq}
  assert "autoscale" in actuators
  # The episode ran entirely on virtual time and cleaned up after
  # itself: the ambient clock must be real again.
  assert not vclock.installed()
  assert summary["wall_s"] < 30.0
  assert summary["sim_duration_s"] > 0


def test_sim_fleet_replica_death_heals_via_failover():
  slo_lib.reset()
  config = epl.Config({
      "serving": {
          "num_slots": 4, "prefill_chunk": 4,
          "resilience": {"enabled": True, "queue_limit": 8},
          "router": {"heartbeat_s": 0.002},
      },
      "observability": {"slo": {
          "enabled": True, "shed_objective": 0.9,
          "replicas_down": True,
          "fast_window": 3, "slow_window": 6,
          "fast_burn": 1.0, "slow_burn": 1.0}},
  })
  epl.init(config)
  fleet = SimFleet(num_replicas=3, config=config, num_slots=4,
                   prefill_chunk=4, max_seq_len=64,
                   cost=CostModel(1e-3, 1e-3, 1e-4))
  wl = make_workload("poisson", XorShift(4), duration_s=2.0,
                     rate_rps=100.0, plen=6, max_new=8)
  faults = FaultInjector(death_and_recovery(0.2, 0, 50.0))
  summary = fleet.run(wl, faults=faults)
  # The dead replica stayed dead (revive lands after the episode's
  # horizon of interest); its work failed over and the fleet served on.
  assert summary["faults_fired"] == 2
  assert summary["served"] > 0
  assert summary["replicas_final_live"] >= 2
