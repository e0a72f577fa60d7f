"""Blue/green checkpoint rollout (serving/rollout.py): zero-downtime
cutover with an SLO-watched canary and automatic rollback.

The quick contract pins: a full rollout under live traffic loses zero
requests, every response is attributable to exactly one checkpoint
version, and all compile counts stay <= 1 per replica; a canary-scoped
SLO breach triggers automatic rollback with the blue stream bit-exact
vs a never-rolled fleet; and the fault-free guard — rollout enabled
but never invoked is bit-identical to the baseline with zero
actuations.  The policy units pin the version-aware dispatch split and
the cross-version replay fences (scheduler, transport, placement).
`make chaos-rollout` runs the slow mid-rollout SIGKILL episode.
"""

import json
import os
import time

import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.observability import slo as slo_lib
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.observability.registry import MetricRegistry
from easyparallellibrary_tpu.observability.slo import SLOMonitor, SLORule
from easyparallellibrary_tpu.runtime.saver import (
    checkpoint_fingerprint, save_checkpoint)
from easyparallellibrary_tpu.serving import Request, Router
from easyparallellibrary_tpu.serving.prefix_cache import (
    PrefixCache, block_prefix_keys)
from easyparallellibrary_tpu.serving.scheduler import FCFSScheduler
from easyparallellibrary_tpu.testing.factories import tiny_gpt

FACTORY = "easyparallellibrary_tpu.testing.factories:tiny_gpt"


@pytest.fixture(autouse=True)
def _drop_ambient_observability():
  yield
  trace_lib.reset()
  slo_lib.reset()


def _prompts(n, lengths=(5, 3, 7, 2), vocab=64, seed=0):
  r = np.random.RandomState(seed)
  return [r.randint(0, vocab, (lengths[i % len(lengths)],)).astype(
      np.int32) for i in range(n)]


def _oracle(model, params, prompt, max_new):
  import jax.numpy as jnp
  from easyparallellibrary_tpu.models.gpt import generate
  return np.asarray(
      generate(model, params, jnp.asarray(prompt)[None], max_new))[0]


class FakeClock:
  def __init__(self, t=0.0):
    self.t = t

  def __call__(self):
    return self.t

  def advance(self, dt):
    self.t += dt


# ------------------------------------------------------- config & units


def test_rollout_config_validation():
  with pytest.raises(ValueError, match="canary_frac"):
    epl.Config({"serving": {"rollout": {"canary_frac": 0.0}}})
  with pytest.raises(ValueError, match="canary_frac"):
    epl.Config({"serving": {"rollout": {"canary_frac": 1.5}}})
  with pytest.raises(ValueError, match="min_replicas"):
    epl.Config({"serving": {"rollout": {"min_replicas": 0}}})
  with pytest.raises(ValueError, match="spawn_timeout_s"):
    epl.Config({"serving": {"rollout": {"spawn_timeout_s": 0.0}}})
  with pytest.raises(ValueError, match="canary_hold_s"):
    epl.Config({"serving": {"rollout": {"canary_hold_s": -1.0}}})
  conf = epl.Config({"serving": {"rollout": {"rules": "ttft_p99"}}})
  assert conf.serving.rollout.rules == ("ttft_p99",)
  assert conf.serving.rollout.enabled is False


def test_prefix_keys_version_salted():
  """Version 0 is byte-identical to the pre-versioning hash (every
  existing affinity/cache pin keeps passing); any other version
  produces a DISJOINT key space at every depth — blue-era affinity
  entries can never name a green replica."""
  p = np.arange(16, dtype=np.int32)
  assert block_prefix_keys(p, 4) == block_prefix_keys(p, 4, version=0)
  v0, v1 = (block_prefix_keys(p, 4, version=v) for v in (0, 1))
  assert len(v0) == len(v1)
  assert not set(v0) & set(v1)
  assert (block_prefix_keys(p, 4, version=1)
          != block_prefix_keys(p, 4, version=2))
  short = np.asarray([1, 2], np.int32)          # sub-block fallback key
  assert (block_prefix_keys(short, 4, version=0)
          != block_prefix_keys(short, 4, version=1))


def test_prefix_cache_version_scoped_roots():
  """Two caches at different checkpoint versions key their radix roots
  disjointly: identical token content registered under v1 is invisible
  to a v2 match (block content under different weights is different KV
  — reuse across versions would be silent corruption)."""
  from easyparallellibrary_tpu.serving import BlockAllocator
  tokens = np.arange(1, 13, dtype=np.int32)       # 3 full blocks
  alloc = BlockAllocator(num_blocks=32, block_size=4)
  c0 = PrefixCache(alloc, block_size=4)
  c1 = PrefixCache(alloc, block_size=4, version=1)
  assert c0.version == 0 and c1.version == 1
  owned0 = [alloc.alloc() for _ in range(3)]
  owned1 = [alloc.alloc() for _ in range(3)]
  assert c0.register(tokens, 3, owned0) == 3
  assert c1.register(tokens, 3, owned1) == 3
  # Each cache matches only its OWN version's blocks for identical
  # token content — the roots live in disjoint key spaces.
  assert c0.match(tokens) == owned0[:2]
  assert c1.match(tokens) == owned1[:2]
  # Version 0 stays byte-compatible: an unversioned cache is version 0.
  assert PrefixCache(alloc, block_size=4).version == 0


def test_scheduler_refuses_cross_version_restore():
  sched = FCFSScheduler(num_slots=2, prefill_chunk=4, max_seq_len=32,
                        checkpoint_version=1)
  req = Request(uid="r1", prompt=np.asarray([1, 2, 3], np.int32),
                max_new_tokens=4, checkpoint_version=1)
  snap = {"request": req.snapshot(), "generated": [7],
          "requeues": 0, "first_token_emitted": True,
          "submitted_at": 0.0}
  # Same version restores; so does an unpinned (None) legacy snapshot.
  assert sched.restore_request(snap) == "r1"
  legacy = dict(snap)
  legacy["request"] = dict(snap["request"], checkpoint_version=None,
                           uid="r2")
  assert sched.restore_request(legacy) == "r2"
  wrong = dict(snap)
  wrong["request"] = dict(snap["request"], checkpoint_version=2,
                          uid="r3")
  with pytest.raises(ValueError, match="cross-version restore refused"):
    sched.restore_request(wrong)


def test_process_transport_refuses_cross_version_restore_parent_side():
  """The parent-side fence fires BEFORE journaling or wire traffic: a
  cross-version snapshot never reaches the child and never poisons the
  crash journal."""
  from easyparallellibrary_tpu.serving.transport import ProcessTransport
  rep = ProcessTransport(
      0, FACTORY, config=epl.Config(),
      engine_kwargs={"checkpoint_version": 3}, start=False)
  assert rep.checkpoint_version == 3     # engine-kwargs fallback
  req = Request(uid="x", prompt=np.asarray([1, 2], np.int32),
                max_new_tokens=2, checkpoint_version=2)
  snap = {"request": req.snapshot(), "generated": [],
          "requeues": 0, "first_token_emitted": False,
          "submitted_at": 0.0}
  with pytest.raises(ValueError, match="cross-version restore refused"):
    rep.restore_request(snap)
  assert not rep._journal, "refused restore must not be journaled"


class _VersionedFake:
  """Duck-typed replica with a pinned checkpoint version for pure
  dispatch/placement policy tests."""

  def __init__(self, index, version=0):
    self.index = index
    self.checkpoint_version = version
    self.finished = {}
    self.has_work = False
    self.num_slots = 4
    self.stats = None
    self.watchdog_timeouts = 0
    self.bad_steps = 0
    self.itl_ewma_s = 0.0
    self.restored = []

  load = property(lambda self: len(self.restored))
  queue_depth = property(lambda self: 0)
  num_active = property(lambda self: 0)

  def submit(self, req):
    return True

  def cancel(self, uid):
    return False

  def step(self):
    return []

  def evacuate(self):
    return []

  def restore_request(self, snap, front=False):
    self.restored.append(snap["request"]["uid"])
    return snap["request"]["uid"]

  def close(self):
    pass


def _pinned_snap(uid, version):
  req = Request(uid=uid, prompt=np.asarray([1, 2, 3], np.int32),
                max_new_tokens=2, checkpoint_version=version)
  return {"request": req.snapshot(), "generated": [], "requeues": 0,
          "first_token_emitted": False, "submitted_at": 0.0}


def test_version_weight_split_is_deterministic_and_exact():
  """The deficit split admits EXACTLY weight-share of requests per
  version, deterministically (no RNG): 10% green over 20 admissions is
  2 green, and a replay of the same sequence splits identically."""
  replicas = [_VersionedFake(0, 0), _VersionedFake(1, 0),
              _VersionedFake(2, 1)]
  router = Router(replicas=replicas, clock=FakeClock())
  prompts = _prompts(20, seed=5)

  def drive():
    router.set_version_weights({0: 0.9, 1: 0.1})
    picks = []
    for i, p in enumerate(prompts):
      idx, _reason = router._choose(p)
      picks.append(router._replica_version(idx))
    return picks

  picks = drive()
  assert picks.count(1) == 2 and picks.count(0) == 18
  assert picks == drive(), "the split must replay identically"
  # Weights cleared -> version-blind dispatch, counters reset.
  router.set_version_weights(None)
  assert router._version_weights is None
  assert router._version_dispatched == {}
  # A weighted version with NO live replica degrades to the rest of
  # the fleet instead of shedding.
  router.set_version_weights({7: 1.0})
  idx, _ = router._choose(prompts[0])
  assert idx is not None
  router.close()


def test_placement_respects_version_pins_and_parks_orphans():
  """Failover placement: a version-pinned snapshot lands only on a
  SAME-version target; with no same-version target it parks (delayed,
  never replayed cross-version) and flushes the moment its version has
  a live replica again."""
  replicas = [_VersionedFake(0, 1), _VersionedFake(1, 1),
              _VersionedFake(2, 2)]
  router = Router(replicas=replicas, clock=FakeClock())
  placed = router._place_snapshots(
      [_pinned_snap("a", 1), _pinned_snap("b", 2),
       _pinned_snap("c", None), _pinned_snap("d", 3)],
      targets=[0, 1, 2])
  assert placed == 3
  blue_restored = replicas[0].restored + replicas[1].restored
  assert "a" in blue_restored and "a" not in replicas[2].restored
  assert replicas[2].restored == ["b"]
  assert "c" in blue_restored + replicas[2].restored
  # The v3 orphan parked; it does NOT churn while no v3 replica exists.
  assert [s["request"]["uid"] for s in router._parked] == ["d"]
  router._flush_parked()
  assert [s["request"]["uid"] for s in router._parked] == ["d"]
  # A v3 replica appears: the orphan flushes onto it.
  replicas.append(_VersionedFake(3, 3))
  router.replicas.append(replicas[3])
  router.health.append(router._make_health(3))
  router._flush_parked()
  assert router._parked == []
  assert replicas[3].restored == ["d"]
  router.close()


def test_rollout_begin_refuses_bad_checkpoint(tmp_path):
  """Validation runs BEFORE any green replica exists: a geometry
  mismatch or a corrupt shard fails begin() in milliseconds and the
  fleet is untouched."""
  import jax
  epl.init()
  config = epl.Config({"serving": {"rollout": {"enabled": True}}})
  model, params = tiny_gpt()
  router = Router(model, params, num_replicas=1, config=config,
                  num_slots=2, prefill_chunk=4)
  assert router.rollout is not None and router.rollout.state == "idle"
  # Wrong geometry: truncate one leaf before saving.
  broken = jax.tree_util.tree_map(lambda x: x, params)
  flat, treedef = jax.tree_util.tree_flatten(broken)
  flat[0] = np.asarray(flat[0])[..., :1]
  broken = jax.tree_util.tree_unflatten(treedef, flat)
  bad_dir = str(tmp_path / "bad")
  save_checkpoint(bad_dir, broken, step=1)
  with pytest.raises(ValueError, match="rollout validation failed"):
    router.rollout.begin(bad_dir)
  # Corrupt shard: the checksum chain rejects it.
  good_dir = str(tmp_path / "good")
  path = save_checkpoint(good_dir, params, step=1)
  shard = next(f for f in os.listdir(path) if f.endswith(".npz"))
  with open(os.path.join(path, shard), "r+b") as f:
    f.seek(0)
    f.write(b"\x00" * 8)
  with pytest.raises((FileNotFoundError, ValueError)):
    router.rollout.begin(good_dir)
  assert router.rollout.state == "idle"
  assert len(router.replicas) == 1
  assert router.rollout.counters()["rollout_started"] == 0.0
  router.close()


def test_saver_records_and_verifies_params_fingerprint(tmp_path):
  """index.json carries a params fingerprint (tree structure + shapes +
  per-shard sha256 rollup) recorded at save time; verify_checkpoint —
  and therefore every restore_params walk — recomputes it, so an
  edited index (leaves remapped over intact shards) is rejected."""
  from easyparallellibrary_tpu.runtime.saver import (
      params_fingerprint, verify_checkpoint)
  epl.init()
  _, params = tiny_gpt()
  path = save_checkpoint(str(tmp_path / "ck"), params, step=3)
  with open(os.path.join(path, "index.json")) as f:
    index = json.load(f)
  assert index["params_fingerprint"] == params_fingerprint(index)
  fingerprint, step = checkpoint_fingerprint(str(tmp_path / "ck"))
  assert fingerprint == index["params_fingerprint"] and step == 3
  ok, reason = verify_checkpoint(path)
  assert ok, reason
  # Tamper with the index only (shards intact): the leaf->shape map no
  # longer matches the recorded fingerprint.
  leaves = index["leaves"]
  key = sorted(leaves)[0]
  leaves[key] = dict(leaves[key], shape=[9999])
  with open(os.path.join(path, "index.json"), "w") as f:
    json.dump(index, f)
  ok, reason = verify_checkpoint(path)
  assert not ok and "fingerprint" in reason


# ----------------------------------------- quick: the rollout contract


def _rollout_config(**rollout):
  rollout.setdefault("enabled", True)
  rollout.setdefault("canary_frac", 0.5)
  rollout.setdefault("canary_hold_s", 1.0)
  rollout.setdefault("min_replicas", 2)
  rollout.setdefault("drain_timeout_s", 60.0)
  return epl.Config({"serving": {"rollout": rollout}})


def _pump(router, clock, until, deadline_s=90.0, dt=0.05,
          submit=None):
  """Step the fleet (advancing the fake clock) until ``until()`` or a
  wall-clock deadline — real threads (the green spawner) need real
  time to post outcomes."""
  deadline = time.monotonic() + deadline_s
  while not until():
    assert time.monotonic() < deadline, (
        f"rollout stuck in state {router.rollout.state!r}")
    if submit is not None:
      submit()
    router.step()
    clock.advance(dt)
    time.sleep(0.002)


@pytest.mark.quick
def test_full_rollout_zero_loss_single_version_attribution(tmp_path):
  """The rollout contract: under live traffic a full blue->green
  rollout loses ZERO requests, every response is attributable to
  exactly one checkpoint version, compile counts stay <= 1 per
  replica, and the fleet lands on green (recipe included)."""
  epl.init()
  config = _rollout_config()
  model, params = tiny_gpt()
  ckpt_dir = str(tmp_path / "green")
  save_checkpoint(ckpt_dir, params, step=7)
  clock = FakeClock()
  router = Router(model, params, num_replicas=2, config=config,
                  clock=clock, num_slots=2, prefill_chunk=4)
  prompts = _prompts(24, seed=9)
  max_new = 5
  admitted_version = {}
  uid_ctr = [0]

  def submit_one():
    uid = uid_ctr[0]
    if uid >= len(prompts):
      return
    uid_ctr[0] += 1
    assert router.submit(Request(uid=uid, prompt=prompts[uid],
                                 max_new_tokens=max_new))
    # Attribution at admission: complete-in-place + version-pinned
    # failover guarantee the request retires on this version.
    admitted_version[uid] = router._replica_version(
        router.placement[uid])

  def feed(upto):
    """Live traffic for one phase, leaving ``prompts[upto:]`` to the
    phases after it — however many sweeps the green spawner (a real
    thread, real time) lets this one last."""
    return lambda: submit_one() if uid_ctr[0] < upto else None

  for _ in range(4):
    submit_one()
  router.step()
  green_version = router.rollout.begin(ckpt_dir)
  assert green_version == 1 and router.rollout.state == "spawning"
  _pump(router, clock,
        until=lambda: router.rollout.state == "canary",
        submit=feed(12))
  assert len(router.replicas) == 4          # 2 blue + 2 green
  assert router._version_weights == {0: 0.5, 1: 0.5}
  # Canary traffic flows to BOTH versions while the hold elapses.
  _pump(router, clock,
        until=lambda: router.rollout.state != "canary",
        submit=feed(18))
  assert router.rollout.state in ("draining_blue", "idle")
  _pump(router, clock,
        until=lambda: router.rollout.state == "idle",
        submit=feed(20))
  while uid_ctr[0] < len(prompts):          # post-cutover traffic
    submit_one()
  router.run()
  # Zero lost: every admitted request retired with its full stream.
  assert sorted(router.finished) == sorted(range(len(prompts)))
  for uid in range(len(prompts)):
    fin = router.finished[uid]
    assert fin.finish_reason == "length", (uid, fin.finish_reason)
    np.testing.assert_array_equal(
        fin.tokens, _oracle(model, params, prompts[uid], max_new),
        err_msg=f"req {uid}")
  # Exactly-one-version attribution, and both versions actually served.
  versions = set(admitted_version.values())
  assert versions == {0, 1}
  post_cutover = [u for u in admitted_version
                  if admitted_version[u] == 1]
  assert len(post_cutover) >= 2
  # Compile-once fleet-wide (greens included).
  for rep in router.replicas:
    assert rep.engine._step_fn._cache_size() <= 1
    assert rep.engine._compile_sentinel.recompiles == 0
  # The fleet LANDED on green: version advanced, weights cleared, blue
  # drained, and the recipe now builds green replicas.
  assert router._fleet_version == 1
  assert router._version_weights is None
  assert [h.state for h in router.health] == [
      "draining", "draining", "healthy", "healthy"]
  assert router._replica_spec["engine_kwargs"][
      "checkpoint_version"] == 1
  assert router.rollout.counters()["rollout_completed"] == 1.0
  assert router.rollout.counters()["rollout_active"] == 0.0
  router.close()


@pytest.mark.quick
def test_canary_breach_rolls_back_blue_bit_exact(tmp_path):
  """A canary-scoped SLO breach (green's per-version stream) triggers
  automatic rollback: green drains with its in-flight canary requests
  completing in place, blue admission restores, and every
  blue-attributed stream is bit-exact vs a never-rolled fleet — even
  though the green checkpoint holds DIFFERENT weights."""
  import jax
  epl.init()
  model, params = tiny_gpt()
  # Green is a genuinely different model (perturbed weights) with the
  # same geometry — the canary must not corrupt any blue stream.
  perturbed = jax.tree_util.tree_map(
      lambda x: np.asarray(x) * 1.5, params)
  ckpt_dir = str(tmp_path / "green")
  save_checkpoint(ckpt_dir, perturbed, step=2)
  prompts = _prompts(16, seed=13)
  max_new = 4

  def drive(router, clock, roll):
    admitted_version = {}
    uid_ctr = [0]

    def submit_one():
      uid = uid_ctr[0]
      if uid >= len(prompts):
        return
      uid_ctr[0] += 1
      assert router.submit(Request(uid=uid, prompt=prompts[uid],
                                   max_new_tokens=max_new))
      admitted_version[uid] = router._replica_version(
          router.placement[uid])

    def keep_blue_busy():
      # While green spawns (a real thread, real time) — but the last
      # four prompts are the canary's, however long the spawn takes.
      if uid_ctr[0] < len(prompts) - 4:
        submit_one()

    for _ in range(4):
      submit_one()
    router.step()
    if roll:
      router.rollout.begin(ckpt_dir)
      _pump(router, clock,
            until=lambda: router.rollout.state == "canary",
            submit=keep_blue_busy)
      for _ in range(4):
        submit_one()              # canary traffic on both versions
      router.step()
      # The green-scoped breach stream fires: the monitor's bare-name
      # rule suffix-matches the per-version key the router publishes.
      slo_lib.get_monitor().observe(
          router.steps, {"serving/fleet/v1/ttft_p99_s": 99.0})
      _pump(router, clock,
            until=lambda: router.rollout.state != "canary")
      assert router.rollout.state == "rolling_back"
      _pump(router, clock,
            until=lambda: router.rollout.state == "idle")
    while uid_ctr[0] < len(prompts):
      submit_one()
    router.run()
    return admitted_version

  def make_router(clock):
    config = epl.Config({
        "serving": {"rollout": {
            "enabled": True, "canary_frac": 0.5,
            "canary_hold_s": 1000.0,   # only the breach ends the canary
            "min_replicas": 2, "drain_timeout_s": 60.0}},
        "observability": {"slo": {"enabled": True,
                                  "ttft_p99_s": 0.5}}})
    epl.init(config)
    return Router(model, params, num_replicas=2, config=config,
                  clock=clock, num_slots=2, prefill_chunk=4), config

  base_router, _ = make_router(FakeClock())
  base_attr = drive(base_router, FakeClock(), roll=False)
  base = {u: f.tokens for u, f in base_router.finished.items()}
  base_router.close()
  slo_lib.reset()

  clock = FakeClock()
  router, _ = make_router(clock)
  attr = drive(router, clock, roll=True)
  rolled = {u: f.tokens for u, f in router.finished.items()}
  # Rollback landed: blue is the fleet again, green drained, version 0.
  assert router.rollout.counters()["rollout_rollbacks"] == 1.0
  assert router.rollout.counters()["rollout_completed"] == 0.0
  assert router._fleet_version == 0
  assert router._version_weights is None
  assert all(router.health[i].state == "draining"
             for i in router.rollout._green)
  # Zero lost through the rollback — canary requests completed on
  # green IN PLACE (their streams differ from base; that is the
  # point of complete-in-place, not a defect).
  assert sorted(rolled) == sorted(range(len(prompts)))
  green_uids = {u for u, v in attr.items() if v == 1}
  assert green_uids, "the canary never carried traffic"
  for uid, toks in rolled.items():
    fin = router.finished[uid]
    assert fin.finish_reason == "length"
    if uid not in green_uids:
      np.testing.assert_array_equal(
          toks, base[uid],
          err_msg=f"blue req {uid} diverged from never-rolled fleet")
  # Both fleets admitted the identical request population.
  assert base_attr.keys() == attr.keys()
  router.close()


@pytest.mark.quick
def test_rollout_enabled_but_idle_is_bit_identical_zero_actuations():
  """The fault-free guard: rollout enabled but never invoked is
  bit-identical to the baseline fleet — zero actuations, zero version
  weights, no extra compiles, identical streams."""
  epl.init()
  prompts = _prompts(4)
  max_new = (6, 7, 4, 5)

  def drive(router):
    out = {}
    for i in range(2):
      assert router.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=max_new[i]))
    for _ in range(2):
      for fin in router.step():
        out[fin.uid] = fin.tokens
    for i in range(2, 4):
      assert router.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=max_new[i]))
    out.update(router.run())
    return out

  model, params = tiny_gpt()
  base_router = Router(model, params, num_replicas=2, num_slots=2,
                       prefill_chunk=4, registry=MetricRegistry())
  base = drive(base_router)
  base_router.close()
  slo_lib.reset()

  config = epl.Config({
      "serving": {"rollout": {"enabled": True}},
      "observability": {"slo": {"enabled": True, "ttft_p99_s": 100.0,
                                "itl_p99_s": 100.0}}})
  epl.init(config)
  router = Router(model, params, num_replicas=2, config=config,
                  num_slots=2, prefill_chunk=4,
                  registry=MetricRegistry())
  rolled = drive(router)
  monitor = slo_lib.get_monitor()
  assert monitor is not None and monitor.actuations == 0
  assert router.rollout is not None
  assert router.rollout.state == "idle"
  assert router.rollout.counters() == {
      "rollout_started": 0.0, "rollout_completed": 0.0,
      "rollout_rollbacks": 0.0, "rollout_spawn_failures": 0.0,
      "rollout_active": 0.0}
  assert router._version_weights is None and router._fleet_version == 0
  assert len(router.replicas) == 2
  for rep in router.replicas:
    assert rep.engine._step_fn._cache_size() == 1
    assert rep.engine._compile_sentinel.recompiles == 0
  assert sorted(base) == sorted(rolled)
  for uid in base:
    np.testing.assert_array_equal(rolled[uid], base[uid],
                                  err_msg=f"req {uid}")
  router.close()


# --------------------------------- slow: the chaos-rollout acceptance


@pytest.mark.slow
def test_midrollout_sigkill_of_blue_loses_nothing(tmp_path):
  """`make chaos-rollout` acceptance: SIGKILL one blue replica child
  mid-canary on a PROCESS-transport fleet — its requests fail over to
  the SURVIVING BLUE (never green: cross-version replay is fenced),
  zero requests are lost, every response is attributable to exactly
  one checkpoint version, the survivor's compile count stays 1, and
  the rollout still completes."""
  import signal

  events_path = str(tmp_path / "slo_events.jsonl")
  config = epl.Config({
      "serving": {
          "router": {"transport": "process", "heartbeat_s": 0.02,
                     "rpc_timeout_s": 60.0, "suspect_after": 0.5,
                     "down_after": 1.0},
          "rollout": {"enabled": True, "canary_frac": 0.5,
                      "canary_hold_s": 2.0, "min_replicas": 1,
                      "spawn_timeout_s": 300.0,
                      "drain_timeout_s": 120.0},
      },
      "observability": {"slo": {"enabled": True,
                                "events_path": events_path}},
  })
  epl.init(config)
  model, params = tiny_gpt()        # parent-side twin of the factory
  ckpt_dir = str(tmp_path / "green")
  save_checkpoint(ckpt_dir, params, step=11)
  router = Router(num_replicas=2, config=config, factory=FACTORY,
                  num_slots=4, prefill_chunk=4)
  prompts = _prompts(18, seed=21)
  max_new = 6
  admitted_version = {}
  uid_ctr = [0]

  def submit_one():
    uid = uid_ctr[0]
    if uid >= len(prompts):
      return
    if router.submit(Request(uid=uid, prompt=prompts[uid],
                             max_new_tokens=max_new)):
      admitted_version[uid] = router._replica_version(
          router.placement[uid])
    uid_ctr[0] += 1

  def pump(until, deadline_s=180.0):
    deadline = time.monotonic() + deadline_s
    while not until():
      assert time.monotonic() < deadline, (
          f"stuck in rollout state {router.rollout.state!r}, "
          f"states {router.states()}")
      submit_one()
      router.step()
      time.sleep(0.01)

  for _ in range(4):
    submit_one()
  router.step()
  assert router.rollout.begin(ckpt_dir) == 1
  pump(lambda: router.rollout.state == "canary")
  blue = list(router.rollout._blue)
  green = list(router.rollout._green)
  assert len(green) == 2
  # Load both blues, then SIGKILL one mid-flight.
  for _ in range(6):
    submit_one()
  router.step()
  victim = next(i for i in blue
                if router.replicas[i].has_work) if any(
      router.replicas[i].has_work for i in blue) else blue[0]
  pid = router.replicas[victim].child_pid
  os.kill(pid, signal.SIGKILL)
  survivor_blue = [i for i in blue if i != victim]
  pump(lambda: router.health[victim].state == "down" or
       not router.replicas[victim].has_work)
  # Drive to completion (breach-free canary -> cutover -> drain).
  pump(lambda: router.rollout.state == "idle")
  while uid_ctr[0] < len(prompts):
    submit_one()
    router.step()
  deadline = time.monotonic() + 120.0
  while router.has_work and time.monotonic() < deadline:
    router.step()
    time.sleep(0.01)
  # Zero lost: every ADMITTED request resolved exactly once; none
  # parked, none vanished.
  assert not router._parked
  for uid in admitted_version:
    fin = router.finished.get(uid)
    assert fin is not None, f"req {uid} lost"
    if fin.finish_reason == "shed":
      continue
    assert fin.finish_reason == "length"
    np.testing.assert_array_equal(
        fin.tokens, _oracle(model, params, prompts[uid], max_new),
        err_msg=f"req {uid}")
  for uid, ver in admitted_version.items():
    assert ver in (0, 1)
  # The surviving blue never recompiled while absorbing the failover.
  assert router.replicas[survivor_blue[0]].compile_count == 1
  assert router.rollout.counters()["rollout_completed"] == 1.0
  assert router._fleet_version == 1
  router.close()
  # Every transition landed in slo_events.jsonl as a rollout actuation.
  events = [json.loads(line) for line in open(events_path)]
  rollout_events = [e for e in events
                    if e.get("actuator") == "rollout"]
  assert all(e["event"] == "actuation" and e["rule"] == "rollout"
             for e in rollout_events)
  seen = {e["transition"] for e in rollout_events}
  assert {"begin", "green_up", "canary_start", "cutover",
          "completed"} <= seen
