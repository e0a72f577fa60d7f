"""Device-truth observability (ISSUE 14): compiled-twin cost cards,
per-site measured collective bytes feeding the overlap planner, HBM
watermark gauges, and the perf regression gate.

Acceptance contract:

* QUICK — device observability fully enabled (introspector + HBM
  gauges + cost-card collection) on a fault-free speculative serving
  episode is BIT-IDENTICAL to the baseline stream, with the fused-step
  compile count still 1 (the AOT capture must not touch the jit call
  cache) and zero added host syncs (the whole episode runs under
  ``jax.transfer_guard_device_to_host("disallow")``).
* ``plan_collective_matmul`` (through ``resolve_num_chunks(site=...)``)
  flips its chunking decision when fed an introspector-measured
  per-site byte count that disagrees with the analytic model, and falls
  back BIT-IDENTICALLY when no measurement exists.
* ``make perf-gate`` passes on the shipped tree (checked-in
  ``perf_budget.json`` vs freshly collected cards) and demonstrably
  fails on a seeded regression (halved flops budget).
"""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.communicators.overlap import (
    resolve_num_chunks)
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.observability import device as device_lib
from easyparallellibrary_tpu.observability import perfgate
from easyparallellibrary_tpu.observability import slo as slo_lib
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.observability.device import (
    DeviceIntrospector, specs_of)
from easyparallellibrary_tpu.observability.registry import (
    DEVICE_NAMESPACE, MetricRegistry)
from easyparallellibrary_tpu.parallel.planner import (
    SITE_GATHER_MATMUL, SITE_ROW_DENSE, plan_collective_matmul)
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, DraftModelDrafter, Request)


TINY = GPTConfig(vocab_size=64, num_layers=1, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _drop_ambient_observability():
  yield
  trace_lib.reset()
  slo_lib.reset()
  device_lib.reset()


def _tiny_model():
  model = GPT(TINY)
  params = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]
  return model, params


def _prompts(n=4, seed=1):
  r = np.random.RandomState(seed)
  return [r.randint(0, 64, (m,)).astype(np.int32)
          for m in (5, 3, 6, 2)[:n]]


def _drive(eng, prompts):
  """A staggered speculative episode: two joins mid-flight."""
  out = {}
  for i in (0, 1):
    eng.submit(Request(uid=f"r{i}", prompt=prompts[i],
                       max_new_tokens=5 + i))
  for _ in range(2):
    for fin in eng.step():
      out[fin.uid] = fin.tokens
  for i in (2, 3):
    eng.submit(Request(uid=f"r{i}", prompt=prompts[i],
                       max_new_tokens=5 + i))
  out.update(eng.run())
  return out


# ------------------------------------------------- quick: fault-free


@pytest.mark.quick
def test_device_observability_fault_free_bit_identical():
  """The quick-matrix guard: introspector + HBM gauges + cost-card
  collection fully enabled on a fault-free speculative serving episode
  changes NOTHING — bit-identical streams, fused-step cache size 1,
  sentinel silent, and the whole episode (captures included) legal
  under a device-to-host transfer guard."""
  prompts = _prompts()
  model, params = _tiny_model()

  epl.init()
  base_eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4,
      drafter=DraftModelDrafter(model, params, k=2))
  baseline = _drive(base_eng, prompts)
  base_eng.close()
  assert device_lib.get_introspector() is None

  config = epl.Config({"observability": {"device": {"enabled": True}}})
  epl.init(config)
  registry = MetricRegistry()
  eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4,
      drafter=DraftModelDrafter(model, params, k=2), registry=registry)
  with jax.transfer_guard_device_to_host("disallow"):
    observed = _drive(eng, prompts)

  # Bit-identical streams.
  assert sorted(observed) == sorted(baseline)
  for uid in baseline:
    np.testing.assert_array_equal(observed[uid], baseline[uid],
                                  err_msg=f"req {uid}")
  # Compile-once held THROUGH the AOT capture (the introspector lowers
  # and compiles the same twin, but never through the call cache).
  assert eng._step_fn._cache_size() == 1
  assert eng._compile_sentinel.recompiles == 0
  # The cards exist: fused step (speculative twin), sanitize-less
  # (resilience off), and the drafter's rollout.
  intro = device_lib.get_introspector()
  assert intro is not None
  card = intro.card("serving/fused_step")
  assert card is not None and card.flops > 0
  assert card.compile_count == 1
  assert card.donation_requested and card.donation_verified
  assert card.meta["tokens_per_step"] == 2 * 4
  drafter_card = intro.card("serving/drafter")
  assert drafter_card is not None and drafter_card.flops > 0
  # HBM gauges published under the device namespace (CPU: the static
  # cost-card bound, explicitly tagged as such).
  latest = registry.latest()
  key = f"{DEVICE_NAMESPACE}/hbm_peak_bytes"
  assert latest[key] > 0
  gauges = intro.hbm_gauges()
  assert gauges["hbm_source"] in ("memory_stats", "cost_card")
  # The gauges/cards ride diagnostic bundles via the engine's context.
  ctx = eng._capture_context()
  assert "serving/fused_step" in ctx["device"]["cost_cards"]
  eng.close()


# -------------------------------- site feed: the measured flip (pin)


def test_resolve_num_chunks_flips_on_measured_site_bytes():
  """THE acceptance pin: the crossover flips in BOTH directions when an
  introspector measurement disagrees with the analytic model, and is
  bit-identical to the analytic decision when no measurement exists."""
  config = epl.Config()
  kw = dict(config=config, dtype=jnp.bfloat16)

  # Analytic says FUSED for a compute-heavy site whose MODELED wire
  # traffic is negligible (a scatter of [m/n, n_out] blocks after a
  # deep-contraction matmul: nothing worth hiding, per the model)...
  deep = dict(m=8, k=2 ** 20, n_out=512)
  analytic = plan_collective_matmul("matmul_reduce_scatter",
                                    axis_size=8, dtype_bytes=2, **deep)
  assert not analytic.enabled
  assert resolve_num_chunks("matmul_reduce_scatter", 8,
                            site=SITE_GATHER_MATMUL, **deep, **kw) == 1
  # ...until a MEASURED wire-byte count (this site's real collective
  # traffic, comparable to its MXU time) says overlap pays after all.
  intro = device_lib.install(DeviceIntrospector())
  intro.record_site_bytes(SITE_GATHER_MATMUL, 4e6)
  flipped = resolve_num_chunks("matmul_reduce_scatter", 8,
                               site=SITE_GATHER_MATMUL, **deep, **kw)
  assert flipped >= 2, "measured bytes did not flip the crossover ON"

  # Analytic says OVERLAP for a big site...
  big = dict(m=8192, k=8192, n_out=8192)
  analytic = plan_collective_matmul("all_gather_matmul", axis_size=8,
                                    dtype_bytes=2, **big)
  assert analytic.enabled and analytic.num_chunks >= 2
  assert resolve_num_chunks("all_gather_matmul", 8,
                            site=SITE_ROW_DENSE, **big, **kw) >= 2
  # ...until a measurement reveals almost no wire traffic.
  intro.record_site_bytes(SITE_ROW_DENSE, 1.0)
  assert resolve_num_chunks("all_gather_matmul", 8,
                            site=SITE_ROW_DENSE, **big, **kw) == 1

  # Fallback bit-identity: an installed introspector with NO
  # measurement for a site decides exactly like no introspector at all.
  device_lib.install(DeviceIntrospector())
  for dims in (deep, big, dict(m=256, k=512, n_out=128)):
    with_feed = resolve_num_chunks("all_gather_matmul", 8,
                                   site="unmeasured/site", **dims, **kw)
    device_lib.reset()
    bare = resolve_num_chunks("all_gather_matmul", 8,
                              site="unmeasured/site", **dims, **kw)
    assert with_feed == bare
    device_lib.install(DeviceIntrospector())


def test_site_registration_and_attribution():
  """resolve_num_chunks REGISTERS the site's analytic signature; a
  captured program whose fused collective matches it feeds the
  measurement store (result bytes -> ring wire bytes); a non-matching
  program leaves the site unmeasured (analytic fallback, no guessing)."""
  intro = device_lib.install(DeviceIntrospector())
  config = epl.Config()
  resolve_num_chunks("matmul_reduce_scatter", 4, m=16, k=8, n_out=8,
                     dtype=jnp.float32, config=config,
                     site=SITE_ROW_DENSE)
  info = intro.sites()[SITE_ROW_DENSE]
  assert info.kind == "matmul_reduce_scatter" and info.axis_n == 4
  # Expected fused result: [m/n, n_out] f32 = 4*8*4 = 128 bytes.
  assert info.expected_result_bytes() == 128.0
  matched = intro._attribute_sites([("reduce_scatter", 128.0),
                                    ("all_gather", 4096.0)])
  assert matched == {SITE_ROW_DENSE: 128.0 * 3}      # (n-1) ring copies
  assert intro.measured_site_bytes(SITE_ROW_DENSE) == 384.0
  # Way-off sizes never match (factor bound): the store is untouched.
  intro2 = device_lib.install(DeviceIntrospector())
  resolve_num_chunks("matmul_reduce_scatter", 4, m=16, k=8, n_out=8,
                     dtype=jnp.float32, config=config,
                     site=SITE_ROW_DENSE)
  assert intro2._attribute_sites([("reduce_scatter", 5000.0)]) == {}
  assert intro2.measured_site_bytes(SITE_ROW_DENSE) is None


def test_capture_twin_attributes_real_lowered_collective():
  """End to end through a REAL lowered program: a jitted shard_map
  psum_scatter's StableHLO reduce_scatter op is attributed back to the
  registered site, and the wire-byte figure lands in the store the
  overlap policy reads."""
  from jax import shard_map
  from jax.sharding import Mesh, PartitionSpec as P
  intro = device_lib.install(DeviceIntrospector())
  # Site expecting a [4, 8] f32 fused reduce_scatter result (128 B).
  intro.register_site("test/rs_site", kind="reduce_scatter", axis_n=4,
                      m=16, k=8, n_out=0, dtype_bytes=4)
  mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
  fn = jax.jit(shard_map(
      lambda v: jax.lax.psum_scatter(v, "x", scatter_dimension=0,
                                     tiled=True),
      mesh=mesh, in_specs=P("x"), out_specs=P("x")))
  card = intro.capture_twin(
      "test/rs_twin", fn,
      (jax.ShapeDtypeStruct((4, 8), jnp.float32),))
  assert card is not None and card.collective_ops == 1
  assert card.site_bytes == {"test/rs_site": 128.0 * 3}
  assert intro.measured_site_bytes("test/rs_site") == 384.0


# ---------------------------------------------------- introspector units


def test_capture_is_idempotent_and_failure_degrades():
  intro = DeviceIntrospector()
  fn = jax.jit(lambda x: x * 2)
  spec = (jax.ShapeDtypeStruct((4,), jnp.float32),)
  card1 = intro.capture_twin("t", fn, spec)
  card2 = intro.capture_twin("t", fn, spec)
  assert card1 is card2 and intro.captures == 1
  # A twin without the AOT surface (a plain function, a chaos wrapper)
  # degrades to a logged skip — never an exception.
  assert intro.capture_twin("broken", lambda x: x, spec) is None
  assert intro.capture_failures == 1
  assert not intro.has_card("broken")


def test_donation_verification_flag():
  spec = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)
  intro = DeviceIntrospector()
  donated = intro.capture_twin(
      "donated", jax.jit(lambda x: x + 1, donate_argnums=0), spec)
  plain = intro.capture_twin("plain", jax.jit(lambda x: x + 1), spec)
  assert donated.donation_requested and donated.donation_verified
  assert donated.alias_bytes > 0 or donated.donation_verified
  assert not plain.donation_requested and not plain.donation_verified


def test_hbm_gauges_cost_card_fallback_and_publish():
  intro = DeviceIntrospector()
  # CPU: memory_stats() is None, no cards yet -> no gauges at all.
  if jax.local_devices()[0].memory_stats() is None:
    assert intro.hbm_gauges() == {}
  intro.capture_twin("t", jax.jit(lambda x: x @ x),
                     (jax.ShapeDtypeStruct((16, 16), jnp.float32),))
  gauges = intro.hbm_gauges()
  assert gauges["hbm_peak_bytes"] > 0
  if gauges["hbm_source"] == "cost_card":
    assert "hbm_frac" not in gauges  # a bound over no limit is no frac
  registry = MetricRegistry()
  intro.publish_hbm(7, registry=registry)
  assert f"{DEVICE_NAMESPACE}/hbm_peak_bytes" in registry.latest()
  # Monitor path (registry-less engines): the record reaches observe.
  seen = []

  class _Mon:
    def observe(self, step, record):
      seen.append((step, dict(record)))

  intro.publish_hbm(8, monitor=_Mon())
  assert seen and f"{DEVICE_NAMESPACE}/hbm_peak_bytes" in seen[0][1]


def test_hbm_frac_rule_from_config():
  rules = slo_lib.rules_from_config(
      epl.Config({"observability": {"slo": {"hbm_frac": 0.9}}})
      .observability.slo)
  hbm = [r for r in rules if r.name == "hbm_high"]
  assert len(hbm) == 1 and hbm[0].metric == "hbm_frac"
  assert hbm[0].target == 0.9
  with pytest.raises(ValueError, match="hbm_frac"):
    epl.Config({"observability": {"slo": {"hbm_frac": 1.5}}})


def test_ensure_configured_contract():
  # Off by default: no ambient introspector.
  epl.init()
  assert device_lib.ensure_configured() is None
  # Enabled via the ambient config: auto-built, stable across calls.
  config = epl.Config({"observability": {"device": {"enabled": True}}})
  epl.init(config)
  intro = device_lib.ensure_configured()
  assert intro is not None
  assert device_lib.ensure_configured() is intro
  # Explicit install wins over config.
  mine = DeviceIntrospector()
  device_lib.install(mine)
  assert device_lib.ensure_configured() is mine
  device_lib.reset()
  # Ambient off-config tears the auto instance down.
  epl.init()
  assert device_lib.ensure_configured() is None


def test_specs_of_passthrough():
  tree = {"a": jnp.ones((2, 3)), "b": 7, "c": np.zeros((4,), np.int32)}
  spec = specs_of(tree)
  assert isinstance(spec["a"], jax.ShapeDtypeStruct)
  assert spec["a"].shape == (2, 3)
  assert spec["b"] == 7
  assert spec["c"].shape == (4,)


def test_fit_step_cost_card_captured(tmp_path):
  """fit() captures the train step's cost card at the first dispatch
  (train/fit_step) with device observability enabled, donation
  verified (parallelize donates the state), and the fit-step compile
  count stays 1 through the capture."""
  import optax
  from flax import linen as nn

  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state, make_train_step,
      parallelize)
  from easyparallellibrary_tpu.runtime.loop import fit

  epl.init(epl.Config({"observability": {"device": {"enabled": True}}}))

  class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
      return nn.Dense(1)(jnp.tanh(nn.Dense(8)(x)))

  mesh = epl.current_plan().build_mesh()
  model = Net()
  r = np.random.RandomState(0)
  batch = {"x": jnp.asarray(r.randn(16, 4), jnp.float32),
           "y": jnp.asarray(r.randn(16, 1), jnp.float32)}

  def init_fn(rng):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(rng, batch["x"])["params"],
                             tx=optax.adam(1e-2))

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0))

  def loss_fn(params, b, rng):
    pred = model.apply({"params": params}, b["x"])
    return jnp.mean((pred - b["y"]) ** 2), {}

  step = parallelize(make_train_step(loss_fn), mesh, shardings)
  fit(step, state, [batch], num_steps=3,
      checkpoint_dir=str(tmp_path / "ck"), log_every=2,
      shardings=shardings)
  assert step.jitted._cache_size() == 1
  intro = device_lib.get_introspector()
  card = intro.card("train/fit_step")
  assert card is not None and card.flops > 0
  assert card.donation_requested and card.donation_verified


# ----------------------------------------------------------- perf gate


@pytest.fixture(scope="module")
def collected_cards():
  """One card collection for every gate test (each engine build
  compiles, so the cost is paid once per module)."""
  epl.init()
  try:
    return perfgate.collect_cards()
  finally:
    trace_lib.reset()
    slo_lib.reset()
    device_lib.reset()


def test_perf_gate_passes_on_shipped_tree(collected_cards):
  """`make perf-gate` on the shipped tree: the checked-in budget holds
  against freshly collected cards."""
  budget = perfgate.load_budget()
  assert budget.get("cost_cards"), "shipped budget pins no twins"
  violations = perfgate.check_cost_cards(budget, collected_cards)
  assert violations == []


def test_perf_gate_fails_on_seeded_regression(collected_cards, tmp_path):
  """Seed a regression: halve the flops budget (equivalently, double
  the measured flops) — the gate must fail with an attributed
  violation; same for a compile-count bust and a lost donation."""
  budget = copy.deepcopy(perfgate.load_budget())
  pins = budget["cost_cards"]["serving/fused_step"]
  pins["flops"]["max"] /= 2.0
  violations = perfgate.check_cost_cards(budget, collected_cards)
  assert any("serving/fused_step].flops" in v and "exceeds" in v
             for v in violations)
  # End to end through run_gate with the tampered budget on disk.
  tampered = tmp_path / "perf_budget.json"
  tampered.write_text(json.dumps(budget))
  errs = perfgate.run_gate(str(tampered), cards=collected_cards)
  assert errs, "tampered budget passed the gate"
  # A recompile shows up as compile_count 2 and busts its exact pin.
  worse = {**collected_cards,
           "serving/fused_step": {**collected_cards["serving/fused_step"],
                                  "compile_count": 2.0,
                                  "donation_verified": 0.0}}
  violations = perfgate.check_cost_cards(perfgate.load_budget(), worse)
  assert any("compile_count" in v for v in violations)
  assert any("donation_verified" in v and "below" in v
             for v in violations)
  # A budgeted twin that was never captured is itself a violation.
  missing = {k: v for k, v in collected_cards.items()
             if k != "serving/fused_step"}
  violations = perfgate.check_cost_cards(perfgate.load_budget(), missing)
  assert any("not captured" in v for v in violations)
