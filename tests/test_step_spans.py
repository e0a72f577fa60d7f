"""What the program says about itself under names the benchmark reads
(PERF.md section 3): the serving step's ``serving/dispatch`` and
``serving/fetch`` spans tile ``serving/device_step`` on every one of the
four step paths, ``serving/enqueue`` wraps every submit, a disabled tracer
records nothing, and every flash ``pallas_call`` carries one of three
names.
"""

import importlib
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.models import GPT, GPTConfig
from easyparallellibrary_tpu.observability import trace as trace_lib
from easyparallellibrary_tpu.observability import validate_trace
from easyparallellibrary_tpu.serving import (
    ContinuousBatchingEngine, NgramDrafter, Request)

fa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.flash_attention")
pa = importlib.import_module(
    "easyparallellibrary_tpu.kernels.paged_attention")

TINY = GPTConfig(vocab_size=64, num_layers=1, num_heads=4, d_model=32,
                 d_ff=64, max_seq_len=32, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _no_ambient_tracer():
  yield
  trace_lib.reset()


@pytest.fixture(scope="module")
def model_and_params():
  gpt = GPT(TINY)
  return gpt, gpt.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32))["params"]


def _serve(model_and_params, tracer, *, paged, speculative, resilient=False,
           config=None):
  """A short staggered episode; returns the engine and the exported
  events of ``tracer`` (installed as the ambient one)."""
  epl.init(config)
  trace_lib.install(tracer)
  model, params = model_and_params
  eng = ContinuousBatchingEngine(
      model, params, num_slots=2, prefill_chunk=4, paged=paged,
      block_size=4 if paged else None, resilience=resilient or None,
      drafter=NgramDrafter(k=2) if speculative else None)
  rp = np.random.RandomState(3)
  for i, n in enumerate((5, 3, 6)):
    eng.submit(Request(uid=i, prompt=rp.randint(0, 64, (n,)).astype(np.int32),
                       max_new_tokens=4 + i))
  eng.run()
  return eng, tracer.events()


def _spans(events, name):
  """``[(t0, t1)]`` of the B/E pairs called ``name``, in time order."""
  open_at, out = [], []
  for ev in events:
    if ev.get("name") != name:
      continue
    if ev["ph"] == "B":
      open_at.append(ev["ts"])
    elif ev["ph"] == "E":
      out.append((open_at.pop(), ev["ts"]))
  assert not open_at
  return out


@pytest.mark.parametrize("resilient", [False, True],
                         ids=["plain-outputs", "resilient-outputs"])
@pytest.mark.parametrize("paged", [False, True],
                         ids=["contiguous", "paged"])
@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_dispatch_and_fetch_tile_the_device_step(model_and_params,
                                                 speculative, paged,
                                                 resilient):
  eng, events = _serve(model_and_params, trace_lib.Tracer(enabled=True),
                       paged=paged, speculative=speculative,
                       resilient=resilient)
  steps = _spans(events, "serving/device_step")
  dispatch = _spans(events, "serving/dispatch")
  fetch = _spans(events, "serving/fetch")
  overlapped = not (speculative or paged or resilient)
  assert eng.step_overlap.startswith("on" if overlapped else "off: ")
  # one dispatch and one fetch a step, whichever call each fell in
  assert len(dispatch) == len(fetch) == eng._steps > 3
  if not overlapped:
    assert len(steps) == eng._steps
  else:
    # The call that launches step k+1 fetches step k: a burst of n steps
    # takes n + 1 calls, the first all dispatch, the last all fetch.
    assert len(steps) > eng._steps
    assert steps[0] == dispatch[0] and steps[-1] == fetch[-1]
  for s0, s1 in steps:
    inside = lambda spans: [(a, b) for a, b in spans if s0 <= a and b <= s1]
    d, f = inside(dispatch), inside(fetch)
    assert len(d) <= 1 and len(f) <= 1 and d + f
    # exact, not approximate: the same stamps are recorded twice
    assert (d + f)[0][0] == s0 and (d + f)[-1][1] == s1
    if d and f:
      assert d[0][1] == f[0][0] and s0 < d[0][1] <= s1
  # all three on the engine's own track, category ``serving``
  by_name = {ev["name"]: ev for ev in events if ev["ph"] == "B"}
  tids = {by_name[n]["tid"] for n in (
      "serving/plan", "serving/device_step", "serving/dispatch",
      "serving/fetch", "serving/commit")}
  assert len(tids) == 1
  assert by_name["serving/dispatch"]["cat"] == "serving"
  assert by_name["serving/fetch"]["cat"] == "serving"
  # strict nesting survives the export's sort by timestamp
  validate_trace(events)
  assert eng._step_fn._cache_size() == 1


def test_one_enqueue_span_per_accepted_and_per_shed_submit(model_and_params):
  tracer = trace_lib.Tracer(enabled=True)
  epl.init(epl.Config({"serving": {"resilience": {
      "enabled": True, "queue_limit": 2}}}))
  trace_lib.install(tracer)
  model, params = model_and_params
  eng = ContinuousBatchingEngine(model, params, num_slots=1,
                                 prefill_chunk=4)
  prompt = np.arange(4, dtype=np.int32)
  took = [eng.submit(Request(uid=i, prompt=prompt, max_new_tokens=2))
          for i in range(4)]
  assert took.count(False) >= 1 and took.count(True) >= 2
  with pytest.raises(ValueError):       # malformed: the span still closes
    eng.submit(Request(uid="bad", prompt=np.zeros((0,), np.int32),
                       max_new_tokens=2))
  eng.run()
  events = tracer.events()
  assert len(_spans(events, "serving/enqueue")) == 5
  instants = [ev for ev in events if ev["ph"] == "i"]
  assert sum(ev["name"] == "serving/submit" for ev in instants) \
      == took.count(True)
  assert sum(ev["name"] == "serving/shed" for ev in instants) \
      == took.count(False)
  enq = next(ev for ev in events if ev["name"] == "serving/enqueue")
  plan = next(ev for ev in events if ev["name"] == "serving/plan")
  assert enq["cat"] == "serving" and enq["tid"] == plan["tid"]
  validate_trace(events)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_disabled_tracer_records_no_event_at_all(model_and_params, paged):
  tracer = trace_lib.Tracer(enabled=False)
  eng, events = _serve(model_and_params, tracer, paged=paged,
                       speculative=True)
  assert eng._steps > 3
  assert tracer.pending == 0 and tracer.dropped == 0
  assert [ev for ev in events if ev["ph"] != "M"] == []


def test_span_at_children_nest_where_bounds_are_shared():
  """Three separate ``span_at`` calls with shared bounds cannot be
  ordered into a strict nesting; ``children`` can."""
  tracer = trace_lib.Tracer(enabled=True)
  tracer.span_at("outer", 10.0, 30.0, track="t",
                 children=(("a", 10.0, 15.0), ("b", 15.0, 30.0)))
  tracer.span_at("outer", 30.0, 30.0, track="t",          # zero length
                 children=(("a", 30.0, 30.0), ("b", 30.0, 30.0)))
  events = validate_trace(tracer.events())
  order = [(ev["ph"], ev["name"]) for ev in events if ev["ph"] in "BE"]
  assert order[:6] == [("B", "outer"), ("B", "a"), ("E", "a"), ("B", "b"),
                       ("E", "b"), ("E", "outer")]
  assert tracer.pending == 12
  flat = trace_lib.Tracer(enabled=True)
  flat.span_at("outer", 10.0, 30.0, track="t")
  flat.span_at("a", 10.0, 15.0, track="t")
  flat.span_at("b", 15.0, 30.0, track="t")
  with pytest.raises(ValueError, match="innermost"):
    validate_trace(flat.events())


# ------------------------------------------------------------ kernel names


def test_kernel_names_are_distinct_and_stable():
  names = (fa.FLASH_FWD, fa.FLASH_DKV, fa.FLASH_DQ, pa.PAGED_ATTN)
  # the benchmark's readers and PERF.md section 3 hold these literally
  assert names == ("flash_fwd", "flash_dkv", "flash_dq", "paged_attn")
  assert len(set(names)) == 4


def _pallas_call_names(module):
  """``name=`` of every ``pl.pallas_call(`` in ``module``'s source, in
  source order, ``None`` where one passes no name."""
  src = inspect.getsource(module)
  out = []
  for m in re.finditer(r"pl\.pallas_call\(", src):
    depth, i = 1, m.end()
    while depth:
      depth += {"(": 1, ")": -1}.get(src[i], 0)
      i += 1
    name = re.search(r"\bname=(\w+)", src[m.end():i])
    out.append(name and name.group(1))
  return out


def test_every_pallas_call_passes_a_name_and_variants_agree():
  # source order: forward resident, forward streaming; then dK/dV and dQ
  # resident, dK/dV and dQ streaming: the two variants of a kernel do the
  # same required work and share a name
  assert _pallas_call_names(fa) == [
      "FLASH_FWD", "FLASH_FWD", "FLASH_DKV", "FLASH_DQ", "FLASH_DKV",
      "FLASH_DQ"]
  assert _pallas_call_names(pa) == ["PAGED_ATTN"]


@pytest.mark.parametrize("S,resident", [(64, True), (4096, False)])
def test_the_names_reach_the_jaxpr(monkeypatch, S, resident):
  """Interpret mode has no custom call; the name rides the ``pallas_call``
  equation's parameters and, as a scope, its name stack, which is where
  the TPU compiler takes the instruction's name from."""
  if not resident:
    monkeypatch.setattr(fa, "_RESIDENT_MAX_BYTES", 0)
  assert fa._resident_ok(S, S, 64, 2) is resident
  x = jax.ShapeDtypeStruct((1, S, 2, 64), jnp.bfloat16)

  def loss(q, k, v):
    return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                   .astype(jnp.float32) ** 2)

  found = []

  def walk(jaxpr):
    for eqn in jaxpr.eqns:
      if eqn.primitive.name == "pallas_call":
        found.append((eqn.params["name"], str(eqn.source_info.name_stack)))
      for sub in jax.core.jaxprs_in_params(eqn.params):
        walk(sub)

  walk(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x).jaxpr)
  assert sorted(n for n, _ in found) == ["flash_dkv", "flash_dq",
                                         "flash_fwd"]
  for name, stack in found:
    # innermost scope, possibly under a transform: ``jvp(flash_fwd)``
    assert name in stack.split("/")[-1], (name, stack)
