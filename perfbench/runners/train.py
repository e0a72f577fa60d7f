"""Runner ``train``: the library's trainer on a mesh of the cell's chips.

``epl.init`` -> ``epl.replicate(1)`` -> ``create_sharded_train_state`` ->
``parallelize(make_gpt_train_step(model))``, a fresh seeded batch every
step, made on the host and placed by the benchmark.

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through its first ``check.steps`` steps (through the
window's own call and feed; they are the warm-up too) and hands the same
object to the window.  After the window the program's state is freed and
the plain reference follows those first steps from the same seeded weights;
each step's loss, the first gradient's norm per leaf (read from the
optimizer's first moment after one step) and the norm of each leaf's change
after the steps are compared.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from perfbench.harness import compare, device as device_lib, flops
from perfbench.harness import hostwatch, stats, tracing
from perfbench.harness import traffic as traffic_lib
from perfbench.harness.result import say
from perfbench.reference import gpt2
from perfbench.runners import epl_gpt


def _reference_shardings(ref_cfg, devices):
  """Where the reference's arrays live: on one chip as they are; on
  several, every matrix split along its first axis (the stacked layers,
  the vocabulary) so that weights, gradients and both moments of the
  larger configuration fit.  Placement only: the mathematics is the same
  jitted plain-jnp program, which XLA partitions."""
  import jax
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
  n = len(devices)
  if n == 1:
    return None
  mesh = Mesh(np.array(devices), ("x",))
  shapes = jax.eval_shape(lambda k: gpt2.init_params(ref_cfg, k),
                          gpt2.seed_key(0))
  return jax.tree_util.tree_map(
      lambda x: NamedSharding(
          mesh, P("x") if x.ndim >= 2 and x.shape[0] % n == 0 else P()),
      shapes)


def _rows_over(devices):
  """Places a reference batch: on several chips its rows are split over
  them, so that the reference's row blocks are computed in parallel."""
  import jax
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
  if len(devices) == 1:
    return lambda ids: ids
  sharding = NamedSharding(Mesh(np.array(devices), ("x",)), P("x"))
  return lambda ids: jax.device_put(ids, sharding)


def run(*, cell, cell_file, config_file, traffic, devices, peaks, seed,
        seconds, trace, t_process_start, control=None):
  # ``control``: a lower precision ("int8", "bfloat16") in which the
  # reference is ALSO followed, in the program's place; perfbench/control.py
  # sets it, run.py never does.
  import jax
  import jax.numpy as jnp
  import optax
  import easyparallellibrary_tpu as epl
  from easyparallellibrary_tpu.models import GPT
  from easyparallellibrary_tpu.models.gpt import make_gpt_train_step
  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state, parallelize)
  from easyparallellibrary_tpu.parallel.api import batch_sharding

  compiles = device_lib.CompileCounter()
  since = lambda: time.perf_counter() - t_process_start
  say(f"set-up: imports done at {since():.1f} s")
  ref_cfg = gpt2.GPT2Config.from_file(config_file)
  opt = cell_file["optimizer"]
  check = cell_file["check"]
  n_chips = len(devices)
  if traffic["kind"] != "train_batches":
    raise ValueError(f"runner train cannot feed traffic kind "
                     f"{traffic['kind']!r}")
  seq = traffic["seq_len"]
  tokens_per_step = traffic["global_batch"] * seq
  batches = traffic_lib.TrainBatches(traffic, seed,
                                     config_file["vocab_size"])

  # ---------------------------------------------------------- set-up
  epl.init(epl.Config(dict(cell_file.get("epl_config", {}))),
           devices=list(devices))
  with epl.replicate(1):
    model = GPT(epl_gpt.gpt_config(ref_cfg, cell_file["model"]))
  mesh = epl.current_plan().build_mesh()
  say(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
  if opt["name"] != "adamw":
    raise ValueError(f"optimizer {opt['name']!r}")
  tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                   eps=opt["eps"], weight_decay=opt["weight_decay"])
  key = gpt2.seed_key(seed)
  ids0 = jnp.zeros((1, 8), jnp.int32)

  def program_params(k):
    # model.init only lends its tree (and its sharding boxes); every
    # value is the benchmark's own, and XLA drops the unused draws.
    shell = model.init(jax.random.PRNGKey(0), ids0)["params"]
    return epl_gpt.to_program_tree(gpt2.init_params(ref_cfg, k), shell)

  def init_fn(k):
    return TrainState.create(apply_fn=model.apply,
                             params=program_params(k), tx=tx)

  state, shardings = create_sharded_train_state(init_fn, mesh, key)
  step = parallelize(make_gpt_train_step(model), mesh, shardings)
  jax.block_until_ready(state)
  say(f"set-up: seeded state on the device at {since():.1f} s")
  place = lambda ids: jax.device_put(ids, batch_sharding(mesh))
  rng = jax.random.PRNGKey(0)
  spans = []                       # (name, t0_ns, t1_ns), host clock

  def one_step(state):
    t0 = time.perf_counter_ns()
    ids = batches()
    t1 = time.perf_counter_ns()
    batch = {"ids": place(ids)}
    t2 = time.perf_counter_ns()
    state, metrics = step(state, batch, rng)
    t3 = time.perf_counter_ns()
    spans.extend((("input/make_batch", t0, t1), ("input/place", t1, t2),
                  ("train/dispatch", t2, t3)))
    return state, metrics["loss"], ids

  sq_of = jax.jit(epl_gpt.named_sq_norms)
  change_of = jax.jit(lambda params, k: epl_gpt.named_sq_norms(
      jax.tree_util.tree_map(lambda a, b: a - b, params,
                             program_params(k))))
  norms = lambda sq: {n: float(np.sqrt(v))
                      for n, v in jax.device_get(sq).items()}

  first_ids, first_losses, grad_norms = [], [], None
  for i in range(check["steps"]):
    state, loss, ids = one_step(state)
    first_ids.append(ids)
    first_losses.append(float(loss))
    if i == 0:
      say(f"set-up: first step done at {since():.1f} s")
      # After one step from zero moments, mu = (1 - b1) * g: the gradient
      # as the optimizer got it.
      mu = state.opt_state[0].mu
      grad_norms = {n: v / (1.0 - opt["b1"])
                    for n, v in norms(sq_of(mu)).items()}
  change_norms = norms(change_of(state.params, key))
  say(f"set-up: check steps and norms done at {since():.1f} s")
  say("first losses " + " ".join(f"{l:.5f}" for l in first_losses))
  if step.jitted._cache_size() != 1:
    raise SystemExit(f"train step compiled {step.jitted._cache_size()} "
                     "times during set-up")

  # ---------------------------------------------------------- window
  dev_trace = tracing.DeviceTrace(cell["name"]) if trace else None
  # The device trace covers the window's last ``trace_seconds``; the
  # profiler is stopped after the window's last step is done.
  trace_for = min(cell_file.get("trace_seconds", 3.0), seconds / 3.0)
  trace_at = seconds - trace_for
  in_flight = cell_file.get("in_flight_steps", 2)
  pending = collections.deque()
  done_at, losses = [], []
  spans.clear()
  mark = compiles.count
  watch = hostwatch.HostWatch(
      heartbeat_s=0.005 if hostwatch.DIAGNOSE else None).start()
  tpu_mon = hostwatch.TpuMonitor().start() if hostwatch.DIAGNOSE else None
  t_start = time.perf_counter()
  setup_s = t_start - t_process_start
  n_steps = 0
  while True:
    now = time.perf_counter() - t_start
    if dev_trace is not None and dev_trace.t0_ns is None and now >= trace_at:
      dev_trace.start()
    if now >= seconds:
      break
    state, loss, _ = one_step(state)
    n_steps += 1
    pending.append(loss)
    if len(pending) > in_flight:
      t0 = time.perf_counter_ns()
      losses.append(jax.block_until_ready(pending.popleft()))
      t1 = time.perf_counter_ns()
      spans.append(("train/wait", t0, t1))
      done_at.append(t1)
  while pending:
    losses.append(jax.block_until_ready(pending.popleft()))
    done_at.append(time.perf_counter_ns())
  jax.block_until_ready(state)
  window_s = time.perf_counter() - t_start
  host_report = watch.stop()
  if dev_trace is not None:
    dev_trace.stop()
  compiles.require_none_since(mark, "the measured window")
  memory_peak = device_lib.live_peak_bytes(devices)
  losses = [float(l) for l in losses]
  tokens_per_s = n_steps * tokens_per_step / window_s
  say(f"window {window_s:.3f} s, {n_steps} steps, "
      f"{tokens_per_s:.1f} tokens/s, loss {losses[0]:.4f} -> "
      f"{losses[-1]:.4f}")
  step_gaps_ms = [g / 1e6 for g in stats.gaps(done_at)]
  say(hostwatch.gap_summary(step_gaps_ms))
  say(hostwatch.summary(host_report))
  if hostwatch.DIAGNOSE:
    hostwatch.dump(f"diag_{cell['name']}_{seed}", {
        "t_start_ns": int(t_start * 1e9), "window_s": window_s,
        "n_steps": n_steps, "in_flight": in_flight,
        "done_at_ns": done_at, "spans": spans, "host": host_report,
        "host_series": watch.series, "tpu": tpu_mon.stop()})

  # ------------------------------------------- free, then the reference
  host_spans = list(spans)
  del state, step, pending
  jax.clear_caches()
  verdict = compare.Verdict()
  t0 = time.perf_counter()
  make_ref = jax.jit(lambda k: gpt2.init_params(ref_cfg, k),
                     out_shardings=_reference_shardings(ref_cfg, devices))
  ref_losses, ref_grad, ref_change = gpt2.follow_steps(
      ref_cfg, make_ref(key), first_ids, opt, check["reference_row_block"],
      place=_rows_over(devices))
  say(f"reference followed {len(first_ids)} steps in "
      f"{time.perf_counter() - t0:.1f} s: losses "
      + " ".join(f"{l:.5f}" for l in ref_losses))
  limits = check["limits"]
  for i, (got, ref) in enumerate(zip(first_losses, ref_losses), start=1):
    verdict.at_most(f"loss_gap_step{i}", abs(got - ref),
                    limits["loss_gap"][i - 1])
  gap, at = compare.worst_leaf_gap(grad_norms, ref_grad)
  say(f"worst gradient leaf {at}")
  verdict.at_most("grad_norm_worst_leaf_gap", gap,
                  limits["grad_norm_worst_leaf_gap"])
  gap, at = compare.worst_leaf_gap(change_norms, ref_change)
  say(f"worst update leaf {at}")
  verdict.at_most("update_norm_worst_leaf_gap", gap,
                  limits["update_norm_worst_leaf_gap"])
  bad = [l for l in losses if not np.isfinite(l)]
  verdict.require("every window loss finite", not bad)
  fifth = max(1, len(losses) // 5)
  verdict.require(
      "window loss falls", np.mean(losses[-fifth:]) < np.mean(losses[:fifth]),
      f"{np.mean(losses[:fifth]):.4f} -> {np.mean(losses[-fifth:]):.4f}")

  control_numbers = None
  for precision in (control.split(",") if control else ()):
    c_losses, c_grad, c_change = gpt2.follow_steps(
        ref_cfg, make_ref(key), first_ids, opt,
        check["reference_row_block"], precision=precision,
        place=_rows_over(devices))
    nums = {f"loss_gap_step{i}": abs(c - r) for i, (c, r) in
            enumerate(zip(c_losses, ref_losses), start=1)}
    nums["grad_norm_worst_leaf_gap"] = compare.worst_leaf_gap(
        c_grad, ref_grad)[0]
    nums["update_norm_worst_leaf_gap"] = compare.worst_leaf_gap(
        c_change, ref_change)[0]
    say(f"control ({precision} in the program's place): {nums}")
    control_numbers = {**(control_numbers or {}),
                       **{f"{precision}:{k}": v for k, v in nums.items()}}

  out = {
      "control_numbers": control_numbers,
      "correct": verdict.correct, "attempted": n_steps, "failed": len(bad),
      "end_to_end": {"train_tokens_per_s": tokens_per_s,
                     "setup_s": setup_s},
      "device": device_lib.device_block(devices, memory_peak),
      "numbers": verdict.numbers,
  }
  if trace:
    block = dev_trace.reduce(host_spans, n_chips)
    for name, secs in block["device_ops"][:6]:
      say(f"device op {name} {secs:.4f} s, e.g. "
          f"{block['op_examples'].get(name, '')[:1200]}")
    out["device"].update(busy_s=block["busy_s"], window_s=block["window_s"])
    out["breakdown"] = {"device_ops": block["device_ops"],
                        "idle_gaps": block["idle_gaps"]}
    flops_per_token = flops.gpt2_train_flops_per_token(
        ref_cfg.n_layer, ref_cfg.n_embd, ref_cfg.n_inner,
        ref_cfg.vocab_size, seq)
    model_cfg = model.cfg
    out["layer_ctx"] = {
        "kind": "train", "trace": block, "peaks": peaks,
        "chips": n_chips,
        # steady-state rate: the profiler's start falls inside this run's
        # window, so its own tokens/s reads a little low
        "tokens_per_s": tokens_per_step / (stats.median(step_gaps_ms) / 1e3),
        "tokens_per_step": tokens_per_step,
        "flops_per_token": flops_per_token,
        "step_done_gaps_ms": step_gaps_ms,
        "host_spans": host_spans,
        "attention": {"batch_per_chip": traffic["global_batch"] // n_chips,
                      "heads": model_cfg.num_heads, "seq": seq,
                      "head_dim": model_cfg.d_model // model_cfg.num_heads,
                      "layers": model_cfg.num_layers},
    }
  return out
