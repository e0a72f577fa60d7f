"""Runner ``serve_family``: ``ContinuousBatchingEngine`` under generated
load, for the architecture family the cell's file names.

``runners/serve.py`` with the family taken from the cell's file instead of
imported: ``"family": "<family>"`` selects the plain reference
``perfbench/reference/<family>.py`` (``seed_key``, ``init_params(cfg,
key)``, ``logits(cfg, params, ids, precision)``) and the glue
``perfbench/runners/epl_<family>.py`` (``ref_config(config_file)``,
``build_model(ref_cfg, model_opts) -> (model, ids -> params shell)``,
``program_params(ref_cfg, key, shell)`` — which compiles what it needs
itself — and ``sum_of_squares(tree)``).  The
loop, the window, the stamps, the metric definitions, the ``layer_ctx``
keys and the ``correct`` check are ``serve.py``'s, copied and not
reinterpreted (ROADMAP R1: the two should become one file).  Two things
differ: the teacher-forced reference runs at the mix's ``max_total_len``
where the served context is longer than any request, and the run also
requires that program and reference started from the same weights (the
glue places them a layer at a time; their sums of squares must agree).

One process, one thread: the loop that steps the engine also submits each
request when it is due (the process that holds the chip is the only one),
and every committed token is stamped from the benchmark's side through the
scheduler's ``on_tokens`` hook.  A request is timed from the moment it was
DUE, not from when the loop got round to submitting it.

Mix kinds: ``open_loop`` (tails judged: ``ttft_p95_ms``, ``itl_p95_ms``)
and ``backlog`` (``serve_tokens_per_s``).  The ramp before the window is
set-up: it brings the slots to their steady mix of prefill and decode.

``correct``: once the window has closed and the engine's cache is freed, a
sample of the finished requests, drawn from the seed with the longest in
it, is teacher-forced through the plain float32 reference; the widest gap
by which a served token's reference logit lies below the reference's best
is held to a limit (greedy tokens only, which is all this traffic sends).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from perfbench.harness import compare, device as device_lib
from perfbench.harness import stats, tracing, traffic as traffic_lib
from perfbench.harness.result import say


def _span_pairs(events, t0_ns: float):
  """``[(name, start_ns, end_ns)]`` of the B/E pairs on the engine's own
  track, on the ``perf_counter_ns`` clock."""
  open_at, out = {}, []
  for ev in events:
    if ev.get("cat") != "serving" or not ev["name"].startswith("serving/"):
      continue
    key = (ev["name"], ev["tid"])
    if ev["ph"] == "B":
      open_at[key] = ev["ts"]
    elif ev["ph"] == "E" and key in open_at:
      out.append((ev["name"], t0_ns + open_at.pop(key) * 1e3,
                  t0_ns + ev["ts"] * 1e3))
  return out


def run(*, cell, cell_file, config_file, traffic, devices, peaks, seed,
        seconds, trace, t_process_start, control=None):
  import jax
  import jax.numpy as jnp
  import easyparallellibrary_tpu as epl
  from easyparallellibrary_tpu.observability import trace as trace_lib
  from easyparallellibrary_tpu.serving import (
      ContinuousBatchingEngine, Request)

  compiles = device_lib.CompileCounter()
  since = lambda: time.perf_counter() - t_process_start
  say(f"set-up: imports done at {since():.1f} s")
  family = cell_file["family"]
  ref = importlib.import_module(f"perfbench.reference.{family}")
  glue = importlib.import_module(f"perfbench.runners.epl_{family}")
  ref_cfg = glue.ref_config(config_file)
  sizes = cell_file["engine"]
  check = cell_file["check"]
  kind = traffic["kind"]
  if kind not in ("open_loop", "backlog"):
    raise ValueError(f"runner serve_family cannot feed traffic kind "
                     f"{kind!r}")
  vocab = config_file["vocab_size"]
  clock = time.perf_counter

  # ---------------------------------------------------------- set-up
  epl.init(epl.Config(dict(cell_file.get("epl_config", {}))),
           devices=list(devices))
  model, shell_of = glue.build_model(ref_cfg, cell_file["model"])
  key = ref.seed_key(seed)
  ids0 = jnp.zeros((1, 8), jnp.int32)

  params = jax.block_until_ready(
      glue.program_params(ref_cfg, key, shell_of(ids0)))
  weights_sq = jax.jit(glue.sum_of_squares)(params)
  say(f"set-up: seeded weights on the device at {since():.1f} s")
  tracer = None
  if trace:
    tracer = trace_lib.install(trace_lib.Tracer(
        enabled=True, ring_capacity=cell_file.get("span_ring", 2_000_000)))
  eng = ContinuousBatchingEngine(
      model, params, num_slots=sizes["num_slots"],
      prefill_chunk=sizes["prefill_chunk"])

  stamps, admit_at = {}, {}
  def on_tokens(uid, toks):
    t = clock()
    stamps.setdefault(uid, []).extend([t] * len(toks))
  eng.scheduler.on_tokens.append(on_tokens)
  eng.scheduler.on_admit.append(
      lambda uid: admit_at.setdefault(uid, clock()))

  def submit(r):
    ok = eng.submit(Request(uid=r.uid, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens))
    if not ok:
      raise SystemExit(f"request {r.uid} refused at admission")

  # Warm-up: the one fused-step program and the small per-request
  # programs, on requests that are no part of the mix.
  rng = np.random.default_rng([int(seed), 9])
  for j in range(2):
    submit(traffic_lib.Req(uid=f"warm{j}", due_s=0.0,
                           prompt=rng.integers(0, vocab, 40).astype(np.int32),
                           max_new_tokens=4))
  say(f"set-up: engine built at {since():.1f} s")
  eng.run()
  say(f"set-up: warm-up requests served at {since():.1f} s")
  stamps.clear()
  admit_at.clear()

  if kind == "open_loop":
    reqs = traffic_lib.open_loop(traffic, seconds, seed, vocab)
  else:
    reqs = traffic_lib.backlog(traffic, seed, vocab)
  by_uid = {r.uid: r for r in reqs}
  ramp = traffic["ramp_s"]
  drain_limit = traffic.get("drain_limit_s", 0.0)
  submit_at = {}
  nxt = 0

  def feed(now):
    """Submit what is due (open loop) or keep the queue full."""
    nonlocal nxt
    if kind == "open_loop":
      while nxt < len(reqs) and reqs[nxt].due_s <= now:
        submit(reqs[nxt])
        submit_at[reqs[nxt].uid] = clock()
        nxt += 1
    else:
      want = traffic["queue_target"]
      # During the ramp the backlog is let in evenly, ``ramp_fill``
      # requests over ``ramp_s``, so that the slots start out of phase
      # (all let in at once they would prefill, decode and retire in
      # waves, and the window would see whichever wave the seed drew).
      cap = len(reqs) if now >= ramp else 1 + int(
          now / ramp * traffic["ramp_fill"])
      while eng.scheduler.queue_depth < want and nxt < cap:
        if nxt >= len(reqs):
          raise SystemExit("the backlog's population ran out: raise "
                           "'population' in the mix")
        submit(reqs[nxt])
        submit_at[reqs[nxt].uid] = clock()
        nxt += 1

  dev_trace = tracing.DeviceTrace(cell["name"]) if trace else None
  # The device trace covers the window's last ``trace_seconds``: stopping
  # the profiler stalls the loop for seconds, which must not fall inside
  # the window.
  trace_for = min(cell_file.get("trace_seconds", 1.0), seconds / 3.0)
  trace_at = ramp + seconds - trace_for

  # ------------------------------------------------- ramp, then window
  t_load0 = clock()
  mark = None
  t_window0 = None
  finished = {}

  def step_or_wait():
    if eng.has_work:
      for fin in eng.step():
        finished[fin.uid] = (fin, clock())
    else:
      time.sleep(0.0005)

  while True:
    now = clock() - t_load0
    if mark is None and now >= ramp:
      mark = compiles.count
      t_window0 = clock()
      if tracer is not None:
        tracer.clear()
    if dev_trace is not None and dev_trace.t0_ns is None and now >= trace_at:
      dev_trace.start()
    if now >= ramp + seconds:
      break
    feed(now)
    step_or_wait()
  if kind == "open_loop":
    feed(ramp + seconds)     # what fell due during the window's last step
  t_window1 = clock()
  queue_at_close = eng.scheduler.queue_depth
  events = tracer.events() if tracer is not None else []
  if dev_trace is not None:
    dev_trace.stop()
  # Drain (open loop): no new arrivals; what the window admitted finishes.
  t_drain0 = clock()
  while (kind == "open_loop" and eng.has_work
         and clock() - t_drain0 < drain_limit):
    step_or_wait()
  compiles.require_none_since(mark, "the measured window")
  if eng._step_fn._cache_size() != 1:
    raise SystemExit(f"the fused step compiled "
                     f"{eng._step_fn._cache_size()} times")
  window_s = t_window1 - t_window0
  setup_s = t_window0 - t_process_start
  memory_peak = device_lib.live_peak_bytes(devices)
  tracer_t0_ns = -tracer.at_us(0) * 1e3 if tracer is not None else 0.0
  occupancy = [ev["args"]["value"] for ev in events
               if ev["ph"] == "C" and ev["name"] == "serving/active_slots"]

  # -------------------------------------------------------- metrics
  e2e = {"setup_s": setup_s}
  if kind == "open_loop":
    margin = traffic["drain_margin_s"]
    # By the schedule, not by the loop's clock: the window's own phase.
    idx = stats.measured_set([r.due_s - ramp for r in reqs], seconds, margin)
    measured = [reqs[i] for i in idx]
  else:
    measured = [by_uid[u] for u, (_, t) in finished.items()
                if t_window0 <= t <= t_window1]
  failed = 0
  ttft, itl = [], []
  for r in measured:
    fin = finished.get(r.uid)
    if fin is None or fin[0].finish_reason != "length":
      failed += 1
      say(f"failed: request {r.uid} due {r.due_s:.2f} s, prompt "
          f"{len(r.prompt)}, asked {r.max_new_tokens}: "
          + ("not finished" if fin is None else
             f"{fin[0].finish_reason} after {fin[0].new_tokens} tokens"))
      continue
    ts = stamps[r.uid]
    ttft.append(1e3 * (ts[0] - (t_load0 + r.due_s)))
    itl.extend(1e3 * g for g in stats.gaps(ts))
  in_window = sum(1 for ts in stamps.values() for t in ts
                  if t_window0 <= t <= t_window1)
  e2e["serve_tokens_per_s"] = in_window / window_s
  if kind == "open_loop" and ttft:
    e2e["ttft_p95_ms"] = stats.percentile(ttft, 95)
    e2e["itl_p95_ms"] = stats.percentile(itl, 95)
    say(f"ttft ms: median {stats.median(ttft):.1f} p95 "
        f"{e2e['ttft_p95_ms']:.1f} over {len(ttft)} requests; itl ms: "
        f"median {stats.median(itl):.2f} p95 {e2e['itl_p95_ms']:.2f} over "
        f"{len(itl)} gaps")
  elif ttft:
    say(f"(recorded, not judged) ttft ms p95 {stats.percentile(ttft, 95):.1f}"
        f", itl ms p95 {stats.percentile(itl, 95):.2f}")
  late = [1e3 * (submit_at[r.uid] - (t_load0 + r.due_s)) for r in measured
          if r.uid in submit_at]
  queue = [1e3 * (admit_at[r.uid] - (t_load0 + r.due_s)) for r in measured
           if r.uid in admit_at]
  say(f"window {window_s:.3f} s, {len(measured)} measured requests, "
      f"{failed} failed, {in_window} tokens committed in the window, "
      f"{e2e['serve_tokens_per_s']:.1f} tokens/s, steps {eng._steps}")

  # ------------------------------ free the engine, then the reference
  verdict = compare.Verdict()
  done = [r for r in measured if r.uid in finished
          and finished[r.uid][0].finish_reason == "length"]
  streams = {r.uid: np.asarray(finished[r.uid][0].tokens) for r in done}
  wrong = [u for u, s in streams.items()
           if len(s) != len(by_uid[u].prompt) + by_uid[u].max_new_tokens
           or not np.array_equal(s[:len(by_uid[u].prompt)], by_uid[u].prompt)]
  verdict.require("every finished request has its length and its prompt",
                  not wrong, f"{len(wrong)} of {len(streams)} wrong")
  pick = np.random.default_rng([int(seed), 4])
  n_sample = min(check["sample"], len(done))
  chosen = {done[i].uid for i in pick.choice(len(done), n_sample,
                                             replace=False)} if done else set()
  if done:
    chosen.add(max(done, key=lambda r: len(streams[r.uid])).uid)
  weights_sq = float(weights_sq)
  eng.close()
  del eng, params
  trace_lib.install(None)
  jax.clear_caches()

  T = min(ref_cfg.n_positions,
          traffic.get("max_total_len", ref_cfg.n_positions))

  def gaps_of(p, ids, precision):
    """Per position: how far the reference logit of (a) the next token
    of ``ids`` and (b) the token ``precision`` puts first lie below the
    reference's best."""
    lg = ref.logits(ref_cfg, p, ids)[0]
    best = jnp.max(lg, -1)
    nxt_tok = jnp.roll(ids[0], -1)
    served = best - jnp.take_along_axis(lg, nxt_tok[:, None], -1)[:, 0]
    ctrl = []
    for prec in precision:
      low = jnp.argmax(ref.logits(ref_cfg, p, ids, prec)[0], -1)
      ctrl.append(best - jnp.take_along_axis(lg, low[:, None], -1)[:, 0])
    return served, ctrl

  ref_params = jax.jit(lambda k: ref.init_params(ref_cfg, k))(key)
  ref_sq = float(jax.jit(glue.sum_of_squares)(ref_params))
  verdict.require(
      "program and reference start from the same weights",
      abs(weights_sq - ref_sq) <= 1e-3 * ref_sq,
      f"sums of squares {weights_sq:.6g} and {ref_sq:.6g}")
  gaps_fn = jax.jit(gaps_of, static_argnums=2)
  precisions = tuple(control.split(",")) if control else ()
  worst, worst_ctrl = 0.0, [0.0] * len(precisions)
  n_tokens = 0
  t0 = clock()
  for uid in sorted(chosen, key=str):
    s = streams[uid]
    ids = np.zeros((1, T), np.int32)
    ids[0, :len(s)] = s
    served, ctrl = jax.device_get(gaps_fn(ref_params, jnp.asarray(ids),
                                          precisions))
    rows = slice(len(by_uid[uid].prompt) - 1, len(s) - 1)
    worst = max(worst, float(served[rows].max()))
    worst_ctrl = [max(w, float(c[rows].max()))
                  for w, c in zip(worst_ctrl, ctrl)]
    n_tokens += rows.stop - rows.start
  say(f"reference scored {n_tokens} served tokens of {len(chosen)} requests "
      f"in {clock() - t0:.1f} s")
  verdict.require("some served tokens were compared", n_tokens > 0)
  verdict.at_most("served_logit_gap", worst,
                  check["limits"]["served_logit_gap"])
  control_numbers = None
  if control:
    control_numbers = {f"{p}:served_logit_gap": w
                       for p, w in zip(precisions, worst_ctrl)}
    say(f"control (in the program's place): {control_numbers}")

  out = {
      "correct": verdict.correct, "attempted": len(measured),
      "failed": failed, "end_to_end": e2e,
      "device": device_lib.device_block(devices, memory_peak),
      "numbers": verdict.numbers, "control_numbers": control_numbers,
      "observed": {
          "queue_depth_at_close": queue_at_close,
          "queue_p95_ms": stats.percentile(queue, 95) if queue else None,
          "queue_last_third_p50_ms": stats.median(
              queue[-max(1, len(queue) // 3):]) if queue else None,
          "late_p95_ms": stats.percentile(late, 95) if late else None,
          "ttft_p50_ms": stats.median(ttft) if ttft else None,
          "measured": len(measured),
      },
  }
  if trace:
    host_spans = _span_pairs(events, tracer_t0_ns)
    block = dev_trace.reduce(
        [s for s in host_spans
         if s[0] in ("serving/plan", "serving/device_step",
                     "serving/commit")], len(devices))
    out["device"].update(busy_s=block["busy_s"], window_s=block["window_s"])
    out["breakdown"] = {"device_ops": block["device_ops"],
                        "idle_gaps": block["idle_gaps"]}
    in_win = lambda s: t_window0 * 1e9 <= s[1] and s[2] <= t_window1 * 1e9
    out["layer_ctx"] = {
        "kind": kind, "trace": block, "peaks": peaks,
        "chips": len(devices), "num_slots": sizes["num_slots"],
        "late_ms": late, "queue_ms": queue,
        "spans": [s for s in host_spans if in_win(s)],
        "active_slots": occupancy,
        "tokens_per_s": e2e["serve_tokens_per_s"],
        # beyond serve.py's keys: what a kernel's required work is
        # computed from (harness/ssm_cost.py)
        "config": config_file, "model": cell_file["model"],
    }
  return out
