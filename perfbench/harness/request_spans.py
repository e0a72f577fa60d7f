"""Arithmetic on the spans of a request's phases and of the host's turn:
``[(name, start_ns, end_ns)]``, every ``serving/*`` span the program
recorded that lies whole inside the window.

The scheduler writes one ``serving/queued`` span a request from submit to
admission and one ``serving/prefill`` from admission to the commit that
emits its first token, on the tracer's clock, which is the benchmark's:
due -> submit (``loadgen.late_p95_ms``) + queued + prefill is a request's
time to first token.

The engine names its turn of the loop: ``serving/plan``, ``serving/
dispatch``, then ``serving/fetch`` (the wait for the device: under the
overlapped loop for what is left of the step before), ``serving/commit``,
``serving/publish``; the rest of a step period is the caller's.  A period
less the fetch of the call that starts it is the host's turn: while it
stays under the device's step the device never waits, and where it reaches
the period the host sets it.  A program that records none of these (a
parent commit) gives ``None``.
"""

from __future__ import annotations

from perfbench.harness import loop_spans, spans as spans_lib, stats
from perfbench.harness.result import say

QUEUED = "serving/queued"
PREFILL = "serving/prefill"
FETCH = "serving/fetch"
# The host's named shares of its turn; what is left is the caller's.
TURN_PARTS = ("serving/plan", loop_spans.DISPATCH, "serving/commit",
              "serving/publish")


def _p95_ms(ctx, name: str):
  d = spans_lib.durations_ms(ctx.get("spans", ()), name)
  return stats.percentile(d, 95) if d else None


def admit_wait_p95_ms(ctx):
  """Reader of ``sched.admit_wait_p95_ms``: the wait in the scheduler's
  queue, submit to admission, 95th percentile over the window's
  ``serving/queued`` spans."""
  return _p95_ms(ctx, QUEUED)


def prefill_p95_ms(ctx):
  """Reader of ``engine.prefill_p95_ms``: admission to the commit that
  emits the first token, 95th percentile over the window's
  ``serving/prefill`` spans."""
  return _p95_ms(ctx, PREFILL)


def host_turns(spans):
  """Per step period (the gap between two successive ``serving/dispatch``
  starts): ``(turn_ms, {part: ms})``, the period less the fetch of the
  call that starts it, and the named parts that start inside it."""
  dispatches = sorted((s, e) for n, s, e in spans if n == loop_spans.DISPATCH)
  # A call's fetch starts on the stamp its dispatch ends on.  The first
  # call of a burst has none (its turn is the whole gap); a drain's fetch
  # follows no dispatch and is no call's that starts a gap.
  fetch_at = {s: e - s for n, s, e in spans if n == FETCH}
  parts = sorted((s, e - s, n) for n, s, e in spans if n in TURN_PARTS)
  out, i = [], 0
  for (d0, d1), (nxt, _) in zip(dispatches, dispatches[1:]):
    named = dict.fromkeys(TURN_PARTS, 0.0)
    while i < len(parts) and parts[i][0] < nxt:
      if parts[i][0] >= d0:
        named[parts[i][2]] += parts[i][1] / 1e6
      i += 1
    out.append(((nxt - d0 - fetch_at.get(d1, 0.0)) / 1e6, named))
  return out


def host_turn_ms(ctx):
  """Reader of ``engine.host_turn_ms.*``: the host's turn of a step
  period, median over the window's periods."""
  turns = host_turns(ctx.get("spans", ()))
  if not turns:
    return None
  named = {p: stats.median([n[p] for _, n in turns]) for p in TURN_PARTS}
  rest = stats.median([t - sum(n.values()) for t, n in turns])
  say("host's turn of a step period, medians over "
      f"{len(turns)} periods: "
      + ", ".join(f"{p.split('/')[1]} {v:.3f}" for p, v in named.items())
      + f", the caller's and between spans {rest:.3f} ms")
  return stats.median([t for t, _ in turns])
