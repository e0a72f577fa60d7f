"""Runner ``serve_family_even_counters``: ``runners/serve_family_even.py``
(the even order, untouched) handing the readers the program's per-step
COUNTERS of a traced run as well.

``serve_family`` reads one counter out of the tracer's events
(``serving/active_slots``) and drops the rest before a reader runs.  A
cell whose per-layer metrics are program counters of their own
(``serving/chip_live_max``, ``serving/exchange_rows_in``, ...) needs them
in the readers' context: ``layer_ctx["counters"]`` is ``{name: [value a
step, ...]}`` of every ``serving/*`` counter event of the window (the
tracer is cleared when the window opens, so all of them are the window's).

Everything else is ``serve_family_even``'s: that runner's module reads the
events through ``serve_family._span_pairs``, which ``run`` wraps for the
length of the call to see what it is handed (the runner's own file is not
this PR's to edit; ROADMAP R1 asks that a runner hand every counter over).
An untraced run is ``serve_family_even``'s to the letter.
"""

from __future__ import annotations

from perfbench.runners import serve_family, serve_family_even


def counters_of(events) -> dict:
  """``{name: [values]}`` of the ``serving/*`` counter events, in order."""
  out = {}
  for ev in events:
    if ev.get("ph") == "C" and ev["name"].startswith("serving/"):
      out.setdefault(ev["name"], []).append(ev["args"]["value"])
  return out


def run(**kw):
  seen = {}
  theirs = serve_family._span_pairs

  def span_pairs(events, t0_ns):
    seen.update(counters_of(events))
    return theirs(events, t0_ns)

  serve_family._span_pairs = span_pairs
  try:
    out = serve_family_even.run(**kw)
  finally:
    serve_family._span_pairs = theirs
  if "layer_ctx" in out:
    out["layer_ctx"]["counters"] = seen
  return out
