"""The readers PR 34 added (``harness/request_spans.py``) on hand-made
span lists: a request's wait in the queue and its prefill, and the host's
turn of a step period in the shapes the engine's loop takes; then the
manifest's entries for them."""

import pytest

from perfbench import run as run_lib
from perfbench.harness import request_spans
from perfbench.harness.manifest import Manifest

MS = 1e6
CHAT = ["gpt2m-chat-steady", "lfm2moe-chat-steady"]
BACKLOG = ["gpt2m-offline-backlog", "jamba2-3b-reasoning-backlog",
           "glm47flash-agent-backlog"]


def read(metric, ctx):
  return run_lib.load_module("layer_metrics", metric).read(ctx)


def call(t, *, plan=0.4, dispatch=2.0, fetch=5.0, commit=0.3, publish=0.9):
  """The spans of one ``engine.step()`` whose dispatch starts at ``t`` ms
  (overlapped loop: the dispatch of step k+1, then the fetch of step k);
  ``dispatch`` or ``fetch`` None: the call has none."""
  out = [("serving/plan", (t - plan) * MS, t * MS)]
  d1 = t + (dispatch or 0.0)
  f1 = d1 + (fetch or 0.0)
  out.append(("serving/device_step", t * MS, f1 * MS))
  if dispatch is not None:
    out.append(("serving/dispatch", t * MS, d1 * MS))
  if fetch is not None:
    out += [("serving/fetch", d1 * MS, f1 * MS),
            ("serving/commit", f1 * MS, (f1 + commit) * MS),
            ("serving/publish", (f1 + commit) * MS,
             (f1 + commit + publish) * MS)]
  return out


def test_host_turn_is_the_period_less_the_fetch_that_starts_it():
  # period 8.5: dispatch 2 + fetch 5 + commit 0.3 + publish 0.9 + plan
  # 0.4 of the next call leaves 0.2 - 0.3 of the caller's
  spans = [s for k in range(6) for s in call(8.5 * k)]
  turns = request_spans.host_turns(spans)
  assert len(turns) == 5
  for turn, named in turns:
    assert turn == pytest.approx(3.5)
    assert named == pytest.approx({
        "serving/plan": 0.4, "serving/dispatch": 2.0,
        "serving/commit": 0.3, "serving/publish": 0.9})
  for metric in ("engine.host_turn_ms.chat", "engine.host_turn_ms.backlog"):
    assert read(metric, {"spans": spans}) == pytest.approx(3.5)


def test_a_first_call_has_no_fetch_and_a_drain_no_dispatch():
  # a burst of three steps: the first call is all dispatch, then two
  # whole calls, then a drain that is all fetch; then a second burst
  burst = (call(0.0, fetch=None) + call(3.0) + call(11.0)
           + call(19.0, dispatch=None))
  spans = burst + call(40.0, fetch=None) + call(43.0)
  turns = [t for t, _ in request_spans.host_turns(spans)]
  # gap 0 -> 3: no fetch, the whole gap is the host's; 3 -> 11: less 5;
  # 11 -> 40 holds the call's own fetch (5) and the drain's, which follows
  # no dispatch and is not taken off; 40 -> 43: no fetch again
  assert turns == pytest.approx([3.0, 3.0, 24.0, 3.0])
  assert request_spans.host_turn_ms({"spans": spans}) == pytest.approx(3.0)
  # the drain's commit and publish start inside the long gap
  named = request_spans.host_turns(spans)[2][1]
  assert named["serving/commit"] == pytest.approx(0.6)
  assert named["serving/publish"] == pytest.approx(1.8)


def test_spans_cut_by_the_window_are_not_there():
  whole = [s for k in range(5) for s in call(10.0 * k)]
  # the window opened inside the first call's device_step (its dispatch
  # and its device_step are gone, its fetch is there) and closed inside
  # the last call's fetch (its dispatch is there, the rest is gone)
  first, last = call(0.0), call(40.0)
  cut = [s for s in whole
         if s not in first[:3] and s not in last[1:2] + last[3:]]
  turns = request_spans.host_turns(cut)
  assert len(turns) == 3            # 10 -> 20 -> 30 -> 40
  assert [t for t, _ in turns] == pytest.approx([5.0] * 3)
  # one dispatch alone makes no period
  assert request_spans.host_turn_ms({"spans": last[2:3]}) is None


def test_queue_wait_and_prefill_percentiles():
  spans = [("serving/queued", i * MS, (i + 1 + 0.1 * i) * MS)
           for i in range(21)]
  spans += [("serving/prefill", 0.0, (100 + 10 * i) * MS) for i in range(21)]
  ctx = {"spans": spans + call(0.0)}
  assert read("sched.admit_wait_p95_ms", ctx) == pytest.approx(2.9)
  assert read("engine.prefill_p95_ms", ctx) == pytest.approx(290.0)
  one = {"spans": [("serving/queued", 0.0, 2 * MS)]}
  assert read("sched.admit_wait_p95_ms", one) == pytest.approx(2.0)
  assert read("engine.prefill_p95_ms", one) is None


@pytest.mark.parametrize("ctx", [
    {}, {"spans": []},
    # a parent's list: the loop's spans, no phase and no publish
    {"spans": [s for k in range(3) for s in call(8.0 * k)
               if s[0] != "serving/publish"]}],
    ids=["no-spans-key", "no-spans", "parent"])
def test_a_program_without_the_spans_reads_none(ctx):
  assert read("sched.admit_wait_p95_ms", ctx) is None
  assert read("engine.prefill_p95_ms", ctx) is None
  turn = read("engine.host_turn_ms.chat", ctx)
  if ctx.get("spans"):
    # dispatch and fetch are older than this PR: the parent has a turn
    assert turn == pytest.approx(3.0)
  else:
    assert turn is None


def test_the_manifest_lists_each_reader_in_its_cells():
  man = Manifest()
  want = {
      "sched.admit_wait_p95_ms": ("scheduler", "ttft_p95_ms", CHAT),
      "engine.prefill_p95_ms": ("engine fused step", "ttft_p95_ms", CHAT),
      "engine.host_turn_ms.chat": ("engine fused step", "itl_p95_ms", CHAT),
      "engine.host_turn_ms.backlog": (
          "engine fused step", "serve_tokens_per_s", BACKLOG),
  }
  assert [m["name"] for m in man.doc["per_layer"][-len(want):]] == list(want)
  for name, (layer, moves, cells) in want.items():
    m = man.metrics[name]
    assert (m["layer"], m["moves"], m["workloads"]) == (layer, moves, cells)
    assert (m["unit"], m["better"], m["source"]) == (
        "ms", "lower", "program_span")
    for cell in man.workloads:
      reported = name in [x["name"] for x in
                          man.metrics_for(cell, "per_layer")]
      assert reported == (cell in cells)
    assert callable(run_lib.load_module("layer_metrics", name).read)
