"""The trace reduction: interval arithmetic on hand-made events, and the
same code on small traces recorded on the chip in PR 23
(``tests/data/trace_planes_*.json``, written by ``xplane.dump_slice``)."""

import json
import os

import pytest

from perfbench.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_subtract_total():
  u = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)])
  assert u == [(0, 3), (5, 8)] and xplane.total(u) == 6
  assert xplane.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
  assert xplane.subtract([(0, 3), (5, 8)], [(1, 6)]) == [(0, 1), (6, 8)]
  assert xplane.subtract([(0, 3)], []) == [(0, 3)]


def test_names():
  text = "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
  assert xplane.op_name(text) == "fusion.12"
  assert xplane.base_name("fusion.12") == "fusion"
  assert xplane.base_name("all-gather-start.3.1") == "all-gather-start"
  assert xplane.is_container("while.7") and not xplane.is_container("while_x")
  assert xplane.is_collective("all-reduce.1")
  assert xplane.is_collective("reduce-scatter.2")
  assert not xplane.is_collective("fusion.3")


def _planes(ops, async_ops=()):
  return {"/device:TPU:0": {"XLA Ops": ops, "Async XLA Ops": list(async_ops)},
          "/host:CPU": {"main": [("perfbench/anchor", 0.0, 1.0)]}}


def test_busy_idle_and_collective_overlap():
  ops = [("%fusion.1 = f32[] fusion()", 0, 10),
         ("%while.2 = () while()", 0, 100),        # container: not counted
         ("%all-reduce.1 = f32[] all-reduce()", 20, 10),
         ("%fusion.2 = f32[] fusion()", 25, 10),
         ("%fusion.3 = f32[] fusion()", 60, 10)]
  r = xplane.reduce(_planes(ops), window=(0, 100),
                    host_spans=[("plan", 10, 20), ("wait", 35, 60)])
  assert r["busy_s"] == pytest.approx(35e-9)       # 0-10, 20-35, 60-70
  assert r["window_s"] == pytest.approx(100e-9)
  assert r["collective_s"] == pytest.approx(10e-9)
  assert r["exposed_collective_s"] == pytest.approx(5e-9)   # 20-25
  gaps = dict(map(tuple, r["idle_gaps"]))
  assert gaps["wait"] == pytest.approx(25e-9)
  assert gaps["plan"] == pytest.approx(10e-9)
  assert gaps["(no host span)"] == pytest.approx(30e-9)
  assert dict(map(tuple, r["device_ops"]))["fusion"] == pytest.approx(30e-9)


def test_async_collective_counts_from_start_to_done():
  ops = [("%fusion.1 = f32[] fusion()", 0, 10)]
  async_ops = [("%all-gather-start.1 = () all-gather-start()", 5, 20),
               ("%copy-start.1 = () copy-start()", 0, 50)]
  r = xplane.reduce(_planes(ops, async_ops), window=(0, 50))
  assert r["collective_s"] == pytest.approx(20e-9)
  assert r["exposed_collective_s"] == pytest.approx(15e-9)   # 10-25


def test_two_chips_are_averaged_and_no_device_plane_is_an_error():
  planes = {"/device:TPU:0": {"XLA Ops": [("a", 0, 10)]},
            "/device:TPU:1": {"XLA Ops": [("a", 0, 30)]}}
  r = xplane.reduce(planes, window=(0, 40))
  assert r["chips"] == 2 and r["busy_s"] == pytest.approx(20e-9)
  with pytest.raises(ValueError):
    xplane.reduce({"/host:CPU": {"python": [("x", 0, 1)]}})


@pytest.mark.parametrize("name,chips", [("trace_planes_1chip.json", 1),
                                        ("trace_planes_4chip.json", 4)])
def test_recorded_trace(name, chips):
  path = os.path.join(DATA, name)
  if not os.path.exists(path):
    pytest.skip(f"{name} was not recorded")
  with open(path) as f:
    planes = json.load(f)
  r = xplane.reduce(planes)
  assert r["chips"] == chips
  assert 0 < r["busy_s"] <= r["window_s"] * (1 + 1e-9)
  # a training step keeps the chip busy nearly all the time (the slice's
  # edges cut into operations, so not quite all of it)
  assert r["busy_s"] / r["window_s"] > 0.85
  assert len(r["device_ops"]) <= 10 and r["device_ops"][0][1] > 0
  # no container among the counted operations
  assert not any(xplane.is_container(n) for n in r["op_seconds"])
  # every kind's time is within the busy time (a chip runs one op at a time)
  assert sum(r["op_seconds"].values()) <= r["busy_s"] * 1.02
  if chips == 1:
    assert r["collective_s"] == 0
  else:
    assert r["collective_s"] > 0
    assert 0 <= r["exposed_collective_s"] <= r["collective_s"]
