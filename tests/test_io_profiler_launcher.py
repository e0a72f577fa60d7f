"""Aux subsystem tests: native/python IO, io slicing, profiler, launcher,
metric merge (reference analogs: estimator_dp_example.py IO tests,
profiler tests, test_launcher.sh)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu.constants import GraphKeys
from easyparallellibrary_tpu.io import (
    RecordReader, native_io_available, shard_files, write_records)
from easyparallellibrary_tpu.parallel.metrics import (
    collect_merged, merge_shard_metrics)
from easyparallellibrary_tpu.profiler import (
    FlopsProfiler, StepProfiler, compiled_cost, compiled_memory,
    estimate_mfu)


# ---------------------------------------------------------------- IO ----

def _make_files(tmp_path, n_files=4, recs_per_file=5):
  files = []
  for i in range(n_files):
    path = str(tmp_path / f"data_{i}.rec")
    write_records(path, [f"file{i}_rec{j}".encode()
                         for j in range(recs_per_file)])
    files.append(path)
  return files


def test_native_io_built(native_io):
  assert native_io_available(), "csrc/ built but the library did not load"


@pytest.mark.parametrize("use_native", [True, False])
def test_record_roundtrip(tmp_path, use_native):
  files = _make_files(tmp_path)
  reader = RecordReader(files, use_native=use_native)
  got = [r.decode() for r in reader]
  expected = [f"file{i}_rec{j}" for i in range(4) for j in range(5)]
  assert got == expected


def test_native_matches_python_reader(tmp_path):
  files = _make_files(tmp_path, n_files=3, recs_per_file=7)
  native = [r for r in RecordReader(files, use_native=True)]
  python = [r for r in RecordReader(files, use_native=False)]
  assert native == python


@pytest.mark.parametrize("use_native", [True, False])
def test_reader_sharding(tmp_path, use_native):
  # Contiguous proportional slicing (reference io_slicing semantics).
  files = _make_files(tmp_path, n_files=4)
  shard0 = [r.decode() for r in RecordReader(
      files, shard_index=0, num_shards=2, use_native=use_native)]
  shard1 = [r.decode() for r in RecordReader(
      files, shard_index=1, num_shards=2, use_native=use_native)]
  assert all(r.startswith(("file0", "file1")) for r in shard0)
  assert all(r.startswith(("file2", "file3")) for r in shard1)
  assert len(shard0) + len(shard1) == 20


def test_native_reader_streams_bounded_memory(tmp_path):
  """A file far larger than the prefetch budget must not be resident all
  at once: the reader streams records through bounded queues (round-1
  weak item 3 — the old design preloaded whole files).  Reads a few
  records from a ~64MB file with prefetch=8 and checks the process RSS
  grew by much less than the file size."""
  if not native_io_available():
    pytest.skip("native IO not built")

  def rss_mb():
    with open("/proc/self/status") as f:
      for line in f:
        if line.startswith("VmRSS:"):
          return int(line.split()[1]) / 1024.0
    return 0.0

  path = str(tmp_path / "big.rec")
  payload = b"x" * 65536                      # 64KB per record
  write_records(path, [payload] * 1024)       # ~64MB file

  before = rss_mb()
  reader = RecordReader([path], use_native=True, prefetch_records=8)
  it = iter(reader)
  got = [next(it) for _ in range(16)]
  grown = rss_mb() - before
  assert all(r == payload for r in got)
  # Budget: 8-record main queue + per-file staging (≥4) ≈ <2MB of
  # records; allow generous allocator slack but far below the 64MB file.
  assert grown < 32.0, f"RSS grew {grown:.1f}MB — whole file resident?"
  del it, reader


def test_large_record_grows_buffer(tmp_path):
  path = str(tmp_path / "big.rec")
  big = os.urandom(300_000)  # > initial 64KB buffer
  write_records(path, [b"small", big, b"tail"])
  got = list(RecordReader([path], use_native=True))
  assert got == [b"small", big, b"tail"]


def test_shard_files_proportional():
  epl.init()
  files = [f"f{i}" for i in range(10)]
  s0 = shard_files(files, 3, 0)
  s1 = shard_files(files, 3, 1)
  s2 = shard_files(files, 3, 2)
  assert s0 + s1 + s2 == files
  assert [len(s0), len(s1), len(s2)] == [4, 3, 3]


def test_shard_files_drop_last():
  epl.init(epl.Config({"io.drop_last_files": True}))
  files = [f"f{i}" for i in range(10)]
  shards = [shard_files(files, 3, i) for i in range(3)]
  assert [len(s) for s in shards] == [3, 3, 3]


def test_shard_files_validation():
  epl.init()
  with pytest.raises(ValueError):
    shard_files(["a"], 2, 2)


# ------------------------------------------------------------ profiler --

def test_compiled_cost_reports_flops():
  def f(x):
    return x @ x

  x = jnp.ones((128, 128))
  cost = compiled_cost(f, x)
  # 2 * 128^3 = 4.2M flops
  assert cost.get("flops", 0) >= 2 * 128 ** 3 * 0.5


def test_compiled_memory_reports_bytes():
  def f(x):
    return (x @ x).sum()

  mem = compiled_memory(f, jnp.ones((64, 64)))
  assert mem.get("argument_size_in_bytes", 0) >= 64 * 64 * 4


def test_step_profiler_summary():
  prof = StepProfiler(flops_per_step=1e9, tokens_per_step=1024, warmup=1)
  import time
  for _ in range(4):
    prof.tick()
    time.sleep(0.01)
  s = prof.summary()
  assert s["step_time_s"] > 0
  assert s["tokens_per_sec"] > 0
  # MFU needs a device with a peak on record: a CPU run reports none.
  assert "mfu" not in s


def test_flops_profiler_measure():
  prof = FlopsProfiler(every_n_steps=2)
  flops = prof.measure_from(lambda x: x @ x, jnp.ones((64, 64)))
  assert flops > 0
  assert prof.step() is None  # first call only arms the timer
  assert prof.step() is None
  stats = prof.step()
  assert stats is not None and stats["gflops_per_step"] > 0
  assert "mfu" not in stats       # CPU: no peak on record, no MFU


# ------------------------------------------------------------- metrics --

def test_collection_merge_in_train_step():
  import optax
  from flax import linen as nn
  from easyparallellibrary_tpu import ops
  from easyparallellibrary_tpu.parallel import (
      TrainState, create_sharded_train_state, make_train_step, parallelize)

  env = epl.init()
  mesh = epl.current_plan().build_mesh()

  class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
      return ops.Dense(1, parallel="none")(x)

  model = Net()
  x = jnp.ones((16, 4))
  y = jnp.zeros((16, 1))

  def loss_fn(params, batch, rng):
    pred = model.apply({"params": params}, batch["x"])
    err = pred - batch["y"]
    epl.add_to_collection(jnp.abs(err), GraphKeys.GLOBAL_MEAN_OBJECTS)
    epl.add_to_collection(jnp.abs(err), GraphKeys.GLOBAL_SUM_OBJECTS)
    return jnp.mean(err ** 2), {}

  def init_fn(rng):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(rng, x)["params"],
                             tx=optax.sgd(0.1))

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0))
  step = parallelize(make_train_step(loss_fn), mesh, shardings)
  state, metrics = step(state, {"x": x, "y": y}, jax.random.PRNGKey(1))
  mean_key = f"{GraphKeys.GLOBAL_MEAN_OBJECTS}_0"
  sum_key = f"{GraphKeys.GLOBAL_SUM_OBJECTS}_0"
  assert mean_key in metrics and sum_key in metrics
  np.testing.assert_allclose(float(metrics[sum_key]),
                             float(metrics[mean_key]) * 16, rtol=1e-5)


def test_merge_shard_metrics():
  shard_map = jax.shard_map
  from jax.sharding import PartitionSpec as P
  env = epl.init()
  mesh = env.cluster.build_mesh()

  def body(v):
    return merge_shard_metrics({"m": jnp.mean(v)}, "mean")["m"]

  f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P())
  out = f(jnp.arange(8.0))
  np.testing.assert_allclose(float(out), 3.5)


# ------------------------------------------------------------- launcher --

def test_launcher_local_multiprocess(tmp_path):
  """Two local processes bootstrap a shared JAX cluster
  (reference analog: tests/test_launcher.sh, 2 workers x 1 GPU)."""
  from easyparallellibrary_tpu.utils.launcher import launch_local
  script = tmp_path / "worker.py"
  script.write_text(
      "import os\n"
      "os.environ['XLA_FLAGS'] = "
      "'--xla_force_host_platform_device_count=2'\n"
      "import jax\n"
      "jax.config.update('jax_platforms', 'cpu')\n"
      "import sys; sys.path.insert(0, %r)\n"
      "from easyparallellibrary_tpu.utils.launcher import init_distributed\n"
      "init_distributed()\n"
      "assert jax.process_count() == 2, jax.process_count()\n"
      "assert len(jax.devices()) == 4\n"
      "print('worker', jax.process_index(), 'ok')\n"
      % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  code = launch_local(2, [sys.executable, str(script)],
                      retries=0, log_dir=str(tmp_path / "logs"))
  logs = "".join(
      open(os.path.join(tmp_path, "logs", f)).read()
      for f in os.listdir(tmp_path / "logs"))
  assert code == 0, logs
  assert "worker 0 ok" in logs and "worker 1 ok" in logs


def test_launcher_retry_on_failure(tmp_path):
  from easyparallellibrary_tpu.utils.launcher import launch_local
  script = tmp_path / "fail.py"
  script.write_text("import sys; sys.exit(3)\n")
  code = launch_local(1, [sys.executable, str(script)], retries=1)
  assert code == 1


def test_memory_profiler_records_csv_png(tmp_path):
  pytest.importorskip("matplotlib")   # optional dep: dump_png degrades
  from easyparallellibrary_tpu.profiler import MemoryProfiler
  prof = MemoryProfiler(every_n_steps=2)
  x = jnp.ones((64, 64))
  for _ in range(6):
    x = (x @ x) / 64.0
    prof.step()
  assert len(prof.records) == 3          # steps 2, 4, 6
  assert prof.peak_bytes() >= 0.0
  csv_path = str(tmp_path / "mem.csv")
  prof.dump_csv(csv_path)
  assert os.path.getsize(csv_path) > 0
  png_path = str(tmp_path / "mem.png")
  prof.dump_png(png_path, phase_spans=[(2, 4, "warmup")])
  assert os.path.exists(png_path) and os.path.getsize(png_path) > 0


def test_memory_profiler_empty_png_is_noop(tmp_path):
  from easyparallellibrary_tpu.profiler import MemoryProfiler
  prof = MemoryProfiler(every_n_steps=1)
  png_path = str(tmp_path / "none.png")
  prof.dump_png(png_path)
  assert not os.path.exists(png_path)
