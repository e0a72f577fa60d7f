"""End-to-end input pipeline: native record reader -> batches ->
DevicePrefetcher -> fit (the full path the reference covers with its
dataset io tests + prefetch config)."""

import struct

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

import easyparallellibrary_tpu as epl
from easyparallellibrary_tpu import ops
from easyparallellibrary_tpu.io import (
    DevicePrefetcher, RecordReader, native_io_available, write_records)
from easyparallellibrary_tpu.parallel import (
    TrainState, create_sharded_train_state, make_train_step, parallelize)
from easyparallellibrary_tpu.runtime.loop import fit


def _write_token_files(tmp_path, n_files=4, recs_per_file=8, seq=16):
  """Each record: seq+1 int32 token ids."""
  r = np.random.RandomState(0)
  files = []
  for i in range(n_files):
    path = str(tmp_path / f"tokens_{i}.rec")
    recs = [r.randint(0, 64, seq + 1).astype(np.int32).tobytes()
            for _ in range(recs_per_file)]
    write_records(path, recs)
    files.append(path)
  return files


def _batches(files, batch_size=8, seq=16, use_native=True):
  """Generator: records -> fixed-size id batches (an epoch)."""
  def gen():
    buf = []
    for rec in RecordReader(files, use_native=use_native):
      buf.append(np.frombuffer(rec, np.int32).reshape(seq + 1))
      if len(buf) == batch_size:
        yield {"ids": np.stack(buf)}
        buf = []
  return gen


def test_native_reader_feeds_training(tmp_path, native_io):
  assert native_io_available()
  env = epl.init()
  mesh = epl.current_plan().build_mesh()
  files = _write_token_files(tmp_path)

  from easyparallellibrary_tpu.models import GPT, GPTConfig
  from easyparallellibrary_tpu.models.gpt import gpt_loss
  cfg = GPTConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                  d_ff=64, max_seq_len=16, dtype=jnp.float32)
  model = GPT(cfg)
  sample = jnp.zeros((8, 16), jnp.int32)

  def init_fn(rng):
    return TrainState.create(apply_fn=model.apply,
                             params=model.init(rng, sample)["params"],
                             tx=optax.adam(1e-2))

  state, shardings = create_sharded_train_state(
      init_fn, mesh, jax.random.PRNGKey(0))
  step = parallelize(
      make_train_step(lambda p, b, r: gpt_loss(model, p, b, r)),
      mesh, shardings)

  make_epoch = _batches(files)
  # Data factory: fresh prefetcher per epoch (4 batches/epoch, 10 steps).
  data = lambda: DevicePrefetcher(make_epoch(), mesh, depth=2)
  state, metrics = fit(step, state, data, num_steps=10, log_every=0)
  assert int(state.step) == 10
  assert np.isfinite(float(metrics["loss"]))


def test_prefetcher_depth_and_order(tmp_path):
  env = epl.init()
  mesh = epl.current_plan().build_mesh()
  files = _write_token_files(tmp_path, n_files=2, recs_per_file=8)
  batches = list(_batches(files, batch_size=8)())
  pre = DevicePrefetcher(iter(batches), mesh, depth=2)
  got = [np.asarray(b["ids"]) for b in pre]
  assert len(got) == len(batches)
  for a, b in zip(got, batches):
    np.testing.assert_array_equal(a, b["ids"])
  # Leaves came back as global sharded arrays on the data axis.
  pre2 = DevicePrefetcher(iter(batches), mesh, depth=1)
  first = next(iter(pre2))
  assert "data" in str(first["ids"].sharding.spec)


def test_reader_skip_records_matches_slice(tmp_path):
  """skip_records=N yields exactly full_stream[N:] — the input-position
  resume contract — on both the native and python readers."""
  seq = 16
  files = _write_token_files(tmp_path, n_files=3, recs_per_file=5, seq=seq)
  full = list(RecordReader(files, use_native=False))
  assert len(full) == 15
  for use_native in ([True, False] if native_io_available() else [False]):
    for skip in (0, 1, 7, 14, 15, 20):
      got = list(RecordReader(files, use_native=use_native,
                              skip_records=skip))
      assert got == full[skip:], (use_native, skip)


def test_reader_skip_detects_truncation(tmp_path):
  """A payload cut short mid-record must raise the same IOError from the
  skip (seek) path as from the read path — a resume offset past a
  truncated file must not be swallowed as clean EOF (ADVICE r2)."""
  import pytest
  from easyparallellibrary_tpu.io.dataloader import _python_reader

  path = str(tmp_path / "trunc.rec")
  write_records(path, [b"x" * 32, b"y" * 32], use_native=False)
  with open(path, "r+b") as f:
    f.truncate(8 + 32 + 8 + 16)  # second payload half gone
  with pytest.raises(IOError, match="truncated record"):
    list(_python_reader([path], skip_records=0))
  with pytest.raises(IOError, match="truncated record"):
    list(_python_reader([path], skip_records=2))
