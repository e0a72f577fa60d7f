"""Start-up rules of the device-facing edge: where the compile cache
goes, what happens without a TPU or with an unknown one, and who may
reach for a chip."""

import os
import subprocess
import sys
import types

import pytest

from easyparallellibrary_tpu.profiler.flops import (
    PEAK_FLOPS, estimate_mfu, peak_flops_info)
from easyparallellibrary_tpu.serving.transport import ProcessTransport
from easyparallellibrary_tpu.testing import factories
from easyparallellibrary_tpu.utils import chip, compile_cache, launcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
  full = {k: v for k, v in os.environ.items()
          if k != compile_cache.ENV_VAR}
  full.update(env)
  return subprocess.run([sys.executable, *args], capture_output=True,
                        text=True, env=full, cwd=REPO, timeout=300)


# ------------------------------------------------------- compile cache --

_REPORT = ("import jax; from easyparallellibrary_tpu.utils import "
           "compile_cache as c; print(c.configure()); "
           "print(jax.config.jax_compilation_cache_dir); "
           "print(jax.config.jax_persistent_cache_min_compile_time_secs)")


def test_compile_cache_env_set_means_code_sets_no_directory(tmp_path):
  where = str(tmp_path / "elsewhere")
  out = _run(["-c", _REPORT], **{compile_cache.ENV_VAR: where})
  assert out.returncode == 0, out.stderr
  used, jax_dir, min_secs = out.stdout.split()
  # jax read the variable itself; nothing in code named another place.
  assert used == jax_dir == where
  assert float(min_secs) == 0.0


def test_compile_cache_default_is_fixed_in_checkout():
  want = os.path.join(REPO, ".jax_cache")
  assert compile_cache.DEFAULT_DIR == want
  for _ in range(2):              # two processes, one place
    out = _run(["-c", _REPORT + "; import os; print(os.environ["
                f"'{compile_cache.ENV_VAR}'])"])
    assert out.returncode == 0, out.stderr
    used, jax_dir, _, exported = out.stdout.split()
    assert used == jax_dir == exported == want


# ------------------------------------------------------------ no guess --


def test_peak_flops_unknown_device_kind_raises():
  v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
  assert peak_flops_info(v5e) == (PEAK_FLOPS["TPU v5 lite"], "TPU v5 lite")
  with pytest.raises(ValueError, match="no peak FLOP/s on record"):
    peak_flops_info(types.SimpleNamespace(device_kind="TPU v99",
                                          platform="tpu"))
  with pytest.raises(ValueError, match="cpu"):      # this box
    estimate_mfu(1e9, 1.0)


def test_chip_smoke_fails_without_a_tpu():
  out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
  assert out.returncode != 0
  assert "no TPU" in out.stderr
  assert '"ok"' not in out.stdout
  assert "platform cpu" in out.stdout       # it says what it found


# -------------------------------------------------- one process per chip --


def test_reaches_for_tpu_reads_the_environment_only():
  assert chip.reaches_for_tpu({})
  assert chip.reaches_for_tpu({"JAX_PLATFORMS": "tpu,cpu"})
  assert not chip.reaches_for_tpu({"JAX_PLATFORMS": "cpu"})
  assert not chip.this_process_holds_tpu()    # conftest pins the CPU


def test_launcher_refuses_local_workers_that_share_the_chips():
  cmd = [sys.executable, "-c", "pass"]
  with pytest.raises(chip.ChipOwnershipError, match="ONE worker"):
    launcher.launch_local(2, cmd, extra_env={"JAX_PLATFORMS": ""})
  assert launcher.launch_local(1, cmd,
                               extra_env={"JAX_PLATFORMS": ""}) == 0


def test_process_transport_refuses_a_chip_the_parent_holds(monkeypatch):
  t = ProcessTransport(0, factories.tiny_gpt, start=False)
  monkeypatch.delenv("JAX_PLATFORMS")
  monkeypatch.setattr(
      "easyparallellibrary_tpu.serving.transport.this_process_holds_tpu",
      lambda: True)
  with pytest.raises(chip.ChipOwnershipError, match="owns the chip"):
    t.start()
  assert t.child_pid is None                  # refused before any spawn
