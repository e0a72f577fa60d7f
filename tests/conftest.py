"""Test harness: 8 virtual CPU devices.

Mirrors the reference's test strategy (SURVEY §4): the reference fakes 8
GPUs by monkey-patching `Cluster.available_gpus`
(/root/reference/tests/scheduler_test.py:37-48); here we ask XLA for 8
host-platform devices so sharding/collective logic runs for real, just on
CPU.  Must run before jax initializes its backends.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
  os.environ["XLA_FLAGS"] = (
      _flags + " --xla_force_host_platform_device_count=8").strip()

import shutil  # noqa: E402
import subprocess  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
  config.addinivalue_line(
      "markers", "slow: heavyweight tests excluded from the tier-1 run "
      "(`-m 'not slow'`)")
  config.addinivalue_line(
      "markers", "quick: one exactness test per composition "
      "(DP/TP/PP/SP/MoE/ZeRO/overlap) — `pytest -m quick` re-runs the "
      "whole matrix in <5 min on one core")


@pytest.fixture(scope="session")
def native_io():
  """The native IO library, built from csrc/ the way `make build` does.
  The .so is a build product git does not track, so a test that needs it
  builds it — or skips, saying why, where no compiler exists."""
  if not (shutil.which("make") and shutil.which(
      os.environ.get("CXX", "g++"))):
    pytest.skip("no make / C++ compiler here: the native IO library "
                "(csrc/) cannot be built")
  csrc = os.path.join(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))), "csrc")
  subprocess.run(["make", "-C", csrc], check=True, capture_output=True,
                 timeout=300)
  from easyparallellibrary_tpu.io import dataloader
  # A reader opened before the build cached "not there".
  dataloader._LIB_TRIED = False


@pytest.fixture(autouse=True)
def _reset_epl_env():
  """Each test gets a fresh Env (the reference resets Env in epl.init)."""
  yield
  from easyparallellibrary_tpu.env import Env
  Env.get().reset()
