"""Launcher CLI — multi-process / multi-host bootstrap.

Analog of the reference's ``epl-launch``
(epl/utils/launcher.py:25-203): the reference synthesizes TF_CONFIG and
CUDA_VISIBLE_DEVICES per process, tails logs, kills stragglers and
retries once (:125-188).  The TPU-native equivalents:

  * cluster bootstrap is `jax.distributed.initialize` (coordinator
    address + process count + process id) — `init_distributed()` wraps it
    with env-var fallbacks (the launcher exports them per process);
  * local multi-process testing (the reference's 2-worker launcher test,
    tests/Makefile:12-13) spawns N processes on CPU with a shared
    coordinator.  On a TPU host one process drives all local chips:
    several local workers that would each reach for them are refused
    (utils/chip.py);
  * straggler kill + single retry semantics are preserved.

Console entry: ``epl-tpu-launch --num_workers 2 -- python train.py``.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time
from typing import List, Optional

from easyparallellibrary_tpu.utils.chip import (
    ChipOwnershipError, reaches_for_tpu)
from easyparallellibrary_tpu.utils.logging import get_logger


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids=None):
  """Initialize multi-host JAX from args or EPL_LAUNCH_* env vars."""
  import jax
  coordinator_address = coordinator_address or os.environ.get(
      "EPL_COORDINATOR_ADDRESS")
  num_processes = num_processes or int(os.environ.get(
      "EPL_NUM_PROCESSES", "0")) or None
  process_id = process_id if process_id is not None else (
      int(os.environ["EPL_PROCESS_ID"])
      if "EPL_PROCESS_ID" in os.environ else None)
  if coordinator_address is None:
    get_logger().info("no coordinator configured; single-process run")
    return
  jax.distributed.initialize(
      coordinator_address=coordinator_address,
      num_processes=num_processes,
      process_id=process_id,
      local_device_ids=local_device_ids)


def _free_port() -> int:
  with socket.socket() as s:
    s.bind(("", 0))
    return s.getsockname()[1]


def launch_local(num_workers: int, command: List[str],
                 retries: int = 1, log_dir: str = "",
                 extra_env: Optional[dict] = None) -> int:
  """Spawn `num_workers` local processes with distributed env wired up.

  Returns the exit code (0 = all workers succeeded).  On any worker
  failure, the remaining workers are killed and the whole job is retried
  up to `retries` times (reference launcher.py:168-188).

  Every worker gets the same environment, so on a TPU host each would
  reach for every local chip: more than one worker is refused unless
  the environment keeps them off the TPU (``JAX_PLATFORMS=cpu``).
  """
  if num_workers > 1 and reaches_for_tpu({**os.environ,
                                          **(extra_env or {})}):
    raise ChipOwnershipError(
        f"{num_workers} local workers would each reach for all of this "
        "host's TPU chips, and a chip belongs to one process: run ONE "
        "worker per TPU host (it drives every local chip), or set "
        "JAX_PLATFORMS=cpu for local multi-process runs on the CPU")
  for attempt in range(retries + 1):
    port = _free_port()
    procs = []
    logs = []
    for rank in range(num_workers):
      env = dict(os.environ)
      env.update(extra_env or {})
      env["EPL_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
      env["EPL_NUM_PROCESSES"] = str(num_workers)
      env["EPL_PROCESS_ID"] = str(rank)
      stdout = None
      if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        logf = open(os.path.join(log_dir, f"worker_{rank}.log"), "w")
        logs.append(logf)
        stdout = logf
      procs.append(subprocess.Popen(
          command, env=env, stdout=stdout,
          stderr=subprocess.STDOUT if stdout else None))
    failed = False
    while procs:
      alive = []
      for p in procs:
        code = p.poll()
        if code is None:
          alive.append(p)
        elif code != 0:
          failed = True
      if failed:
        for p in alive:
          p.kill()  # kill stragglers (reference behavior)
        alive = []
      procs = alive
      if procs:
        time.sleep(0.2)
    for logf in logs:
      logf.close()
    if not failed:
      return 0
    get_logger().warning("worker failed (attempt %d/%d)", attempt + 1,
                         retries + 1)
  return 1


def main(argv: Optional[List[str]] = None) -> int:
  parser = argparse.ArgumentParser(
      prog="epl-tpu-launch",
      description="Launch a multi-process training job "
                  "(reference: epl-launch)")
  parser.add_argument("--num_workers", type=int, default=1)
  parser.add_argument("--machine_rank", type=int, default=0,
                      help="rank of this machine (multi-host)")
  parser.add_argument("--coordinator", default="",
                      help="host:port of process 0 (multi-host)")
  parser.add_argument("--log_dir", default="")
  parser.add_argument("--retries", type=int, default=1)
  parser.add_argument("command", nargs=argparse.REMAINDER,
                      help="-- python train.py ...")
  args = parser.parse_args(argv)
  command = [c for c in args.command if c != "--"]
  if not command:
    parser.error("no command given; usage: epl-tpu-launch -- python ...")
  if args.coordinator:
    # Multi-host: this process IS one worker; export env and exec.
    os.environ["EPL_COORDINATOR_ADDRESS"] = args.coordinator
    os.environ["EPL_NUM_PROCESSES"] = str(args.num_workers)
    os.environ["EPL_PROCESS_ID"] = str(args.machine_rank)
    return subprocess.call(command)
  return launch_local(args.num_workers, command, retries=args.retries,
                      log_dir=args.log_dir)


if __name__ == "__main__":
  sys.exit(main())
