"""Persistent XLA compile cache at a place that can be set from outside.

Every process on the chip starts with no compiled code, and compiling the
24-layer step is a large part of a cold run.  Entry points
(``chip_smoke.py``) call :func:`configure` before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax already honours it; this module
  sets no directory.
* unset — the cache goes to ``<checkout>/.jax_cache`` (git-ignored).  The
  path is part of the cache key, so it is fixed: never a temp, pid or
  timestamp directory.  The variable is exported so child processes
  inherit the same place.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
  """Point jax's persistent compile cache somewhere stable and let every
  program in; returns the directory in use."""
  path = os.environ.get(ENV_VAR)
  if not path:
    path = DEFAULT_DIR
    os.environ[ENV_VAR] = path
    jax.config.update("jax_compilation_cache_dir", path)
  # The defaults skip programs that compile in under a second; the
  # serving engine's small twins and the kernels' test programs are
  # exactly those, and a cold process pays for each of them again.
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  return path
