"""Device time a step of the ``kv_write`` kernel where it moves
``serve_tokens_per_s``; the arithmetic is ``harness/kernel_time.py``'s."""

from perfbench.harness.kernel_time import kv_write_ms as read  # noqa: F401
