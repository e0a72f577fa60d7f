"""Scheduler host time per step where it moves ``serve_tokens_per_s``; the arithmetic
is ``harness/spans.py``'s."""

from perfbench.harness.spans import sched_host_ms as read  # noqa: F401
