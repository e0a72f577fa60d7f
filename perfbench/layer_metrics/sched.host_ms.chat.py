"""Scheduler host time per step where it moves ``itl_p95_ms``; the arithmetic
is ``harness/spans.py``'s."""

from perfbench.harness.spans import sched_host_ms as read  # noqa: F401
