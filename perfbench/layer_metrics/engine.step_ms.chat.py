"""Fused-step time where it moves ``itl_p95_ms``; the arithmetic is
``harness/spans.py``'s."""

from perfbench.harness.spans import engine_step_ms as read  # noqa: F401
