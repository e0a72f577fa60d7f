"""Fused-step time where it moves ``serve_tokens_per_s``; the arithmetic is
``harness/spans.py``'s."""

from perfbench.harness.spans import engine_step_ms as read  # noqa: F401
