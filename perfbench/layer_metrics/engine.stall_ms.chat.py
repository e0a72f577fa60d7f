"""Time lost to stalls of the serving loop where it moves
``ttft_p95_ms``; the arithmetic is ``harness/loop_spans.py``'s."""

from perfbench.harness.loop_spans import stall_ms as read  # noqa: F401
