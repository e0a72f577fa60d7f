"""Host share of the fused step (``serving/dispatch``) where it moves
``serve_tokens_per_s``; the arithmetic is ``harness/loop_spans.py``'s."""

from perfbench.harness.loop_spans import dispatch_ms as read  # noqa: F401
