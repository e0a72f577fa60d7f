"""Admission to the commit that emits the first token (the program's
``serving/prefill`` span), 95th percentile over the window's requests; the
arithmetic is ``harness/request_spans.py``'s."""

from perfbench.harness.request_spans import prefill_p95_ms as read  # noqa: F401
