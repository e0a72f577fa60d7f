"""Wait in the scheduler's queue, submit to admission (the program's
``serving/queued`` span), 95th percentile over the window's requests; the
arithmetic is ``harness/request_spans.py``'s."""

from perfbench.harness.request_spans import admit_wait_p95_ms as read  # noqa: F401
