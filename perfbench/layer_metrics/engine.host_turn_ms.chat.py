"""The host's turn of a step period (the period less the ``serving/fetch``
of the call that starts it) where it moves ``itl_p95_ms``; the arithmetic
is ``harness/request_spans.py``'s."""

from perfbench.harness.request_spans import host_turn_ms as read  # noqa: F401
