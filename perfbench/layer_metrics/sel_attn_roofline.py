"""The selected-attend kernel's share of its roofline (``slot_attn_sel``).

Required work a second: the cell's completed requests a second
(``serve_tokens_per_s`` over the mix's mean output length) times the
mix's mean requirement a request (``harness/dsa_cost.py``: operations and
bytes from shapes and the mix's own length quantiles).  Time: the kernel's
busy share of the step period (``harness/kernel_time.py``).  A
steady-state estimate; which bound holds is printed.  ``None`` where the
kernel's name is absent from the trace or the run handed over no such
configuration.
"""

from perfbench.harness import dsa_cost


def read(ctx):
  return dsa_cost.roofline(ctx, dsa_cost.SEL_ATTN, "sel_attn_roofline")
