"""The K/V-window attend's share of its roofline (``slot_attn_kvwin``).

Required work a second: the cell's completed requests a second
(``serve_tokens_per_s`` over the mix's mean output length) times the mix's
mean requirement a request (``harness/kv_attn_cost.py``: the rows each
slot-step's windows cover, read once as keys and values in every window
layer, and every query's scores and value product, from shapes and the
mix's own length quantiles).  Time: the kernel's busy share of the step
period (``harness/kernel_time.py``).  A steady-state estimate; which bound
holds is printed.  ``None`` where the kernel's name is absent from the
trace or the run handed over no such configuration.
"""

from perfbench.harness import kv_attn_cost


def read(ctx):
  return kv_attn_cost.roofline(ctx, "kv_win_attn_roofline")
