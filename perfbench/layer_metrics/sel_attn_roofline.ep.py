"""The selected-attend kernel's share of its roofline (``slot_attn_sel``)
A CHIP of a divided engine, every layer a selecting one:
``harness/ep_cost.py`` (a steady-state estimate, labelled ``host_clock``
as ``sel_attn_roofline`` is).  ``None`` where the kernel's name is absent
from the trace or the run is on one chip."""

from perfbench.harness import dsa_cost, ep_cost


def read(ctx):
  return ep_cost.selecting_roofline(ctx, dsa_cost.SEL_ATTN,
                                    "sel_attn_roofline.ep")
