"""The index-score kernel's share of its roofline (``dsa_index``) A CHIP
of a divided engine, every layer a selecting one: ``harness/ep_cost.py``
(a steady-state estimate, labelled ``host_clock`` as
``dsa_index_roofline`` is).  ``None`` where the kernel's name is absent
from the trace or the run is on one chip."""

from perfbench.harness import dsa_cost, ep_cost


def read(ctx):
  return ep_cost.selecting_roofline(ctx, dsa_cost.DSA_INDEX,
                                    "dsa_index_roofline.ep")
