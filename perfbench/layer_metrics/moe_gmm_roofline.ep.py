"""The grouped-matmul kernel's share of its roofline A CHIP in a cell
whose held experts are divided over the chips of a mesh axis: 16 held
experts' three matrices a layer read once a step, plus the rows that
arrived at them from all chips in and out, against ``moe_gmm``'s device
time a chip; the arithmetic is ``harness/ep_cost.py``'s.  ``None`` on one
chip, without the program's ``serving/held_assignments`` counter (a
parent commit) or the kernel's name in the trace."""

from perfbench.harness.ep_cost import moe_roofline as read  # noqa: F401
