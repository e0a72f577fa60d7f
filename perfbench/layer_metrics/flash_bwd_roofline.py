"""The two flash-attention backward kernels' share of their roofline,
dK/dV and dQ together: the required work of one backward
(``harness/flops.py:flash_bwd_cost``) is done by the pair.  Names, time and
steps as ``flash_fwd_roofline.py`` has them, whose arithmetic this is.
"""

from perfbench.harness import flops
from perfbench.layer_metrics.flash_fwd_roofline import share

KERNELS = ("flash_dkv", "flash_dq")


def read(ctx):
  return share(ctx, KERNELS, flops.flash_bwd_cost, "flash backward")
