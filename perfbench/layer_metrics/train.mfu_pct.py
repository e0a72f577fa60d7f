"""Model FLOP/s utilization: required FLOPs per token (causal attention
counted once, recomputation not counted; ``harness/flops.py``) x tokens
per second of this run / chips / the chip's bf16 peak."""


def read(ctx):
  if ctx.get("kind") != "train" or not ctx.get("peaks"):
    return None
  return (100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"]
          / ctx["chips"] / ctx["peaks"]["bf16_flops_per_s"])
