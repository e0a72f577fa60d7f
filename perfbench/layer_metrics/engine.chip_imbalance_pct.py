"""How much fuller the fullest chip of a divided engine is than the mean
one, over the window's steps: the mean of ``serving/chip_live_max`` (the
fullest chip's live positions a step) over the mean live positions a chip
(``serving/flat_positions`` over the chips), less one, in percent.  The
step is as slow as its fullest chip.  ``None`` without the counters (one
chip, a parent commit, a runner that does not hand them over)."""


def read(ctx):
  counters = ctx.get("counters") or {}
  fullest = counters.get("serving/chip_live_max")
  live = counters.get("serving/flat_positions")
  chips = ctx.get("chips", 1)
  if not fullest or not live or chips < 2 or sum(live) <= 0:
    return None
  mean_chip = sum(live) / len(live) / chips
  return 100.0 * (sum(fullest) / len(fullest) / mean_chip - 1.0)
