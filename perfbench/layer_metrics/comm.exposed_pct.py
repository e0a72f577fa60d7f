"""Share of the traced window in which a collective runs on a chip and
no compute does, averaged over the chips."""


def read(ctx):
  block = ctx.get("trace")
  if not block or ctx.get("chips", 1) < 2 or block["collective_s"] <= 0:
    return None
  return 100.0 * block["exposed_collective_s"] / block["window_s"]
