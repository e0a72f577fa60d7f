"""Mean of the program's ``serving/active_slots`` counter over the
window's steps, as a share of ``num_slots``."""


def read(ctx):
  active = ctx.get("active_slots")
  if not active:
    return None
  return 100.0 * sum(active) / len(active) / ctx["num_slots"]
