"""How late the load generator ran: actual submit minus due time, 95th
percentile over the measured requests.  A starved generator must not be
read as a fast server."""

from perfbench.harness import stats


def read(ctx):
  late = ctx.get("late_ms")
  return stats.percentile(late, 95) if late else None
