"""Due to admitted into a slot (``scheduler.on_admit``, stamped on the
benchmark's clock), 95th percentile over the measured requests."""

from perfbench.harness import stats


def read(ctx):
  queue = ctx.get("queue_ms")
  return stats.percentile(queue, 95) if queue else None
