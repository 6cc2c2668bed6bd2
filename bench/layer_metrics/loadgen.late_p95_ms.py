"""How late the load generator sent: the 95th percentile of send time
minus due time over every request of the window, in milliseconds (a
starved generator is not a fast server)."""

import harness


def read(ctx):
    late = ctx.window.get("late_ms")
    return harness.quantile(late, 95) if late else None
