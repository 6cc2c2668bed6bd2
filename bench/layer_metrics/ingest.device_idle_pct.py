"""Share of the profiled window of whole jobs in which the device ran
no operation, in percent."""


def read(ctx):
    d = ctx.device
    if d is None or d.window_s <= 0:
        return None
    return 100.0 * (1.0 - d.busy_s / d.window_s)
