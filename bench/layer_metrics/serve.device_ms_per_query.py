"""Device milliseconds per answered query: the durations of the serve
programs' device events in the profiled window over the queries
answered in it."""


def read(ctx):
    d = ctx.device
    answered = ctx.window.get("answered", 0)
    if d is None or not answered or not d.programs:
        return None
    return 1e3 * sum(d.programs.values()) / answered
