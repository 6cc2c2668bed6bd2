"""Device milliseconds per job: the durations of the jitted programs'
device events in the profiled window (``_dedup_step``,
``_ojm_sorted_step``, ``_lexsort3`` and the rest), per job."""


def read(ctx):
    d = ctx.device
    jobs = ctx.window.get("jobs", 0)
    if d is None or not jobs or not d.programs:
        return None
    return 1e3 * sum(d.programs.values()) / jobs
