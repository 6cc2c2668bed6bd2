"""Object-join step seconds per job: the summed durations of the engine's
``ingest.step`` spans whose ``op`` is ``OJM``, their ``ingest.sync`` and
``ingest.compact`` children included."""


def read(ctx):
    jobs = ctx.window.get("jobs", 0)
    steps = [ev["dur"] for ev in ctx.spans
             if ev.get("name") == "ingest.step" and ev.get("args", {}).get("op") == "OJM"]
    if not jobs or not steps:
        return None
    return sum(steps) / 1e6 / jobs
