"""Engine seconds per job: the mean of the ``create_kg`` spans that
``repro.launch.rdfize`` records around ``core/executor.py``'s
``create_kg`` in the window's jobs."""


def read(ctx):
    spans = ctx.span_seconds("create_kg")
    return sum(spans) / len(spans) if spans else None
