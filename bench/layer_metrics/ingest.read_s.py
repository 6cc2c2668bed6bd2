"""Read seconds per job: the self time of the engine's ``ingest.read``
spans, source rows from a CSV read (eager) or the wait for the next block
of a stream."""

import spans


def read(ctx):
    return spans.self_seconds_per_job(ctx, "ingest.read")
