"""Sync seconds per job: the self time of the engine's ``ingest.sync``
spans, the host blocked on each step's outputs; it holds the step's
device time."""

import spans


def read(ctx):
    return spans.self_seconds_per_job(ctx, "ingest.sync")
