"""Compaction seconds per job: the self time of the engine's
``ingest.compact`` spans, the host's selection of each step's emitted
rows and their append."""

import spans


def read(ctx):
    return spans.self_seconds_per_job(ctx, "ingest.compact")
