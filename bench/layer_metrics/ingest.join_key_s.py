"""Join-key seconds per job: the self time of the engine's
``ingest.join_key`` spans, each building a join key from two or more
columns (their joined values and its encode), for a child's batches or a
parent's PJTT.  A program without composite keys records no such span."""

import spans


def read(ctx):
    return spans.self_seconds_per_job(ctx, "ingest.join_key")
