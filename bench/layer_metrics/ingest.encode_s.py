"""Encode seconds per job: the self time of the engine's ``ingest.encode``
spans, term columns joined and turned into dictionary ids (reads nested in
them excluded)."""

import spans


def read(ctx):
    return spans.self_seconds_per_job(ctx, "ingest.encode")
