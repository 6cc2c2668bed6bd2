"""Blocking device-to-host reads per job on the engine's path: the
window's increase of the ``ingest.host_syncs`` counter over its jobs."""

import spans


def read(ctx):
    return spans.counter_per_job(ctx, "ingest.host_syncs")
