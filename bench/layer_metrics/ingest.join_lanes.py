"""Probe lanes per job of the engine's object-join steps, padding and
masked lanes included: the window's increase of the
``ingest.join_lanes`` counter (rows × probe width, per step) over its
jobs."""

import spans


def read(ctx):
    return spans.counter_per_job(ctx, "ingest.join_lanes")
