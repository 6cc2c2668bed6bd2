"""Predicates replayed after a PTT overflow, per job: the window's
increase of the ``ingest.replays`` counter over its jobs; wants 0."""

import spans


def read(ctx):
    return spans.counter_per_job(ctx, "ingest.replays")
