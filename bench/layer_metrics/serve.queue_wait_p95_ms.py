"""Micro-batching wait: the 95th percentile of the server's
``serve.queue_wait_ms`` histogram over the window (enqueue to dispatch
pick-up in ``serve/server.py``), from the difference of two snapshots."""

import harness


def read(ctx):
    return harness.histogram_quantile(ctx.histogram("serve.queue_wait_ms"), 95)
