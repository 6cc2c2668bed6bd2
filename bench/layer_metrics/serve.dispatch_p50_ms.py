"""Executor dispatch: the median of the ``exec.dispatch_ms`` histogram
over the window (``serve/exec.py``, ``serve/fastpath.py``; it ends after
the capacity vector reaches the host, so it holds the device's time)."""

import harness


def read(ctx):
    return harness.histogram_quantile(ctx.histogram("exec.dispatch_ms"), 50)
