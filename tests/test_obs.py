"""Tests for repro.obs — metrics substrate and dispatch tracing.

Property tests (hypothesis, or the seeded shim when it isn't installed)
pin the histogram's accuracy contract: bucketed quantiles are within one
bucket's relative error (``1/SUBBUCKETS``) of the exact nearest-rank
sample quantile at any magnitude, and merging is associative.  The trace
tests are golden: the export must be valid Chrome trace-event JSON with
properly nested spans.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - fallback for slim images
    from _hypothesis_shim import given, settings, st

from repro.obs import (
    SUBBUCKETS,
    Histogram,
    MetricsRegistry,
    Tracer,
    bucket_bounds,
    bucket_index,
    get_registry,
    get_tracer,
)

# one bucket's relative width — the histogram's accuracy contract
REL_ERR = 1.0 / SUBBUCKETS


def _values(seed: int, n: int, lo=1e-6, hi=1e4) -> np.ndarray:
    """Log-uniform latency samples spanning 1µs..10s (in ms units)."""
    rng = np.random.default_rng(seed)
    return np.exp(rng.uniform(np.log(lo), np.log(hi), size=n))


# ---------------------------------------------------------------- buckets


@given(m=st.integers(1, 1000), e=st.integers(-20, 13))
@settings(max_examples=200, deadline=None)
def test_bucket_containment(m, e):
    # every positive value lands in a bucket containing it, whose width
    # is at most 1/SUBBUCKETS of its magnitude
    v = (m / 1000.0) * 2.0**e
    idx = bucket_index(v)
    lo, hi = bucket_bounds(idx)
    assert lo < v <= hi or np.isclose(v, lo), (v, lo, hi)
    assert hi / lo <= 1.0 + REL_ERR + 1e-12


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 400))
@settings(max_examples=30, deadline=None)
def test_percentile_vs_numpy(seed, n):
    # bucketed nearest-rank quantile is within one bucket's relative
    # error of numpy's exact inverted-CDF quantile, across 10 orders of
    # magnitude in one histogram
    vals = _values(seed, n)
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    for q in (50.0, 90.0, 99.0):
        exact = float(np.percentile(vals, q, method="inverted_cdf"))
        est = h.percentile(q)
        assert exact <= est <= exact * (1.0 + REL_ERR) + 1e-12, (
            q, exact, est, n,
        )


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_merge_associative(seed):
    vals = _values(seed, 300)
    parts = np.array_split(vals, 3)
    hs = []
    for part in parts:
        h = Histogram()
        for v in part:
            h.observe(float(v))
        hs.append(h)
    a, b, c = hs
    left = Histogram.merged(Histogram.merged(a, b), c)
    right = Histogram.merged(a, Histogram.merged(b, c))
    assert left.buckets == right.buckets
    assert left.count == right.count == len(vals)
    # merging equals observing everything into one histogram
    whole = Histogram()
    for v in vals:
        whole.observe(float(v))
    assert left.buckets == whole.buckets
    assert left.percentile(99) == whole.percentile(99)


def test_empty_histogram():
    h = Histogram()
    assert h.percentile(50) is None and h.percentile(99) is None
    d = h.to_dict()
    assert d["count"] == 0 and d["p50"] is None and d["p99"] is None
    # merging an empty histogram is the identity
    other = Histogram()
    other.observe(3.0)
    before = dict(other.buckets)
    other.merge(h)
    assert other.buckets == before and other.count == 1
    assert Histogram.from_dict(d).percentile(50) is None


def test_zero_and_negative_observations():
    h = Histogram()
    for v in (0.0, -1.5, 0.0):
        h.observe(v)
    assert h.count == 3 and h.zero == 3 and h.buckets == {}
    # all mass at zero: every quantile is 0.0, not None
    assert h.percentile(50) == 0.0 and h.percentile(99) == 0.0
    h.observe(8.0)
    assert h.percentile(50) == 0.0  # rank 2 of 4 still in the zero bucket
    assert h.percentile(99) >= 8.0


def test_to_dict_roundtrips_through_json():
    h = Histogram()
    for v in _values(7, 123):
        h.observe(float(v))
    d = json.loads(json.dumps(h.to_dict()))
    back = Histogram.from_dict(d)
    assert back.buckets == h.buckets
    assert back.count == h.count and back.max == h.max
    for q in (50, 90, 99):
        assert back.percentile(q) == h.percentile(q)


# --------------------------------------------------------------- registry


def test_registry_create_on_first_touch_and_snapshot():
    reg = MetricsRegistry()
    reg.inc("a.b", 2)
    reg.inc("a.b", 3)
    reg.gauge("g").set_max(5)
    reg.gauge("g").set_max(1)  # running max keeps 5
    reg.observe("h.ms", 2.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"a.b": 5}
    assert snap["gauges"] == {"g": 5}
    assert snap["histograms"]["h.ms"]["count"] == 1
    json.dumps(snap)  # the wire-op payload must be JSON-ready
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_registry_concurrent_updates_exact():
    # the regression the old hand-rolled ServerStats had: unlocked
    # += from accept/client/dispatch threads drops increments
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 2000

    def work():
        for _ in range(n_iter):
            reg.inc("c")
            reg.observe("h", 1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("c").value == n_threads * n_iter
    assert reg.histogram("h").count == n_threads * n_iter


def test_global_registry_is_a_singleton():
    assert get_registry() is get_registry()


# ------------------------------------------------------------------ trace


def test_tracer_disabled_records_nothing():
    tr = Tracer()
    with tr.span("x", cat="t"):
        pass
    tr.add_complete("y", "t", 0, 10)
    assert tr.export()["traceEvents"] == []


def test_trace_export_is_valid_chrome_trace_with_nested_spans():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", cat="test", plan="deadbeef"):
        with tr.span("inner", cat="test", round=0):
            pass
    doc = json.loads(json.dumps(tr.export()))  # must survive JSON
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert e["pid"] and e["tid"]
    inner, outer = evs
    # proper nesting: the inner span's interval sits inside the outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"]["plan"] == "deadbeef"
    assert inner["args"]["round"] == 0


def test_trace_retroactive_span_and_ring_capacity():
    tr = Tracer(capacity=4)
    tr.enable()
    for i in range(10):
        tr.add_complete("ev", "t", i * 1000, i * 1000 + 500, i=i)
    doc = tr.export()
    evs = doc["traceEvents"]
    assert len(evs) == 4  # ring keeps only the newest spans
    assert [e["args"]["i"] for e in evs] == [6, 7, 8, 9]
    assert doc["otherData"]["dropped_events"] == 6
    tr.clear()
    assert tr.export()["traceEvents"] == []
    assert tr.export()["otherData"]["dropped_events"] == 0


def test_tracer_span_records_on_exception():
    tr = Tracer()
    tr.enable()
    with pytest.raises(ValueError):
        with tr.span("failing", cat="t"):
            raise ValueError("boom")
    evs = tr.export()["traceEvents"]
    assert [e["name"] for e in evs] == ["failing"]


def test_global_tracer_is_a_singleton():
    assert get_tracer() is get_tracer()


# ------------------------------------------------- the profiler's clock


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """With tracing on, a ``span()`` inside a ``jax.profiler`` capture is
    on the capture's host plane as ``repro.<name>``; the Chrome export
    keeps the bare name."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tr = Tracer()
    tr.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("probe", cat="test", rows=3, flag=True):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [e.name for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    assert "repro.probe" in host
    assert [e["name"] for e in tr.export()["traceEvents"]] == ["probe"]


def test_annotations_only_while_tracing(monkeypatch):
    """Tracing off builds no ``TraceAnnotation``; on, each ``span()``
    builds one and a retroactive span none."""
    import jax

    built = []

    class Counting:
        def __init__(self, name, **kw):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    tr = Tracer()
    with tr.span("off", cat="t", rows=1):
        pass
    assert built == []
    tr.enable()
    with tr.span("on", cat="t", rows=1):
        pass
    tr.add_complete("late", "t", 0, 10)
    assert built == ["repro.on"]
    assert [e["name"] for e in tr.export()["traceEvents"]] == ["on", "late"]
