"""The small-batch fast path: fused scan-join chain vs the general
executor (bit-identical rows over property-generated queries), overlay
fallback, signature warm-up, and the adaptive micro-batch linger."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # test image without hypothesis: seeded-example fallback
    from _hypothesis_shim import given, settings, st

from repro.kg.store import TripleStore
from repro.live.delta import LiveStore
from repro.obs import get_registry
from repro.serve import parse_select
from repro.serve.exec import get_executor
from repro.serve.server import _AdaptiveLinger

SUBS = [f"<http://ex/s{i}>" for i in range(5)]
PREDS = [f"<http://ex/p{i}>" for i in range(3)]
OBJS = SUBS[:2] + ['"1"', '"2"', '"10"', '"abc"', '""']


def rand_store(seed: int, n_triples: int) -> TripleStore:
    rng = np.random.default_rng(seed)
    triples = {
        (
            SUBS[rng.integers(0, len(SUBS))],
            PREDS[rng.integers(0, len(PREDS))],
            OBJS[rng.integers(0, len(OBJS))],
        )
        for _ in range(n_triples)
    }
    return TripleStore.from_ntriples(sorted(triples))


# chain-eligible shapes (Scan → BindJoin* with sort/project/limit on
# top); the templates close over predicate/object constants
CHAIN_TEMPLATES = [
    lambda p, o: f"SELECT * WHERE {{ ?s {p[0]} ?o }}",
    lambda p, o: f"SELECT * WHERE {{ ?s {p[0]} {o[0]} }}",
    lambda p, o: f"SELECT * WHERE {{ ?s ?p ?o }}",
    lambda p, o: f"SELECT ?o WHERE {{ ?s {p[0]} ?o }} LIMIT 2",
    lambda p, o: f"SELECT * WHERE {{ ?s {p[0]} ?a . ?s {p[1]} ?b }}",
    lambda p, o: (
        f"SELECT ?s ?c WHERE {{ ?s {p[0]} ?a . ?s {p[1]} ?b . "
        f"?s {p[0]} ?c }} LIMIT 5"
    ),
    lambda p, o: f"SELECT * WHERE {{ {o[0]} {p[0]} ?o }}",
]


def _both_paths(ex, qtext, n_queries=1):
    """Rows from the fast path and the forced-general path for the same
    micro-batch; asserts the fast path actually took the batch."""
    q = parse_select(qtext)
    plan = ex.plan(q)
    qs = [q] * n_queries
    reg = get_registry()
    before = reg.counter("exec.fastpath_dispatches").value
    ex.fastpath_enabled = True
    fast = ex.execute(plan, qs)
    took_fast = reg.counter("exec.fastpath_dispatches").value > before
    ex.fastpath_enabled = False
    try:
        gen = ex.execute(plan, qs)
    finally:
        ex.fastpath_enabled = True
    return fast, gen, took_fast


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 10**6),
    n=st.integers(0, 30),
    t=st.integers(0, len(CHAIN_TEMPLATES) - 1),
    bsz=st.sampled_from([1, 3]),
)
def test_fastpath_matches_general(seed, n, t, bsz):
    rng = np.random.default_rng(seed + 1)
    store = rand_store(seed, n)
    ex = get_executor(store)
    p = [PREDS[rng.integers(0, len(PREDS))] for _ in range(2)]
    o = [SUBS[rng.integers(0, len(SUBS))]]
    qtext = CHAIN_TEMPLATES[t](p, o)
    fast, gen, took_fast = _both_paths(ex, qtext, n_queries=bsz)
    for i in range(bsz):
        assert fast.n(i) == gen.n(i), qtext
        assert fast.rows(i) == gen.rows(i), qtext
    # an eligible chain over a non-empty packed store must route fast
    # (star templates are eligible only when the planner picked bind
    # joins, which depends on the per-store cardinality estimates)
    from repro.serve import plan as P

    if (
        store.n_triples > 0
        and store.device_keys("spo") is not None
        and P.fastpath_chain(ex.plan(parse_select(qtext))) is not None
    ):
        assert took_fast, qtext


def test_ineligible_shapes_fall_back():
    store = rand_store(2, 40)
    ex = get_executor(store)
    reg = get_registry()
    for qtext in (
        "SELECT * WHERE { ?s <http://ex/p0> ?o FILTER(?o > 1) }",
        "SELECT * WHERE { { ?s <http://ex/p0> ?o } UNION "
        "{ ?s <http://ex/p1> ?o } }",
        "SELECT ?s (COUNT(*) AS ?n) WHERE { ?s ?p ?o } GROUP BY ?s",
    ):
        q = parse_select(qtext)
        before = reg.counter("exec.fastpath_dispatches").value
        ex.execute(ex.plan(q), [q])
        assert reg.counter("exec.fastpath_dispatches").value == before, qtext


def test_overlay_falls_back_to_general():
    """A live store's overlay view never takes the fast path (PR 7
    semantics: fused overlay queries run the general pipeline) but the
    answers still reflect the mutations."""
    store = rand_store(4, 30)
    live = LiveStore(store)
    ex = get_executor(store)
    q = parse_select("SELECT * WHERE { ?s <http://ex/p0> ?o }")
    plan = ex.plan(q)
    base_n = ex.execute(plan, [q]).n(0)
    live.insert([("<http://ex/new>", "<http://ex/p0>", '"live"')])
    reg = get_registry()
    before = reg.counter("exec.fastpath_dispatches").value
    res = ex.execute(plan, [q], view=live.view())
    assert reg.counter("exec.fastpath_dispatches").value == before
    assert res.n(0) == base_n + 1
    assert ("<http://ex/new>", '"live"') in res.rows(0)


def test_warmup_precompiles_signatures():
    store = rand_store(8, 50)
    ex = get_executor(store)
    n_warmed = ex.warmup()
    assert n_warmed >= 1
    reg = get_registry()
    compiles = reg.counter("exec.fastpath_compiles").value
    # the exact shapes warmup ran: a batch-1 single-pattern query on the
    # store's top predicate must hit the compiled-function cache
    pos = store.indexes["pos"]
    preds, counts = np.unique(np.asarray(pos.cols[0]), return_counts=True)
    p0 = store.decode_term(int(preds[np.argmax(counts)]))
    q = parse_select(f"SELECT * WHERE {{ ?s {p0} ?o }}")
    res = ex.execute(ex.plan(q), [q])
    assert res.n(0) > 0
    assert reg.counter("exec.fastpath_compiles").value == compiles


def test_adaptive_linger_windows():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    lg = _AdaptiveLinger(max_s=0.002, registry=reg, full_batch=64)
    # cold start: no rate estimate yet -> the full configured window
    assert lg.window_s() == 0.002
    t = 0
    lg.observe_arrival(t)
    assert lg.window_s() == 0.002  # one arrival: still no gap estimate
    # sparse traffic (1 request/s): nobody will share the batch -> zero
    for _ in range(5):
        t += 1_000_000_000
        lg.observe_arrival(t)
    assert lg.window_s() == 0.0
    # a dense burst (50 µs gaps): linger, scaled by expected batch share
    for _ in range(200):
        t += 50_000
        lg.observe_arrival(t)
    w = lg.window_s()
    assert 0.0 < w <= 0.002
    expected = 0.002 / lg._gap_s
    assert w == pytest.approx(0.002 * min(1.0, expected / 64), rel=1e-6)
    # the exec-time floor: batching finer than one dispatch can't help
    reg.observe("serve.exec_ms", 1.5)
    assert 0.0015 - 1e-9 <= lg.window_s() <= 0.002
