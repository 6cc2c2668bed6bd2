"""repro.api: one query surface over every transport — local sessions,
``.kgz`` paths, and the socket server — answering the same
``QueryResult`` and raising the same typed errors.  The parity property
(local rows == remote rows, query by query) is the module's contract."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # test image without hypothesis: seeded-example fallback
    from _hypothesis_shim import given, settings, st

from repro import api
from repro.kg import persist
from repro.kg.store import TripleStore
from repro.live.delta import LiveStore
from repro.serve.server import KGServer

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


# queries spanning the algebra: plain BGP, star join, projection+LIMIT,
# OPTIONAL, UNION, GROUP BY-COUNT — every shape must answer identically
# through both transports
PARITY_QUERIES = [
    "SELECT * WHERE { ?s <http://ex/p0> ?o }",
    "SELECT * WHERE { ?s <http://ex/p0> ?o . ?s <http://ex/p1> ?o2 }",
    "SELECT ?s WHERE { ?s <http://ex/p1> ?o } LIMIT 3",
    "SELECT * WHERE { ?s <http://ex/p0> ?o "
    "OPTIONAL { ?s <http://ex/p2> ?h } }",
    "SELECT * WHERE { { ?s <http://ex/p0> ?o } UNION "
    "{ ?s <http://ex/p2> ?o } }",
    "SELECT ?s (COUNT(*) AS ?n) WHERE { ?s ?p ?o } GROUP BY ?s "
    "ORDER BY DESC(?n)",
]


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10**6))
def test_local_remote_parity(seed):
    store = rand_store(seed, 30)
    srv = KGServer(store, port=0, linger_ms=1.0, log=False).start()
    try:
        local = api.connect(store)
        with api.connect(f"127.0.0.1:{srv.port}", retry_s=5.0) as remote:
            for qtext in PARITY_QUERIES:
                lr = local.query(qtext)
                rr = remote.query(qtext)
                assert lr.vars == rr.vars, qtext
                assert lr.rows == rr.rows, qtext
                assert lr.n_total == rr.n_total, qtext
                assert lr.agg_vars == rr.agg_vars, qtext
                assert local.explain(qtext) == remote.explain(qtext)
    finally:
        srv.stop()


def test_query_result_surface():
    store = rand_store(11, 40)
    res = api.connect(store).query("SELECT * WHERE { ?s <http://ex/p0> ?o }")
    assert len(res) == len(res.rows) == res.n_total
    assert list(iter(res)) == res.rows
    d = res.to_dict()
    assert d["vars"] == list(res.vars)
    assert d["rows"] == [list(r) for r in res.rows]
    assert d["n_total"] == res.n_total
    assert res.raw is None  # local sessions have no wire reply


def test_local_typed_errors():
    s = api.connect(rand_store(3, 20))
    with pytest.raises(api.QueryParseError):
        s.query("SELECT nonsense {")
    with pytest.raises(api.BadRequestError, match="limit"):
        s.query("SELECT * WHERE { ?s ?p ?o }", limit=-1)
    # a plain TripleStore is read-only; every mutation op is rejected
    assert s.read_only
    for op in (lambda: s.insert([("<a>", "<b>", '"c"')]),
               lambda: s.delete([("<a>", "<b>", '"c"')]),
               s.compact):
        with pytest.raises(api.ReadOnlyError):
            op()
    live = api.connect(LiveStore(rand_store(3, 20)))
    with pytest.raises(api.BadRequestError, match="triples"):
        live.insert([("<only>", "<two>")])
    # every API error is a RuntimeError: pre-hierarchy callers still catch
    assert issubclass(api.KGError, RuntimeError)
    with pytest.raises(api.BadRequestError):
        api.connect(object())


def test_remote_typed_errors():
    store = rand_store(5, 25)
    srv = KGServer(store, port=0, linger_ms=1.0, log=False).start()
    try:
        with api.connect(f"127.0.0.1:{srv.port}", retry_s=5.0) as s:
            with pytest.raises(api.QueryParseError, match="server error"):
                s.query("SELECT nonsense {")
            with pytest.raises(api.BadRequestError, match="limit"):
                s.query("SELECT * WHERE { ?s ?p ?o }", limit=-1)
            with pytest.raises(api.ReadOnlyError) as ei:
                s.insert([("<a>", "<b>", '"c"')])
            assert ei.value.code == "read_only"
    finally:
        srv.stop()
    # the transport error doubles as ConnectionError for legacy callers
    assert issubclass(api.ProtocolError, ConnectionError)


_CLIENT = """
import sys
from repro.launch import serve
sys.argv = ["serve", "--connect", {addr!r}, "--query", {q!r}]
serve.main()
from jax._src import xla_bridge
sys.exit(3 if xla_bridge.backends_are_initialized() else 0)
"""


def test_serve_client_mode_leaves_jax_uninitialised():
    """The CLI's client mode runs beside a server that holds the chip, so
    it must never bring up a JAX backend of its own."""
    import os
    import subprocess
    import sys

    store = rand_store(6, 25)
    srv = KGServer(store, port=0, linger_ms=1.0, log=False).start()
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    try:
        script = _CLIENT.format(addr=f"127.0.0.1:{srv.port}",
                                q="SELECT * WHERE { ?s <http://ex/p0> ?o }")
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            env=dict(os.environ, PYTHONPATH=src), timeout=120,
        )
    finally:
        srv.stop()
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert '"vars"' in proc.stdout


def test_connect_path_arms(tmp_path):
    store = rand_store(7, 30)
    path = str(tmp_path / "t.kgz")
    persist.save(store, path)
    q = "SELECT * WHERE { ?s <http://ex/p0> ?o }"
    want = api.connect(store).query(q).rows

    ro = api.connect(path, read_only=True)
    assert ro.read_only
    assert ro.query(q).rows == want
    with pytest.raises(api.ReadOnlyError):
        ro.insert([("<x>", "<http://ex/p0>", '"y"')])

    rw = api.connect(path)  # mutable: a LiveStore over the loaded chain
    assert not rw.read_only
    r = rw.insert([("<x>", "<http://ex/p0>", '"y"')])
    assert r["inserted"] == 1 and r["generation"] >= 1
    assert rw.query(q).n_total == len(want) + 1
    assert rw.compact()["compacted"]
    assert rw.query(q).n_total == len(want) + 1


def test_shims_route_through_api():
    """kg.query.solve answers over live and plain stores via the same
    LocalSession.execute path (encoded bindings preserved)."""
    from repro.kg.query import decode_bindings, solve_text

    store = rand_store(9, 30)
    b = solve_text(store, "?s <http://ex/p0> ?o")
    want = api.connect(store).query("SELECT * WHERE { ?s <http://ex/p0> ?o }")
    got = [
        (row["?s"], row["?o"]) for row in decode_bindings(store, b)
    ]
    assert got == want.rows and b.n == want.n_total
    live = LiveStore(store)
    live.insert([("<zz>", "<http://ex/p0>", '"live"')])
    b2 = solve_text(live, "?s <http://ex/p0> ?o")
    assert b2.n == b.n + 1
