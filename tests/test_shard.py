"""repro.shard: subject-hash partitioning, dispatch-mode routing, the
scatter/gather merge vs the unsharded engine (property tests across shard
counts), manifest persistence, sharded ingestion, the coordinator server,
and the satellite regressions (open_store LRU cap, signature-legend cap)."""

import json
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # test image without hypothesis: seeded-example fallback
    from _hypothesis_shim import given, settings, st

from repro import api
from repro.api import LocalSession
from repro.kg import persist
from repro.kg.store import TripleStore
from repro.obs import MetricsRegistry
from repro.serve.algebra import parse_select, to_text
from repro.shard import (
    build_shard_stores,
    choose_dispatch,
    ingest_sharded,
    partition_store,
    partition_triples,
    shard_of_term,
    shard_store,
)
from repro.shard import merge as M
from repro.shard.coordinator import ShardGroup, ShardSession, _LocalBackend


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

SUBS = [f"<http://ex/s{i}>" for i in range(5)]
PREDS = [f"<http://ex/p{i}>" for i in range(3)]
LITS = ['"1"', '"2"', '"10"', '"2.5"', '"-3"', '"abc"', '"b c"', '""']
OBJS = SUBS[:2] + LITS


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


def decoded_triples(store: TripleStore):
    return [
        (
            store.decode_term(int(store.s[i])),
            store.decode_term(int(store.p[i])),
            store.decode_term(int(store.o[i])),
        )
        for i in range(store.n_triples)
    ]


def sharded_session(store: TripleStore, n_shards: int) -> ShardSession:
    """In-process scatter/gather session over n partitions of ``store``,
    with a private registry so counter asserts see only their own run."""
    backends = [
        _LocalBackend(LocalSession(s))
        for s in build_shard_stores(store, n_shards)
    ]
    return ShardSession(ShardGroup(backends, registry=MetricsRegistry()))


def assert_parity(store: TripleStore, qtext: str, n_shards: int) -> None:
    want = LocalSession(store).query(qtext)
    sess = sharded_session(store, n_shards)
    try:
        got = sess.query(qtext)
    finally:
        sess.close()
    assert got.vars == want.vars, (qtext, got.vars, want.vars)
    assert got.rows == want.rows, (
        f"{qtext} @ {n_shards} shards\n got: {got.rows[:5]}"
        f"\nwant: {want.rows[:5]}"
    )
    assert got.n_total == want.n_total, (qtext, got.n_total, want.n_total)
    assert got.agg_vars == want.agg_vars, qtext


# the eight algebra template classes the sharded engine must answer
# byte-identically: every dispatch mode (routed / scatter / decompose) and
# every merge rule (plain, ORDER BY/LIMIT top-k, DISTINCT dedup, keyed and
# global aggregate re-sum, OPTIONAL nulls, UNION bags) is covered
TEMPLATES = [
    lambda p, s: f"SELECT * WHERE {{ ?a {p[0]} ?b }}",
    lambda p, s: f"SELECT ?b WHERE {{ {s} {p[0]} ?b }}",  # routed
    lambda p, s: (  # star BGP + LIMIT: scatter with top-k merge
        f"SELECT * WHERE {{ ?a {p[0]} ?b . ?a {p[1]} ?c }} LIMIT 4"
    ),
    lambda p, s: (  # subject-object chain: decomposed dispatch
        f"SELECT * WHERE {{ ?a {p[0]} ?b . ?b {p[1]} ?c }}"
    ),
    lambda p, s: (
        f"SELECT DISTINCT ?b WHERE {{ ?a {p[0]} ?b }} ORDER BY ?b LIMIT 3"
    ),
    lambda p, s: (
        f"SELECT ?b (COUNT(?a) AS ?n) WHERE {{ ?a {p[0]} ?b }} "
        "GROUP BY ?b ORDER BY DESC(?n) LIMIT 5"
    ),
    lambda p, s: f"SELECT (COUNT(*) AS ?n) WHERE {{ ?a {p[0]} ?b }}",
    lambda p, s: (
        f"SELECT * WHERE {{ ?a {p[0]} ?b OPTIONAL {{ ?a {p[1]} ?c }} "
        f'FILTER(?b != "zz") }}'
    ),
]


# --------------------------------------------------------------------------
# partitioning
# --------------------------------------------------------------------------


def test_shard_of_term_stable_and_bounded():
    # crc32 is pinned by the manifest spec: same subject -> same shard,
    # everywhere, forever; single shard degenerates to 0
    for s in SUBS:
        assert shard_of_term(s, 1) == 0
        for n in (2, 3, 4, 7):
            a, b = shard_of_term(s, n), shard_of_term(s, n)
            assert a == b and 0 <= a < n
    import zlib

    assert shard_of_term("<http://ex/s0>", 4) == (
        zlib.crc32(b"<http://ex/s0>") % 4
    )


def test_partition_covers_and_colocates():
    store = rand_store(7, 60)
    triples = decoded_triples(store)
    for n in (1, 2, 4):
        buckets = partition_triples(triples, n)
        assert sum(len(b) for b in buckets) == len(triples)
        assert sorted(t for b in buckets for t in b) == sorted(triples)
        for i, bucket in enumerate(buckets):
            assert all(shard_of_term(s, n) == i for s, _p, _o in bucket)
        # the store-level partition agrees with the triple-level one
        assert [sorted(b) for b in partition_store(store, n)] == [
            sorted(b) for b in buckets
        ]
    stores = build_shard_stores(store, 4)
    assert sum(s.n_triples for s in stores) == store.n_triples


# --------------------------------------------------------------------------
# dispatch-mode routing
# --------------------------------------------------------------------------


def test_choose_dispatch_modes():
    p0, p1 = PREDS[0], PREDS[1]
    routed = parse_select(f"SELECT ?o WHERE {{ {SUBS[0]} {p0} ?o }}")
    star = parse_select(f"?a {p0} ?b . ?a {p1} ?c")
    chain = parse_select(f"?a {p0} ?b . ?b {p1} ?c")
    assert choose_dispatch(routed, 4) == (
        M.ROUTED, shard_of_term(SUBS[0], 4)
    )
    assert choose_dispatch(star, 4) == (M.SCATTER, None)
    assert choose_dispatch(chain, 4) == (M.DECOMPOSE, None)
    # one shard never fans out, whatever the shape
    for q in (routed, star, chain):
        assert choose_dispatch(q, 1) == (M.ROUTED, 0)


def test_scatter_query_strips_order_limit_for_aggregates_only():
    agg = parse_select(
        f"SELECT ?b (COUNT(?a) AS ?n) WHERE {{ ?a {PREDS[0]} ?b }} "
        "GROUP BY ?b ORDER BY DESC(?n) LIMIT 2"
    )
    sub = M.scatter_query(agg)
    assert sub.order_by == () and sub.limit is None
    plain = parse_select(f"SELECT ?b WHERE {{ ?a {PREDS[0]} ?b }} LIMIT 2")
    assert M.scatter_query(plain) is plain
    # decode caps: aggregates need every partial group, DISTINCT needs the
    # full per-shard distinct set, plain rows only the reply cap
    assert M.scatter_decode_limit(agg, 10) == M.BIG_LIMIT
    dist = parse_select(
        f"SELECT DISTINCT ?b WHERE {{ ?a {PREDS[0]} ?b }} LIMIT 3"
    )
    assert M.scatter_decode_limit(dist, 10) == 3
    assert M.scatter_decode_limit(plain, 10) == 10


def test_merge_scatter_rules():
    plain = parse_select(f"SELECT ?b WHERE {{ ?a {PREDS[0]} ?b }} LIMIT 3")
    rows, n = M.merge_scatter(
        plain, [([('"b"',), ('"a"',)], 2), ([('"c"',), ('"0"',)], 5)]
    )
    assert rows == [('"0"',), ('"a"',), ('"b"',)] and n == 3  # min(7, LIMIT)
    agg = parse_select(
        f"SELECT ?b (COUNT(?a) AS ?n) WHERE {{ ?a {PREDS[0]} ?b }} GROUP BY ?b"
    )
    rows, n = M.merge_scatter(
        agg, [([('"x"', 2), ('"y"', 1)], 2), ([('"x"', 3)], 1)]
    )
    assert rows == [('"x"', 5), ('"y"', 1)] and n == 2  # partials re-summed
    dist = parse_select(f"SELECT DISTINCT ?b WHERE {{ ?a {PREDS[0]} ?b }}")
    rows, n = M.merge_scatter(
        dist, [([('"a"',), ('"b"',)], 2), ([('"b"',), ('"c"',)], 2)]
    )
    assert rows == [('"a"',), ('"b"',), ('"c"',)] and n == 3  # cross-shard dedup


def test_decomposed_to_text_roundtrip():
    chain = parse_select(f"?a {PREDS[0]} ?b . ?b {PREDS[1]} ?c")
    for sub, _subject in M.decompose_queries(chain):
        again = parse_select(to_text(sub))
        assert again.patterns == sub.patterns
        assert again.out_vars() == sub.out_vars()


# --------------------------------------------------------------------------
# sharded answers == unsharded answers (the core property)
# --------------------------------------------------------------------------


def test_all_templates_all_shard_counts():
    store = rand_store(13, 40)
    for n in (1, 2, 4):
        for tpl in TEMPLATES:
            assert_parity(store, tpl(PREDS, SUBS[0]), n)


def test_empty_store_parity():
    store = TripleStore.from_ntriples([])
    for tpl in TEMPLATES:
        assert_parity(store, tpl(PREDS, SUBS[0]), 2)


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 10**6),
    n=st.integers(0, 30),
    t=st.integers(0, len(TEMPLATES) - 1),
    shards=st.sampled_from([1, 2, 4]),
)
def test_sharded_matches_unsharded_on_random_graphs(seed, n, t, shards):
    rng = np.random.default_rng(seed + 1)
    store = rand_store(seed, n)
    p = [PREDS[rng.integers(0, len(PREDS))] for _ in range(2)]
    s = SUBS[rng.integers(0, len(SUBS))]
    assert_parity(store, TEMPLATES[t](p, s), shards)


def test_routed_query_touches_exactly_one_shard():
    store = rand_store(5, 50)
    sess = sharded_session(store, 4)
    reg = sess.group.registry
    try:
        sess.query(f"SELECT ?o WHERE {{ {SUBS[0]} {PREDS[0]} ?o }}")
        assert reg.counter("shard.routed").value == 1
        assert reg.counter("shard.shard_requests").value == 1
        assert reg.histogram("shard.fanout").max == 1.0
        sess.query(f"?a {PREDS[0]} ?b . ?a {PREDS[1]} ?c")
        assert reg.counter("shard.scattered").value == 1
        assert reg.counter("shard.shard_requests").value == 1 + 4
        assert reg.histogram("shard.fanout").max == 4.0
    finally:
        sess.close()


# --------------------------------------------------------------------------
# manifest persistence + sharded ingestion
# --------------------------------------------------------------------------


def test_manifest_roundtrip_and_validation(tmp_path):
    store = rand_store(21, 30)
    path = str(tmp_path / "kg.shards.json")
    manifest = ingest_sharded(decoded_triples(store), path, 2)
    assert persist.is_manifest(path)
    m = persist.load_manifest(path)
    assert m["format"] == persist.MANIFEST_FORMAT and m["n_shards"] == 2
    assert m["partition"] == {"by": "subject", "hash": "crc32"}
    assert m["dictionary"]["n_triples"] == store.n_triples
    # shard term dictionaries overlap, so their sum bounds the union
    assert m["dictionary"]["n_terms_shards"] >= m["dictionary"]["n_terms_union"]
    for entry in m["shards"]:
        assert os.path.exists(entry["abs_path"])
        assert persist.open_store(entry["abs_path"]).n_triples == (
            entry["n_triples"]
        )
    assert sum(e["n_triples"] for e in m["shards"]) == store.n_triples
    assert manifest["n_shards"] == 2

    bad = dict(m, format="nonsense/9")
    with pytest.raises(ValueError, match="format"):
        persist.save_manifest(str(tmp_path / "bad.json"), bad)
    mm = {k: v for k, v in m.items()}
    mm["n_shards"] = 3  # disagrees with the 2 shard entries
    p2 = str(tmp_path / "bad2.json")
    with open(p2, "w", encoding="utf-8") as f:
        json.dump(
            {**mm, "shards": [{"path": e["path"]} for e in m["shards"]]}, f
        )
    with pytest.raises(ValueError, match="n_shards"):
        persist.load_manifest(p2)
    p3 = str(tmp_path / "bad3.json")
    with open(p3, "w", encoding="utf-8") as f:
        json.dump({**mm, "n_shards": 2, "partition": {"by": "object"}}, f)
    with pytest.raises(ValueError, match="partition"):
        persist.load_manifest(p3)
    # the sniff rejects non-manifest files without raising
    assert not persist.is_manifest(str(tmp_path / "missing.json"))
    other = str(tmp_path / "plain.json")
    with open(other, "w", encoding="utf-8") as f:
        json.dump({"hello": 1}, f)
    assert not persist.is_manifest(other)


def test_store_placement_follows_overlay_and_compaction():
    """A placed store keeps its device through the live overlay and the
    compacted rebuild, and refuses a move once it holds device arrays."""
    import jax

    from repro.live.delta import LiveStore

    dev = jax.devices()[0]
    store = rand_store(3, 30).place(dev)
    assert store.device_cols("spo")[0].device == dev
    live = LiveStore(store)
    live.insert([("<http://ex/new>", PREDS[0], '"x"')])
    view = live.view()
    assert view.delta.device == dev and view.alive("spo").device == dev
    assert live.compact().device == dev
    with pytest.raises(ValueError, match="before its first query"):
        store.place(None)


# --------------------------------------------------------------------------
# api.connect over a manifest (queries + routed mutations)
# --------------------------------------------------------------------------


def test_connect_manifest_parity_and_mutations(tmp_path):
    store = rand_store(17, 50)
    path = str(tmp_path / "kg.shards.json")
    shard_store(store, path, 2)
    single = LocalSession(store)
    with api.connect(path) as sess:
        assert isinstance(sess, ShardSession)
        for tpl in TEMPLATES:
            q = tpl(PREDS, SUBS[0])
            a, b = single.query(q), sess.query(q)
            assert (a.rows, a.n_total) == (b.rows, b.n_total), q
        # inserts route by subject hash: one triple -> one shard
        r = sess.insert([("<http://ex/new>", PREDS[0], '"fresh"')])
        assert r["inserted"] == 1 and r["shards_touched"] == 1
        got = sess.query(f"SELECT ?o WHERE {{ <http://ex/new> {PREDS[0]} ?o }}")
        assert got.rows == [('"fresh"',)]
        d = sess.delete([("<http://ex/new>", PREDS[0], '"fresh"')])
        assert d["deleted"] == 1 and d["shards_touched"] == 1
        # compact broadcasts to every shard
        c = sess.compact()
        assert c["compacted"] and c["shards_touched"] == 2
        with pytest.raises(api.QueryParseError):
            sess.query("SELECT nonsense {")


def test_connect_manifest_read_only(tmp_path):
    store = rand_store(19, 20)
    path = str(tmp_path / "ro.shards.json")
    shard_store(store, path, 2)
    with api.connect(path, read_only=True) as sess:
        assert sess.query(f"?a {PREDS[0]} ?b").n_total >= 0
        with pytest.raises(api.ReadOnlyError):
            sess.insert([("<http://ex/x>", PREDS[0], '"v"')])


# --------------------------------------------------------------------------
# the coordinator server (wire protocol over a shard group)
# --------------------------------------------------------------------------


def test_coordinator_server_end_to_end(tmp_path):
    from repro.serve.client import connect
    from repro.shard.coordinator import Coordinator

    store = rand_store(23, 60)
    path = str(tmp_path / "kg.shards.json")
    shard_store(store, path, 2)
    reg = MetricsRegistry()
    coord = Coordinator.from_manifest(
        path, port=0, wire_shards=False, registry=reg, log=False,
        linger_ms=1.0,
    ).start()
    single = LocalSession(store)
    try:
        with connect("127.0.0.1", coord.port, retry_s=5.0) as c:
            for tpl in TEMPLATES:
                qt = tpl(PREDS, SUBS[0])
                want = single.query(qt)
                r = c.query(qt)
                assert [tuple(x) for x in r["rows"]] == want.rows, qt
                assert r["n_total"] == want.n_total, qt
            routed0 = reg.counter("shard.routed").value
            reqs0 = reg.counter("shard.shard_requests").value
            c.query(f"SELECT ?o WHERE {{ {SUBS[1]} {PREDS[0]} ?o }}")
            assert reg.counter("shard.routed").value == routed0 + 1
            assert reg.counter("shard.shard_requests").value == reqs0 + 1
            # mutations apply through the coordinator barrier
            r = c.insert([["<http://ex/wire>", PREDS[0], '"w"']])
            assert r["inserted"] == 1 and r["shards_touched"] == 1
            got = c.query(f"SELECT ?o WHERE {{ <http://ex/wire> {PREDS[0]} ?o }}")
            assert [tuple(x) for x in got["rows"]] == [('"w"',)]
            # the metrics op reports group counters and signature examples
            met = c.metrics()
            assert met["metrics"]["counters"]["shard.scattered"] >= 1
            assert met["metrics"]["gauges"]["shard.n_shards"] == 2
    finally:
        coord.stop()


def test_coordinator_wire_shards_spawns_real_servers(tmp_path):
    from repro.serve.client import connect
    from repro.shard.coordinator import Coordinator

    store = rand_store(29, 30)
    path = str(tmp_path / "kg.shards.json")
    shard_store(store, path, 2)
    coord = Coordinator.from_manifest(
        path, port=0, wire_shards=True, registry=MetricsRegistry(),
        log=False, linger_ms=1.0,
    ).start()
    single = LocalSession(store)
    try:
        assert len(coord._servers) == 2
        with connect("127.0.0.1", coord.port, retry_s=5.0) as c:
            qt = f"SELECT * WHERE {{ ?a {PREDS[0]} ?b }}"
            want = single.query(qt)
            r = c.query(qt)
            assert [tuple(x) for x in r["rows"]] == want.rows
            assert r["n_total"] == want.n_total
    finally:
        coord.stop()


# --------------------------------------------------------------------------
# satellite regressions
# --------------------------------------------------------------------------


def test_open_store_cache_lru_cap(tmp_path):
    _, cap0 = persist.open_store_cache_info()
    try:
        persist.set_open_store_cache_size(2)
        paths = []
        for i in range(4):
            p = str(tmp_path / f"s{i}.kgz")
            persist.save(rand_store(i, 5 + i), p)
            paths.append(p)
        for p in paths:
            persist.open_store(p)
            size, cap = persist.open_store_cache_info()
            assert size <= cap == 2
        # most-recent stays resident: reopening it is the cached object
        again = persist.open_store(paths[-1])
        assert again is persist.open_store(paths[-1])
        with pytest.raises(ValueError):
            persist.set_open_store_cache_size(0)
    finally:
        persist.set_open_store_cache_size(cap0)


def test_sig_legend_capped():
    from repro.serve.server import MAX_TRACKED_SIGS, track_sig

    examples: dict = {}
    for i in range(MAX_TRACKED_SIGS):
        assert track_sig(examples, f"sig{i}", f"q{i}") == f"sig{i}"
    assert len(examples) == MAX_TRACKED_SIGS
    # the legend is full: new signatures collapse into one overflow label
    assert track_sig(examples, "sig-new", "q-new") == "overflow"
    assert len(examples) == MAX_TRACKED_SIGS
    assert "sig-new" not in examples
    # known labels keep reporting under their own name
    assert track_sig(examples, "sig0", "q0-again") == "sig0"
