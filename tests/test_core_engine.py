"""Core engine: hash set, PJTT, the engine's jitted steps against the
naive reference's, the shared predicate runner, and the paper's
operation-count (φ) model."""

import collections

import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # test image without hypothesis: seeded-example fallback
    from _hypothesis_shim import given, settings, st

from repro import obs
from repro.core import executor, hashing, hashset, pjtt
from repro.core.executor import create_kg
from repro.data.encoder import NULL_ID
from repro.rml import generator


def _keys(vals):
    return hashing.mix64([jnp.asarray(np.asarray(vals, np.int32))])


# ----------------------------------------------------------------- hash set


@pytest.mark.parametrize("n,n_distinct,batches", [(100, 10, 1), (5000, 500, 5), (333, 7, 3)])
def test_hashset_first_wins_semantics(n, n_distinct, batches):
    rng = np.random.default_rng(n)
    vals = rng.integers(0, n_distinct, size=n).astype(np.int32)
    hi, lo = _keys(vals)
    hi, lo = np.asarray(hi), np.asarray(lo)
    seen, expected = set(), []
    for h, l in zip(hi.tolist(), lo.tolist()):
        expected.append((h, l) not in seen)
        seen.add((h, l))
    table = hashset.make(4 * n)
    got = []
    split = np.array_split(np.arange(n), batches)
    for part in split:
        res = hashset.insert(table, jnp.asarray(hi[part]), jnp.asarray(lo[part]))
        table = res.table
        assert not bool(res.overflowed)
        got.extend(np.asarray(res.is_new).tolist())
    assert got == expected
    assert int(hashset.count(table)) == len(seen)


def test_hashset_overflow_reported():
    table = hashset.make(2)  # capacity 2
    hi, lo = _keys(np.arange(10))
    res = hashset.insert(table, hi, lo)
    assert bool(res.overflowed)


def test_mix64_structured_triple_keys_collision_free():
    """Regression: the final cross-lane mix must be a bijection on the
    64-bit state.  The old parallel shifted-xor had a 2^31-element kernel
    (~33 effective key bits), which produced real collisions — silently
    dropped triples — on COSMIC-style id grids at 100K rows."""
    n = 1 << 21
    ids = jnp.arange(n, dtype=jnp.int32)
    hi, lo = hashing.triple_key(
        jnp.int32(7), ids, jnp.int32(9), jnp.int32(11), ids + jnp.int32(1000003)
    )
    key = (np.asarray(hi).astype(np.uint64) << 32) | np.asarray(lo).astype(
        np.uint64
    )
    assert len(np.unique(key)) == n


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 400), k=st.integers(1, 50), seed=st.integers(0, 999))
def test_hashset_distinct_count_property(n, k, seed):
    vals = np.random.default_rng(seed).integers(0, k, n).astype(np.int32)
    hi, lo = _keys(vals)
    res = hashset.insert(hashset.make(4 * n + 8), hi, lo)
    assert int(np.asarray(res.is_new).sum()) == len(set(vals.tolist()))


# --------------------------------------------------------------------- PJTT


@pytest.mark.parametrize("n,m,keys", [(50, 30, 5), (1000, 700, 40), (64, 64, 1)])
def test_pjtt_matches_python_join(n, m, keys):
    rng = np.random.default_rng(n + m)
    pk = rng.integers(0, keys, n).astype(np.int32)
    ps = rng.integers(0, 10000, n).astype(np.int32)
    ck = rng.integers(0, keys + 2, m).astype(np.int32)
    K = int(np.bincount(pk, minlength=keys).max()) + 1

    idx = pjtt.build_sorted(jnp.asarray(pk), jnp.asarray(ps))
    pr = pjtt.probe_sorted(idx, jnp.asarray(ck), K)
    assert not bool(pr.truncated)

    from collections import defaultdict

    d = defaultdict(set)
    for k, s in zip(pk.tolist(), ps.tolist()):
        d[k].add(s)
    subs, valid = np.asarray(pr.subjects), np.asarray(pr.valid)
    for i, k in enumerate(ck.tolist()):
        assert set(subs[i][valid[i]].tolist()) == d.get(k, set()), i


def test_pjtt_set_semantics_masks_duplicate_pairs():
    # identical (key, subject) pairs collapse (paper: values are a SET)
    pk = jnp.asarray(np.array([1, 1, 1, 2], np.int32))
    ps = jnp.asarray(np.array([7, 7, 8, 9], np.int32))
    idx = pjtt.build_sorted(pk, ps)
    pr = pjtt.probe_sorted(idx, jnp.asarray(np.array([1], np.int32)), 4)
    got = np.asarray(pr.subjects)[0][np.asarray(pr.valid)[0]]
    assert sorted(got.tolist()) == [7, 8]


def test_pjtt_truncation_flag():
    pk = jnp.zeros(8, jnp.int32)
    ps = jnp.arange(8, dtype=jnp.int32)
    idx = pjtt.build_sorted(pk, ps)
    pr = pjtt.probe_sorted(idx, jnp.zeros(1, jnp.int32), 4)
    assert bool(pr.truncated)


def _probe(pk, ps, ck, K):
    pk, ps, ck = (jnp.asarray(np.asarray(a, np.int32)) for a in (pk, ps, ck))
    return pjtt.probe_sorted(pjtt.build_sorted(pk, ps), ck, K)


def _distinct_subjects(pk, ps):
    d = collections.defaultdict(set)
    for k, s in zip(pk, ps):
        d[k].add(s)
    return d


@pytest.mark.parametrize("n,keys,subjects", [(200, 5, 4), (1000, 30, 3), (333, 1, 7)])
def test_pjtt_span_holds_distinct_pairs(n, keys, subjects):
    """Parent rows that repeat (key, subject) pairs: a probe's span holds
    each key's distinct subjects once, ascending and packed at the front,
    so K = the largest distinct count probes without truncation."""
    rng = np.random.default_rng(n + keys)
    pk = rng.integers(0, keys, n).astype(np.int32)
    ps = rng.integers(0, subjects, n).astype(np.int32)
    ck = np.arange(keys + 2, dtype=np.int32)
    d = _distinct_subjects(pk, ps)
    K = max(len(v) for v in d.values())
    assert K < np.bincount(pk).max()  # the raw spans are wider
    pr = _probe(pk, ps, ck, K)
    assert not bool(pr.truncated)
    subs, valid = np.asarray(pr.subjects), np.asarray(pr.valid)
    for i, k in enumerate(ck.tolist()):
        want = sorted(d.get(k, ()))
        assert valid[i].sum() == len(want)
        assert valid[i][: len(want)].all()
        assert subs[i][: len(want)].tolist() == want
    assert bool(_probe(pk, ps, ck, K - 1).truncated)


def test_pjtt_raw_span_beyond_K_distinct_within():
    """Key 1 has six parent rows but three distinct subjects: K = 3 holds
    it, and the key after it starts right behind its distinct span."""
    pk = [1, 1, 1, 1, 1, 1, 2, 2, 2]
    ps = [7, 9, 7, 8, 9, 7, 5, 5, 5]
    pr = _probe(pk, ps, [1, 2, 3], 3)
    assert not bool(pr.truncated)
    subs, valid = np.asarray(pr.subjects), np.asarray(pr.valid)
    assert subs[0].tolist() == [7, 8, 9] and valid[0].all()
    assert subs[1][0] == 5 and valid[1].tolist() == [True, False, False]
    assert not valid[2].any()
    assert bool(_probe(pk, ps, [1, 2, 3], 2).truncated)


# ------------------------------------------ the ragged lanes of the OJM step


def _ragged(idx, ck, live):
    """A batch's ragged lanes, laid out from the host copy of the keys."""
    return pjtt.ragged_lanes(np.asarray(idx.skeys), ck, live)


# parent rows, keys, subjects a key draws from, child rows, NULL child
# keys' share, padding rows at the end; the last two cases have no
# candidate at all (every key missing, every row padding)
RAGGED_CASES = {
    "repeated-pairs": (600, 40, 4, 300, 0.1, 37),
    "fan-out-1": (500, 500, 1, 256, 0.0, 0),
    "wide-spans": (900, 3, 60, 128, 0.2, 20),
    "all-missing": (100, 10, 3, 64, 0.0, 0),
    "all-padding": (100, 10, 3, 64, 0.0, 64),
}


@pytest.mark.parametrize("case", sorted(RAGGED_CASES))
def test_ragged_lanes_are_the_padded_probes_valid_lanes(case):
    """On a PJTT with masked duplicate pairs and NULL parent keys, a batch
    with empty spans, NULL child keys and padding rows: the ragged lanes
    below the candidate count are the padded probe's valid ``(row,
    subject)`` lanes of the live rows, in the same row-major order, and
    the lanes are ``lane_bucket`` of that count."""
    n, keys, subjects, m, null_share, pad = RAGGED_CASES[case]
    rng = np.random.default_rng(n + m)
    pk = rng.integers(0, keys, n).astype(np.int32)
    ps = rng.integers(0, subjects, n).astype(np.int32) + 10 * pk
    pk[rng.random(n) < 0.05] = NULL_ID
    ck = rng.integers(0, keys + 5, m).astype(np.int32)
    if case == "all-missing":
        ck += keys
    ck[rng.random(m) < null_share] = NULL_ID
    live = ck != NULL_ID
    live[m - pad:] = False
    idx = pjtt.build_sorted(jnp.asarray(pk), jnp.asarray(ps))

    rows, pos, n_cand = _ragged(idx, ck, live)
    assert len(rows) == len(pos) == pjtt.lane_bucket(n_cand)
    ssubj = np.asarray(idx.ssubj)
    got = list(zip(rows[:n_cand].tolist(), ssubj[pos[:n_cand]].tolist()))

    counts = pjtt.spans(np.asarray(idx.skeys), ck)[1]
    pr = pjtt.probe_sorted(idx, jnp.asarray(ck), max(int(counts.max()), 1))
    valid = np.asarray(pr.valid) & live[:, None]
    r, k = np.nonzero(valid)
    want = list(zip(r.tolist(), np.asarray(pr.subjects)[r, k].tolist()))
    assert got == want
    assert (n_cand == 0) == case.startswith("all-")


def test_lane_bucket_pads_under_a_quarter():
    """The lanes hold every candidate; padding is under a quarter of the
    candidates, or under one 1024-lane block; and the counts of one
    doubling share at most four lane shapes."""
    assert pjtt.lane_bucket(0) == 0
    counts = list(range(1, 1 << 15)) + [(1 << 20) + 1, 3 << 19, (1 << 21) - 1]
    for t in counts:
        lanes = pjtt.lane_bucket(t)
        assert lanes >= t and lanes % 1024 == 0
        assert lanes - t < max(t / 4, 1024)
    for k in range(21):
        doubling = range((1 << k) + 1, (2 << k) + 1, 1 + (k > 15) * 97)
        assert len({pjtt.lane_bucket(t) for t in doubling}) <= 4


def test_engines_agree_when_parents_repeat_pairs():
    """Parent rows repeat, so the raw widest span is wider than the
    distinct one.  The eager and streamed engines (each join's distinct
    candidates, laid out exactly) and the naive engine (its nested loop
    as wide as the widest raw span) give the same KG, and the naive
    engine reports no truncated span."""
    from repro.rml.plan import build_plan

    tb = generator.make_testbed("OJM", 800, 0.75, n_poms=2, seed=3)
    tables = {"csv:child.csv": tb.child, "csv:parent.csv": tb.parent}
    plan = build_plan(tb.doc).exec_plan
    for op in (op for op in plan.ops if op.kind == "OJM"):
        psrc, (pcol,), _ppat, pcols = plan.pjtt_builds[op.pjtt_key]
        keys = tables[psrc][pcol]
        d = _distinct_subjects(keys, zip(*(tables[psrc][c] for c in pcols)))
        raw = collections.Counter(keys)
        ck = set(tables[op.source_key][op.join_child_columns[0]])
        assert max(raw[k] for k in ck) > max(len(d[k]) for k in ck)
    runs = {
        "eager": dict(),
        "stream": dict(stream=True, block_rows=300),
        "naive": dict(engine="naive"),
    }
    kgs = {k: create_kg(tb.doc, tables=tables, **cfg) for k, cfg in runs.items()}
    assert kgs["eager"].as_set() == kgs["naive"].as_set()
    want = kgs["eager"].sorted_ntriples()
    assert len(want) > 0
    assert all(kg.sorted_ntriples() == want for kg in kgs.values())


# ------------------------------------------- jitted steps against the naive's

# a rule's term ids: subject pattern, predicate, object pattern
SPAT, PID, OPAT = np.int32(1), np.int32(2), np.int32(3)


def _naive_unique(subj, obj, valid):
    """The naive engine's count of distinct triples: every candidate key,
    then one sort-dedup."""
    khi, klo = executor._naive_keys_step(
        SPAT, jnp.asarray(subj), PID, OPAT, jnp.asarray(obj)
    )
    return int(executor._naive_dedup(khi, klo, jnp.asarray(valid)).n_unique)


def test_som_vs_naive_identical_triples():
    rng = np.random.default_rng(0)
    subj = rng.integers(0, 50, 500).astype(np.int32)
    obj = rng.integers(0, 20, 500).astype(np.int32)

    table = hashset.make(1024)
    _hi, _lo, is_new, ovf = executor._dedup_step(
        table.hi, table.lo, SPAT, jnp.asarray(subj), PID, OPAT, jnp.asarray(obj),
        jnp.ones(500, bool),
    )
    assert not bool(ovf)
    n_opt = int(np.asarray(is_new).sum())

    assert n_opt == _naive_unique(subj, obj, np.ones(500, bool))
    assert n_opt == len({(s, o) for s, o in zip(subj.tolist(), obj.tolist())})


def test_ojm_index_join_vs_nested_loop():
    rng = np.random.default_rng(1)
    pk = rng.integers(0, 20, 200).astype(np.int32)
    psub = rng.integers(0, 500, 200).astype(np.int32)
    ck = rng.integers(0, 22, 150).astype(np.int32)
    csub = rng.integers(0, 100, 150).astype(np.int32)
    K = int(np.bincount(pk).max()) + 1

    idx = executor._build_sorted(jnp.asarray(pk), jnp.asarray(psub))
    rows, pos, n_cand = _ragged(idx, ck, np.ones(150, bool))
    table = hashset.make(hashset.next_pow2(200 * K))
    _hi, _lo, is_new, _psubj, ovf = executor._ojm_sorted_step(
        table.hi, table.lo, idx.ssubj, SPAT, jnp.asarray(csub), PID, OPAT,
        jnp.asarray(rows), jnp.asarray(pos), np.int32(n_cand),
    )
    assert not bool(ovf)
    n_opt = int(np.asarray(is_new).sum())

    jr = executor._naive_join_step(jnp.asarray(pk), jnp.asarray(psub), K, jnp.asarray(ck))
    assert not bool(jr.truncated)
    subj = np.broadcast_to(csub[:, None], (150, K)).reshape(-1)
    obj = np.asarray(jr.subjects).reshape(-1)
    assert n_opt == _naive_unique(subj, obj, np.asarray(jr.valid).reshape(-1))

    # python oracle
    pairs = set()
    d = _distinct_subjects(pk.tolist(), psub.tolist())
    for k, s in zip(ck.tolist(), csub.tolist()):
        for ps_ in d.get(k, ()):
            pairs.add((s, ps_))
    assert n_opt == len(pairs)


def _padded_step(hi, lo, idx, subj, ck, live, K):
    """The OJM step on the padded ``(rows, K)`` probe block: every lane
    hashed, the invalid ones masked."""
    pr = pjtt.probe_sorted(idx, jnp.asarray(ck), K)
    v = (np.asarray(pr.valid) & live[:, None]).reshape(-1)
    khi, klo = hashing.triple_key(
        SPAT, jnp.asarray(np.repeat(subj, K)), PID, OPAT, pr.subjects.reshape(-1)
    )
    res = hashset.insert_masked(hashset.HashSet(hi, lo), khi, klo, jnp.asarray(v))
    r, k = np.nonzero(np.asarray(res.is_new).reshape(-1, K))
    emitted = list(zip(r.tolist(), np.asarray(pr.subjects)[r, k].tolist()))
    return res.table.hi, res.table.lo, emitted


@pytest.mark.parametrize("slots", [1 << 11, 1 << 14])
def test_ojm_step_emits_what_the_padded_step_emits(slots):
    """Batch after batch into one PTT, small enough that keys contend for
    slots: the ragged step marks new the same ``(row, subject)`` pairs as
    the padded step, in the same order, and leaves the same table."""
    rng = np.random.default_rng(slots)
    pk = rng.integers(0, 30, 400).astype(np.int32)
    ps = rng.integers(0, 6, 400).astype(np.int32) + 10 * pk
    idx = executor._build_sorted(jnp.asarray(pk), jnp.asarray(ps))
    K = max(int(pjtt.spans(np.asarray(idx.skeys), np.arange(30))[1].max()), 1)
    ragged, padded = hashset.make(slots), hashset.make(slots)
    rhi, rlo, phi, plo = ragged.hi, ragged.lo, padded.hi, padded.lo
    for _batch in range(3):
        ck = rng.integers(0, 33, 64).astype(np.int32)
        subj = rng.integers(0, 40, 64).astype(np.int32)
        live = rng.random(64) < 0.9
        rows, pos, n_cand = _ragged(idx, ck, live)
        rhi, rlo, is_new, psubj, ovf = executor._ojm_sorted_step(
            rhi, rlo, idx.ssubj, SPAT, jnp.asarray(subj), PID, OPAT,
            jnp.asarray(rows), jnp.asarray(pos), np.int32(n_cand),
        )
        assert not bool(ovf)
        lanes = np.nonzero(np.asarray(is_new))[0]
        got = list(zip(rows[lanes].tolist(), np.asarray(psubj)[lanes].tolist()))
        phi, plo, want = _padded_step(phi, plo, idx, subj, ck, live, K)
        assert got == want and len(got) > 0
    assert np.array_equal(np.asarray(rhi), np.asarray(phi))
    assert np.array_equal(np.asarray(rlo), np.asarray(plo))


# ------------------------------------------------- the shared predicate runner


def _testbed_run(kind, **cfg):
    tb = generator.make_testbed(kind, 1500, 0.75, n_poms=2, seed=11)
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    return create_kg(tb.doc, tables=tables, **cfg)


def _replays():
    return obs.get_registry().snapshot()["counters"].get("ingest.replays", 0)


@pytest.mark.parametrize("kind", ["SOM", "ORM", "OJM"])
def test_eager_and_streamed_engines_report_the_same_stats(kind):
    """Both inputs go through one predicate runner: every field of every
    predicate's stats agrees, and so does the KG."""
    eager = _testbed_run(kind)
    streamed = _testbed_run(kind, stream=True, block_rows=700)
    assert streamed.engine == "stream"
    assert eager.stats == streamed.stats
    assert any(st.n_candidates > st.n_unique > 0 for st in eager.stats.values())
    assert eager.sorted_ntriples() == streamed.sorted_ntriples()


@pytest.mark.parametrize("stream", [False, True], ids=["eager", "stream"])
def test_replay_keeps_the_stats(stream):
    """A PTT sized at 8 candidates a slot overflows: the runner replays the
    predicate with a doubled table, and what it reports and emits is what
    a run without a replay reports and emits."""
    cfg = dict(stream=stream, block_rows=700)
    want = _testbed_run("OJM", **cfg)
    before = _replays()
    got = _testbed_run("OJM", load_factor=8.0, **cfg)
    assert _replays() - before >= 1
    assert got.stats == want.stats
    assert got.sorted_ntriples() == want.sorted_ntriples()


# ------------------------------------------------------------------ φ model


def test_phi_model_matches_paper_formulas():
    from repro.core.executor import PredicateStats

    st_ = PredicateStats(kind="SOM", n_candidates=1000, n_unique=250)
    assert st_.phi_optimized() == 1000 + 2 * 250
    assert st_.phi_naive() == pytest.approx(1000 + 250 + 1000 * np.log2(1000))

    stj = PredicateStats(
        kind="OJM", n_candidates=4000, n_unique=1000, n_parent=500, n_child=600
    )
    assert stj.phi_optimized() == 2 * 500 + 600 + 4000 + 2 * 1000
    assert stj.phi_naive() == pytest.approx(
        500 * 600 + 4000 + 1000 + 4000 * np.log2(4000)
    )
    # the paper's claim: orders of magnitude fewer operations
    assert stj.phi_naive() / stj.phi_optimized() > 30
