"""The ingest engine's own instrumentation: the ``ingest.host_syncs``,
``ingest.replays``, ``ingest.join_lanes`` and ``ingest.join_candidates``
counters against counts worked out from the batches and the tables, and
the ``ingest.*`` spans nested properly on the engine's thread."""

import collections

import pytest

from repro import obs
from repro.core import pjtt
from repro.core.executor import create_kg
from repro.data import pipeline
from repro.rml import generator
from repro.rml.plan import build_plan

# blocking device-to-host reads per batch: an OJM batch with candidates
# reads is_new, the lanes' parent subjects and ovf (one without makes no
# device call); a dedup step reads is_new and ovf.  Both engines read
# each PJTT's sorted keys back once.
READS_PER_BATCH = {"OJM": 3}
COUNTERS = ("ingest.host_syncs", "ingest.replays", "ingest.join_lanes",
            "ingest.join_candidates")


def _tables(tb):
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    return tables


def _batches(n, batch_size, stream, block_rows):
    """The ``(start, end)`` source rows of an ``n``-row op's device
    batches."""
    if stream:
        rows = min(batch_size, block_rows)
        return [(a, min(a + rows, b0 + block_rows, n))
                for b0 in range(0, n, block_rows)
                for a in range(b0, min(b0 + block_rows, n), rows)]
    bs = min(batch_size, pipeline.pick_batch_size(n))
    return [(a, min(a + bs, n)) for a in range(0, n, bs)]


def _candidates(plan, op, tables):
    """Each child row's candidates: the distinct parent subjects of its
    join key."""
    psrc, pjcols, _ppat, pcols = plan.pjtt_builds[op.pjtt_key]
    subjects = collections.defaultdict(set)
    pkeys = zip(*(tables[psrc][c] for c in pjcols))
    for key, *subj in zip(pkeys, *(tables[psrc][c] for c in pcols)):
        subjects[key].add(tuple(subj))
    ckeys = zip(*(tables[op.source_key][c] for c in op.join_child_columns))
    return [len(subjects[k]) for k in ckeys]


def _op_batches(doc, tables, batch_size, stream, block_rows):
    """Each op and its device batches' candidate counts (the rows of a
    batch for an op that joins nothing)."""
    plan = build_plan(doc).exec_plan
    for op in plan.ops:
        if op.kind == "OJM":
            counts = _candidates(plan, op, tables)
        else:
            counts = [1] * len(next(iter(tables[op.source_key].values())))
        yield op, [sum(counts[a:b]) for a, b in
                   _batches(len(counts), batch_size, stream, block_rows)]


def expected_host_syncs(doc, tables, batch_size, stream=False, block_rows=None):
    total = len(build_plan(doc).exec_plan.pjtt_builds)
    for op, steps in _op_batches(doc, tables, batch_size, stream, block_rows):
        total += READS_PER_BATCH.get(op.kind, 2) * sum(1 for t in steps if t)
    return total


def expected_join_lanes(doc, tables, batch_size, stream=False, block_rows=None):
    """The lanes the OJM steps hash and the candidates among them: each
    batch's candidates, the child rows' distinct parent subjects, rounded
    up by ``pjtt.lane_bucket``."""
    lanes = candidates = 0
    for op, steps in _op_batches(doc, tables, batch_size, stream, block_rows):
        if op.kind == "OJM":
            lanes += sum(pjtt.lane_bucket(t) for t in steps)
            candidates += sum(steps)
    return lanes, candidates


def _counted(doc, tables, **config):
    reg = obs.get_registry()
    before = reg.snapshot()["counters"]
    result = create_kg(doc, tables=tables, **config)
    after = reg.snapshot()["counters"]
    delta = {k: after[k] - before.get(k, 0) for k in COUNTERS}
    return result, delta


ENGINES = {
    "eager": dict(batch_size=512),
    "stream": dict(batch_size=256, stream=True, block_rows=700),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("kind", ["OJM", "SOM"])
def test_host_syncs_count_the_batches(kind, engine):
    tb = generator.make_testbed(kind, 1500, 0.75, n_poms=2, seed=5)
    tables = _tables(tb)
    config = ENGINES[engine]
    _result, delta = _counted(tb.doc, tables, **config)
    want = expected_host_syncs(tb.doc, tables, config["batch_size"],
                               stream=config.get("stream", False),
                               block_rows=config.get("block_rows"))
    assert delta["ingest.host_syncs"] == want
    assert delta["ingest.replays"] == 0


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("kind", ["OJM", "SOM"])
def test_join_lanes_count_the_distinct_spans(kind, engine):
    """An OJM step hashes its batch's candidates, rounded up by
    ``pjtt.lane_bucket``: a child row gives one lane for each distinct
    parent subject of its key, so the parent's repeated rows add none.  A
    SOM job joins nothing."""
    tb = generator.make_testbed(kind, 1500, 0.75, n_poms=2, seed=5)
    tables = _tables(tb)
    config = ENGINES[engine]
    result, delta = _counted(tb.doc, tables, **config)
    lanes, candidates = expected_join_lanes(
        tb.doc, tables, config["batch_size"], stream=config.get("stream", False),
        block_rows=config.get("block_rows"))
    assert (delta["ingest.join_lanes"], delta["ingest.join_candidates"]) == (lanes, candidates)
    joined = sum(st.n_candidates for st in result.stats.values() if st.kind == "OJM")
    assert delta["ingest.join_candidates"] == joined
    assert (lanes > 0) == (kind == "OJM")


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_overflow_replays_and_keeps_the_output(engine):
    """A load factor of 8 sizes each PTT at an eighth of its candidates:
    every predicate overflows and is replayed, and the KG is the same."""
    tb = generator.make_testbed("OJM", 1500, 0.75, n_poms=2, seed=5)
    tables = _tables(tb)
    base, delta = _counted(tb.doc, tables, **ENGINES[engine])
    assert delta["ingest.replays"] == 0
    packed, delta = _counted(tb.doc, tables, load_factor=8.0, **ENGINES[engine])
    assert delta["ingest.replays"] >= 1
    assert packed.sorted_ntriples() == base.sorted_ntriples()


@pytest.fixture
def tracing():
    tracer = obs.get_tracer()
    was = tracer.enabled
    tracer.enable()
    tracer.clear()
    yield tracer
    tracer.clear()
    if not was:
        tracer.disable()


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_spans_nest_on_the_engine_thread(engine, tracing, tmp_path):
    """Every ``ingest.*`` span of a run lies inside or outside each other
    one (none is left open across a block stream's ``yield``), on one
    thread, with each ``ingest.sync`` and ``ingest.compact`` inside an
    ``ingest.step``."""
    tb = generator.make_testbed("OJM", 1500, 0.75, n_poms=2, seed=5)
    tb.write(str(tmp_path))
    create_kg(tb.doc, data_root=str(tmp_path), **ENGINES[engine])
    evs = [e for e in tracing.export()["traceEvents"] if e["name"].startswith("ingest.")]
    names = collections.Counter(e["name"] for e in evs)
    assert {"ingest.read", "ingest.encode", "ingest.pjtt_build", "ingest.size",
            "ingest.step", "ingest.sync", "ingest.compact", "ingest.assemble"} <= set(names)
    assert names["ingest.sync"] == names["ingest.compact"] == names["ingest.step"]
    assert len({e["tid"] for e in evs}) == 1
    ivs = [(e["ts"], e["ts"] + e["dur"], e["name"]) for e in evs]
    eps = 1e-3
    for a0, a1, _a in ivs:
        for b0, b1, _b in ivs:
            disjoint = a1 <= b0 + eps or b1 <= a0 + eps
            nested = (a0 >= b0 - eps and a1 <= b1 + eps) or (b0 >= a0 - eps and b1 <= a1 + eps)
            assert disjoint or nested
    steps = [(a0, a1) for a0, a1, n in ivs if n == "ingest.step"]
    for a0, a1, n in ivs:
        if n in ("ingest.sync", "ingest.compact"):
            assert any(s0 - eps <= a0 and a1 <= s1 + eps for s0, s1 in steps)
    step = next(e for e in evs if e["name"] == "ingest.step")
    assert set(step["args"]) == {"op", "rows"}
