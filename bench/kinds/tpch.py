"""TPC-H Direct-Mapping ingestion jobs back to back.

Set-up writes the warehouse (eight CSV tables and the RML mapping,
``tpch.py``) from the seed and runs one ``rdfize`` job over it, which
compiles or loads every program the jobs use.  That job's output must
hold a reference triple of the composite foreign key
``(l_partkey, l_suppkey)``, or the run stops there: a program that cannot
join on two ``rr:joinCondition``s cannot run this configuration.  The
window, the host spans of a profiled run and the readers of written
files are those of ``kinds/ingest.py``.

The check compares every written file with the reference KG of
``tpch.reference_lines``, built there and not at set-up, as a multiset
(``reference.compare``): triples missing, triples extra, copies beyond the
first, and jobs that failed or wrote a KG that differs, all with the limit
0.  Files with the same bytes are compared once.
"""

from __future__ import annotations

import hashlib
import os
import shutil

import harness
import reference
import tpch

_ingest = harness.load_module(os.path.join(harness.BENCH, "kinds", "ingest.py"),
                              "bench_kind_ingest")
window = _ingest.window
annotate = _ingest.annotate
release = _ingest.release
read_output = _ingest.read_output
State = _ingest.State

_COMPOSITE = f"<{tpch.reference_property('lineitem', ('l_partkey', 'l_suppkey'))}>".encode()


def setup(ctx) -> State:
    cfg = ctx.cell.config
    tb = tpch.make(cfg["testbed"]["scale_factor"], ctx.seed)
    shutil.rmtree(ctx.workdir, ignore_errors=True)
    data = os.path.join(ctx.workdir, "data")
    mapping = tb.write(data)
    argv = ["--mapping", mapping, "--data-root", data] + cfg["rdfize_args"]
    state = State(tb, argv, ctx.workdir, cfg["output"])
    warm = os.path.join(ctx.workdir, f"warm.{state.output}")
    _ingest._rdfize(state.argv + ["--out", warm])
    with open(warm, "rb") as f:
        joined = _COMPOSITE in f.read()
    os.remove(warm)
    if not joined:
        raise harness.BenchError("the program's KG holds no triple of the composite "
                                 "foreign key: it cannot run this configuration")
    return state


def _readings(diffs: "list[dict]", failed: int) -> "list[tuple[str, float, float]]":
    totals = {k: sum(d[k] for d in diffs) for k in ("missing", "extra", "duplicates")}
    return [
        ("bad_jobs", failed + sum(any(d.values()) for d in diffs), 0),
        ("missing_triples", totals["missing"], 0),
        ("extra_triples", totals["extra"], 0),
        ("duplicate_triples", totals["duplicates"], 0),
    ]


def check(state: State, win: dict) -> "list[tuple[str, float, float]]":
    want = tpch.reference_lines(state.tb)
    seen: dict = {}
    diffs = []
    for path in state.outs:
        with open(path, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        if digest not in seen:
            seen[digest] = reference.compare(read_output(state, path), want)
        diffs.append(seen[digest])
        os.remove(path)
    return _readings(diffs, win["failed"])


def control(state: State) -> "list[tuple[str, float, float]]":
    """The check's readings with the control in the program's place: the
    plain reference with every foreign key joined on its first column
    alone (the guarantee that a join is exact on all of its conditions,
    broken): each lineitem meets all four suppliers of its part."""
    got = list(tpch.reference_lines(state.tb, conditions=1))
    return _readings([reference.compare(got, tpch.reference_lines(state.tb))], 0)
