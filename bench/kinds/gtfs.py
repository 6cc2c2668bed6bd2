"""GTFS-Madrid-Bench ingestion jobs back to back.

Set-up writes the feed (ten CSV tables and the RML mapping, ``gtfs.py``)
from the seed and runs one ``rdfize`` job over it, which compiles or loads
every program the jobs use.  That job's output must hold a typed literal,
or the run stops there: a program that cannot write ``rr:datatype`` cannot
run this configuration.  The window, the host spans of a profiled run and
the readers of written files are those of ``kinds/ingest.py``.

The check compares every written file with the reference KG of
``gtfs.reference_lines``, built there and not at set-up, as a multiset
(``reference.compare``): triples missing, triples extra, copies beyond the
first, and jobs that failed or wrote a KG that differs, all with the limit
0.  Files with the same bytes are compared once.
"""

from __future__ import annotations

import hashlib
import os
import shutil

import gtfs
import harness
import reference

_ingest = harness.load_module(os.path.join(harness.BENCH, "kinds", "ingest.py"),
                              "bench_kind_ingest")
window = _ingest.window
annotate = _ingest.annotate
release = _ingest.release
read_output = _ingest.read_output
State = _ingest.State

_TYPED = b'"^^<http://www.w3.org/2001/XMLSchema#'


def setup(ctx) -> State:
    cfg = ctx.cell.config
    tb = gtfs.make(cfg["testbed"]["rows"], ctx.seed)
    shutil.rmtree(ctx.workdir, ignore_errors=True)
    data = os.path.join(ctx.workdir, "data")
    mapping = tb.write(data)
    argv = ["--mapping", mapping, "--data-root", data] + cfg["rdfize_args"]
    state = State(tb, argv, ctx.workdir, cfg["output"])
    warm = os.path.join(ctx.workdir, f"warm.{state.output}")
    _ingest._rdfize(state.argv + ["--out", warm])
    with open(warm, "rb") as f:
        typed = _TYPED in f.read()
    os.remove(warm)
    if not typed:
        raise harness.BenchError("the program's KG holds no typed literal: "
                                 "it cannot run this configuration")
    return state


def check(state: State, win: dict) -> "list[tuple[str, float, float]]":
    want = gtfs.reference_lines(state.tb)
    totals = {"missing": 0, "extra": 0, "duplicates": 0}
    bad = win["failed"]
    seen: dict = {}
    for path in state.outs:
        with open(path, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        if digest not in seen:
            seen[digest] = reference.compare(read_output(state, path), want)
        diff = seen[digest]
        bad += any(diff.values())
        for k, v in diff.items():
            totals[k] += v
        os.remove(path)
    return [
        ("bad_jobs", bad, 0),
        ("missing_triples", totals["missing"], 0),
        ("extra_triples", totals["extra"], 0),
        ("duplicate_triples", totals["duplicates"], 0),
    ]


def control(state: State) -> "list[tuple[str, float, float]]":
    """The check's readings with the control in the program's place: the
    plain reference with empty cells read as empty values, not NULL (the
    guarantee that a NULL cell gives no term, broken)."""
    got = list(gtfs.reference_lines(state.tb, empty_is_null=False))
    diff = reference.compare(got, gtfs.reference_lines(state.tb))
    return [
        ("bad_jobs", int(any(diff.values())), 0),
        ("missing_triples", diff["missing"], 0),
        ("extra_triples", diff["extra"], 0),
        ("duplicate_triples", diff["duplicates"], 0),
    ]
