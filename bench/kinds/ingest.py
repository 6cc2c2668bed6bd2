"""Ingestion jobs back to back: the paper's workload.

Set-up writes the configuration's testbed (CSV sources and the RML
mapping) from the seed and runs one ``rdfize`` job over it, which
compiles or loads every program the jobs use.  The window then runs
``repro.launch.rdfize.main`` job after job with the configuration's
arguments, each into a file of its own; a job still running when the
window's time is up runs to its end and counts, so no time is dropped.
``ingest_rows_per_s`` is the source rows (child and parent) of the jobs
that succeeded over the whole time the jobs took.

The check reads every written KG back with the benchmark's own readers
and compares it with the reference KG built from the same tables:
triples missing, triples extra, copies of a triple beyond the first, and
jobs that failed or wrote a KG that differs, all with the limit 0.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import time
import traceback

import reference
import testbed


@dataclasses.dataclass
class State:
    tb: testbed.Testbed
    argv: list
    workdir: str
    output: str
    outs: list = dataclasses.field(default_factory=list)


def _rdfize(argv: list) -> None:
    from repro.launch import rdfize

    # the CLI's report goes to standard error: standard output ends with
    # the result line alone
    with contextlib.redirect_stdout(sys.stderr):
        rdfize.main(argv)


def setup(ctx) -> State:
    cfg = ctx.cell.config
    spec = cfg["testbed"]
    tb = testbed.make(spec["kind"], spec["rows"], spec["dup_rate"], spec["n_poms"], ctx.seed)
    shutil.rmtree(ctx.workdir, ignore_errors=True)
    data = os.path.join(ctx.workdir, "data")
    mapping = tb.write(data)
    argv = ["--mapping", mapping, "--data-root", data] + cfg["rdfize_args"]
    state = State(tb, argv, ctx.workdir, cfg["output"])
    warm = os.path.join(ctx.workdir, f"warm.{state.output}")
    _rdfize(state.argv + ["--out", warm])
    os.remove(warm)
    return state


def window(state: State, seconds: float) -> dict:
    import jax

    failed = 0
    t0 = time.perf_counter()
    while True:
        out = os.path.join(state.workdir, f"job{len(state.outs) + failed}.{state.output}")
        with jax.profiler.TraceAnnotation("bench.job"):
            try:
                _rdfize(state.argv + ["--out", out])
                state.outs.append(out)
            except Exception:  # noqa: BLE001 — a failed job counts, the rest go on
                traceback.print_exc()
                failed += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    jobs = len(state.outs)
    return {
        "attempted": jobs + failed,
        "failed": failed,
        "end_to_end": {"ingest_rows_per_s": jobs * state.tb.source_rows / elapsed},
        "jobs": jobs,
        "elapsed_s": elapsed,
    }


@contextlib.contextmanager
def annotate(state: State):
    """Host spans, for the profiled run, around the engine, the store
    build and the write the jobs call."""
    import jax

    from repro.core import executor
    from repro.kg import persist

    def wrap(fn, label):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **kw)
        return wrapped

    saved = [
        (executor, "create_kg", "bench.create_kg"),
        (executor.KGResult, "to_store", "bench.store_build"),
        (executor.KGResult, "write_ntriples", "bench.write_nt"),
        (persist, "save", "bench.write_kgz"),
    ]
    originals = [(obj, attr, getattr(obj, attr)) for obj, attr, _l in saved]
    for obj, attr, label in saved:
        setattr(obj, attr, wrap(getattr(obj, attr), label))
    try:
        yield
    finally:
        for obj, attr, fn in originals:
            setattr(obj, attr, fn)


def release(state: State) -> None:
    """Nothing outlives a job."""


def read_output(state: State, path: str) -> "list[str]":
    return reference.read_kgz(path) if state.output == "kgz" else reference.read_nt(path)


def check(state: State, win: dict) -> "list[tuple[str, float, float]]":
    want = reference.reference_kg(state.tb).lines()
    totals = {"missing": 0, "extra": 0, "duplicates": 0}
    bad = win["failed"]
    for path in state.outs:
        diff = reference.compare(read_output(state, path), want)
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
    plain reference without its set semantics, so that every duplicate
    source row gives its triples again (the guarantee that the KG is a
    set, broken)."""
    want = reference.reference_kg(state.tb).lines()
    diff = reference.compare(list(reference.reference_kg(state.tb, distinct=False).lines()), want)
    return [
        ("bad_jobs", int(any(diff.values())), 0),
        ("missing_triples", diff["missing"], 0),
        ("extra_triples", diff["extra"], 0),
        ("duplicate_triples", diff["duplicates"], 0),
    ]
