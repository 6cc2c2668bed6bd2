"""The GTFS-Madrid-Bench cell at a tiny size on the CPU, its reference and
control, the readers of its two layer metrics, and every engine against
the reference."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from benchsteer import RESULT_KEYS, run_cell, steer

import control
import gtfs
import harness

CELL = "gtfs-100k-ingest"


def test_cell_end_to_end(tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, CELL)
    assert rc == 0, err[-3000:]
    assert list(result) == RESULT_KEYS + ["checks"]
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"ingest_rows_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert all(c["value"] == 0 for c in result["checks"].values())


def test_traced_run_reports_the_host_side_metrics(tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, CELL, seed=31, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    # a CPU trace has no device plane: every metric the cell lists but the
    # three device ones
    assert set(result["metrics"]) == {
        "ingest.engine_s", "ingest.emit_s", "ingest.compiles", "ingest.read_s",
        "ingest.encode_s", "ingest.sync_s", "ingest.compact_s", "ingest.host_syncs",
        "ingest.replays", "ingest.join_step_s",
    }
    assert result["metrics"]["ingest.join_step_s"]["value"] > 0


def test_control_breaks_the_null_guarantee(tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    assert control.main(["--workload", CELL, "--seeds", "5", "--seconds", "0.5"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert all(v == 0 for v in line["program"].values())
    assert line["control"]["extra_triples"] > 0 and line["control"]["missing_triples"] == 0


def test_mapping_shape():
    """Twelve triples maps over ten tables, nine joins (one a self-join)
    and two referencing maps over the same table."""
    kinds = [kind for _m, _t, _s, _c, poms in gtfs.MAPS for _p, kind, *_ in poms]
    assert len(gtfs.MAPS) == 12 and len({m[1] for m in gtfs.MAPS}) == 10
    assert kinds.count("join") == 9 and kinds.count("orm") == 2
    assert ("Stop", "stops") in [(m[0], m[1]) for m in gtfs.MAPS]
    sizes = gtfs.sizes(100_000)
    assert sum(sizes.values()) == 117_726


def test_seed_renames_but_keeps_the_work():
    a, b = gtfs.make(1500, 1), gtfs.make(1500, 2**31 + 11)
    assert a.source_rows == b.source_rows
    assert a.tables["trips"]["trip_id"] != b.tables["trips"]["trip_id"]
    assert len(gtfs.reference_lines(a)) == len(gtfs.reference_lines(b))
    assert gtfs.null_terms(a) == gtfs.null_terms(b) > 0
    assert gtfs.make(1500, 1).tables == a.tables


ENGINES = [
    pytest.param(dict(engine=e, stream=s, mapping_plan=p), id=f"{n}-{'plan' if p else 'noplan'}")
    for n, e, s in (("eager", "optimized", False), ("stream", "optimized", True),
                    ("naive", "naive", False))
    for p in (True, False)
]


@pytest.mark.parametrize("cfg", ENGINES)
def test_engines_agree_with_the_reference(cfg, tmp_path):
    from repro import obs
    from repro.core.executor import create_kg
    from repro.rml import parser

    tb = gtfs.make(600, 7)
    doc = parser.parse_file(tb.write(str(tmp_path)))
    reg = obs.get_registry()
    before = reg.snapshot()["counters"].get("ingest.null_terms", 0)
    kg = create_kg(doc, str(tmp_path), block_rows=256, **cfg)
    nulls = reg.snapshot()["counters"]["ingest.null_terms"] - before
    got = [ln[:-2] for ln in kg.iter_ntriples()]
    assert len(got) == len(set(got))
    assert sorted(got) == list(gtfs.reference_lines(tb))
    assert nulls == gtfs.null_terms(tb)


def test_a_program_that_drops_datatypes_fails_at_setup(tmp_path, monkeypatch, capsys):
    """Set-up refuses a program whose KG holds no typed literal: one that
    writes typed literals as plain ones stops there, non-zero, with no
    result line."""
    from repro.core import planner

    untyped = planner.term_pattern

    def plain(term):
        pat = untyped(term)
        return "lit:" + pat.split(" ", 1)[1] if pat.startswith("typ:") else pat

    monkeypatch.setattr(planner, "term_pattern", plain)
    steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, CELL)
    assert rc == 2 and result is None
    assert "holds no typed literal" in err


def _reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "layer_metrics", f"{name}.py"),
                               "bench_layer_" + name.replace(".", "_"))


def _chrome(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": 1, "tid": 1, "args": args}


def test_join_step_reader_sums_ojm_steps_with_their_children():
    events = [_chrome("ingest.step", 0.0, 100.0, op="OJM"),
              _chrome("ingest.sync", 10.0, 50.0),
              _chrome("ingest.step", 200.0, 300.0, op="SOM"),
              _chrome("ingest.step", 600.0, 40.0, op="OJM")]
    read = _reader("ingest.join_step_s").read
    assert read(NS(spans=events, window={"jobs": 2})) == pytest.approx(140e-6 / 2)
    assert read(NS(spans=events[2:3], window={"jobs": 2})) is None


def test_pjtt_build_reader_sums_the_build_programs():
    programs = {"jit__build_sorted": 0.03, "jit__hash_index": 0.01, "jit__dedup_step": 2.0}
    read = _reader("ingest.pjtt_build_s").read
    assert read(NS(device=NS(programs=programs), window={"jobs": 4})) == pytest.approx(0.01)
    no_build = NS(device=NS(programs={"jit__dedup_step": 2.0}), window={"jobs": 4})
    assert read(no_build) is None
    assert read(NS(device=None, window={"jobs": 4})) is None
