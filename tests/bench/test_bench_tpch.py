"""The TPC-H Direct-Mapping cell at a tiny scale factor on the CPU, its
generator, reference and control, and the engines against the reference."""

import collections
import dataclasses
import json

import pytest

from benchsteer import RESULT_KEYS, run_cell, steer

import control
import harness
import tpch

CELL = "tpch-sf0.01-ingest"
SF = 0.0005  # 5 suppliers, 100 parts, 750 orders: 4,439 rows, 78,074 triples


def _steer(monkeypatch, tmp_path) -> None:
    """``benchsteer.steer``, and the cell's scale factor down to ``SF``."""
    steer(monkeypatch, tmp_path)
    find = harness.find_cell

    def tiny(name, root=harness.ROOT):
        cell = find(name, root)
        if name == CELL:
            cell.config["testbed"]["scale_factor"] = SF
        return cell

    monkeypatch.setattr(harness, "find_cell", tiny)


# -- the cell ------------------------------------------------------------------------


def test_cell_end_to_end(tmp_path, monkeypatch, capsys):
    _steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, CELL)
    assert rc == 0, err[-3000:]
    assert list(result) == RESULT_KEYS + ["checks"]
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"ingest_rows_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["checks"]) == {"bad_jobs", "missing_triples", "extra_triples",
                                     "duplicate_triples"}
    assert all(c["value"] == 0 == c["limit"] for c in result["checks"].values())


def test_traced_run_reports_the_join_key_seconds(tmp_path, monkeypatch, capsys):
    _steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, CELL, seed=2_147_483_777, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    # a CPU trace has no device plane: every metric the cell lists but the
    # device ones
    assert set(result["metrics"]) == {
        "ingest.engine_s", "ingest.emit_s", "ingest.compiles", "ingest.read_s",
        "ingest.encode_s", "ingest.sync_s", "ingest.compact_s", "ingest.host_syncs",
        "ingest.replays", "ingest.join_step_s", "ingest.join_key_s",
    }
    assert 0 < result["metrics"]["ingest.join_key_s"]["value"] < \
        result["metrics"]["ingest.engine_s"]["value"]


def test_control_joins_on_the_first_condition_alone(tmp_path, monkeypatch, capsys):
    """The control breaks exactness: each lineitem meets its part's four
    suppliers, three extra reference triples a lineitem."""
    _steer(monkeypatch, tmp_path)
    assert control.main(["--workload", CELL, "--seeds", "5", "--seconds", "0.5"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert all(v == 0 for v in line["program"].values())
    lineitems = len(tpch.make(SF, 5).tables["lineitem"]["l_orderkey"])
    assert line["control"]["extra_triples"] == 3 * lineitems
    assert line["control"]["missing_triples"] == 0 and line["control"]["bad_jobs"] == 1


def test_a_program_that_drops_a_join_condition_is_not_correct(tmp_path, monkeypatch, capsys):
    """A program that joins on the first rr:joinCondition alone runs, and
    its extra triples make the run incorrect."""
    from repro.rml import parser
    from repro.rml.model import RefObjectMap

    parse = parser.parse

    def first_condition_only(text):
        doc = parse(text)
        for name, tm in doc.triples_maps.items():
            poms = tuple(
                dataclasses.replace(pom, object_map=dataclasses.replace(
                    pom.object_map, joins=pom.object_map.joins[:1]))
                if isinstance(pom.object_map, RefObjectMap) else pom
                for pom in tm.poms)
            doc.triples_maps[name] = dataclasses.replace(tm, poms=poms)
        return doc

    monkeypatch.setattr(parser, "parse", first_condition_only)
    _steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, CELL)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    assert result["checks"]["extra_triples"]["value"] > 0


# -- the generator and the reference ----------------------------------------------------


def test_row_counts_follow_clause_4_2_5():
    for sf in (SF, 0.01):
        n = tpch.sizes(sf)
        assert (n["supplier"], n["part"], n["customer"], n["orders"]) == (
            round(sf * 10_000), round(sf * 200_000), round(sf * 150_000), round(sf * 1_500_000))
        assert n["partsupp"] == 4 * n["part"] and (n["nation"], n["region"]) == (25, 5)
        lines = tpch.lines_per_order(sf)
        assert len(lines) == n["orders"] and lines.min() >= 1 and lines.max() <= 7
    tb = tpch.make(SF, 3)
    got = {t: len(cols[tpch.columns(t)[0]]) for t, cols in tb.tables.items()}
    assert got == dict(tpch.sizes(SF), lineitem=int(tpch.lines_per_order(SF).sum()))
    assert sum(len(cols) for cols in tb.tables.values()) == 61
    assert sum(len(c) for _t, c, _p, _pc in tpch.FOREIGN_KEYS) == 11  # ten keys, one of two


def test_the_bridge_gives_each_part_four_distinct_suppliers():
    raw = [[(p + i * (100 // 4 + (p - 1) // 100)) % 100 + 1 for i in range(4)]
           for p in range(1, 2001)]
    assert tpch.part_suppliers(2000, 100).tolist() == raw  # SF 0.01: the formula itself
    small = tpch.part_suppliers(100, 5)
    assert all(len(set(row)) == 4 for row in small.tolist())


def test_every_foreign_key_resolves():
    tb = tpch.make(SF, 2_147_483_701)
    for table, cols, parent, pcols in tpch.FOREIGN_KEYS:
        pk = set(zip(*(tb.tables[parent][c] for c in pcols)))
        keys = list(zip(*(tb.tables[table][c] for c in cols)))
        assert all(k in pk for k in keys), (table, cols)
    for table, keys in tpch.PRIMARY_KEYS.items():
        rows = list(zip(*(tb.tables[table][c] for c in keys)))
        assert len(set(rows)) == len(rows), table
    # the composite key tells apart the four suppliers of a part
    li = tb.tables["lineitem"]
    by_part = collections.defaultdict(set)
    for p, s in zip(tb.tables["partsupp"]["ps_partkey"], tb.tables["partsupp"]["ps_suppkey"]):
        by_part[p].add(s)
    assert all(len(v) == 4 for v in by_part.values())
    assert len(set(zip(li["l_partkey"], li["l_suppkey"]))) > len(set(li["l_partkey"]))


def test_seed_renames_but_keeps_the_work():
    a, b = tpch.make(SF, 1), tpch.make(SF, 2**31 + 11)
    assert a.source_rows == b.source_rows
    assert a.tables["part"]["p_partkey"] != b.tables["part"]["p_partkey"]
    assert a.tables["lineitem"]["l_comment"] != b.tables["lineitem"]["l_comment"]
    ra, rb = tpch.reference_lines(a), tpch.reference_lines(b)
    assert len(ra) == len(rb) and set(ra) != set(rb)
    assert tpch.make(SF, 1).tables == a.tables


@pytest.mark.parametrize("cfg", [dict(), dict(stream=True, block_rows=512)],
                         ids=["eager", "stream"])
def test_engines_agree_with_the_reference(cfg, tmp_path):
    from repro.core.executor import create_kg
    from repro.rml import parser

    tb = tpch.make(SF, 7)
    doc = parser.parse_file(tb.write(str(tmp_path)))
    kg = create_kg(doc, str(tmp_path), **cfg)
    got = [ln[:-2] for ln in kg.iter_ntriples()]
    assert len(got) == len(set(got))
    assert sorted(got) == list(tpch.reference_lines(tb))
