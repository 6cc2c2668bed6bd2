"""RML layer end-to-end: parser round-trip, generator statistics, and the
engine-equivalence guarantee (optimized == naive output) on all testbeds."""

import numpy as np
import pytest

from repro.core.executor import create_kg
from repro.rml import generator, parser, serializer


@pytest.mark.parametrize("kind", ["SOM", "ORM", "OJM"])
@pytest.mark.parametrize("dup", [0.25, 0.75])
def test_engines_produce_identical_kg(kind, dup):
    tb = generator.make_testbed(kind, 1500, dup, n_poms=2, seed=11)
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    results = {
        eng: create_kg(tb.doc, tables=tables, engine=eng)
        for eng in ("optimized", "naive")
    }
    assert results["optimized"].as_set() == results["naive"].as_set()
    assert results["optimized"].n_triples > 0


@pytest.mark.parametrize("kind", ["SOM", "ORM", "OJM"])
def test_parser_roundtrip(kind):
    tb = generator.make_testbed(kind, 100, 0.25, n_poms=3)
    ttl = serializer.to_turtle(tb.doc)
    doc2 = parser.parse(ttl)
    assert doc2.triples_maps == tb.doc.triples_maps


def test_parse_from_file_and_run(tmp_path):
    tb = generator.make_testbed("OJM", 400, 0.25, n_poms=1)
    tb.write(str(tmp_path))
    serializer.write_turtle(tb.doc, str(tmp_path / "map.ttl"))
    doc = parser.parse_file(str(tmp_path / "map.ttl"))
    res = create_kg(doc, data_root=str(tmp_path))
    assert res.n_triples > 0
    out = tmp_path / "kg.nt"
    n = res.write_ntriples(str(out))
    assert n == res.n_triples
    lines = out.read_text().splitlines()
    assert all(line.endswith(" .") and line.count(" ") >= 3 for line in lines)


def test_rdfize_rejects_the_join_flag(tmp_path, capsys):
    """The sorted PJTT is the only join index: ``--join`` is no option."""
    from repro.launch import rdfize

    with pytest.raises(SystemExit) as exc:
        rdfize.main(["--mapping", str(tmp_path / "map.ttl"), "--join", "hash"])
    assert exc.value.code == 2
    assert "unrecognized arguments: --join hash" in capsys.readouterr().err


def test_generator_duplicate_rate():
    """The testbed construction: dup_rate of rows are duplicates, each
    duplicated value repeated DUP_GROUP times (paper §V)."""
    t = generator.make_child_table(10000, 0.75, seed=3)
    ids = t["MUTATION_ID"]
    _, counts = np.unique(ids, return_counts=True)
    n_dup_rows = int((counts[counts > 1]).sum())
    assert n_dup_rows / len(ids) == pytest.approx(0.75, abs=0.02)
    # duplicated values repeat ~DUP_GROUP times
    assert np.median(counts[counts > 1]) == pytest.approx(generator.DUP_GROUP, abs=2)


def test_duplicate_rate_affects_unique_counts():
    """Q1 of the paper: duplicate rate drives |S_p| and therefore φ."""
    out = {}
    for dup in (0.25, 0.75):
        tb = generator.make_testbed("SOM", 4000, dup, n_poms=1, seed=5)
        res = create_kg(tb.doc, tables={"csv:child.csv": tb.child})
        st = [s for s in res.stats.values() if s.kind == "SOM"][0]
        out[dup] = st.n_unique / st.n_candidates
    assert out[0.75] < out[0.25] < 1.0


def test_pjtt_reuse_across_rules():
    """A parent map referenced by several join rules builds ONE PJTT."""
    from repro.core import planner

    tb = generator.make_ojm_testbed(200, 0.25, n_poms=3)
    # same parent column join: collapse the three ExonMaps into joins
    # against one map to exercise reuse
    from repro.rml.model import (
        JoinCondition, MappingDocument, PredicateObjectMap, RefObjectMap,
    )

    base = tb.doc.triples_maps["TriplesMap1"]
    parent = tb.doc.triples_maps["ExonMap1"]
    poms = tuple(
        PredicateObjectMap(
            predicate=f"http://repro.org/vocab/p{i}",
            object_map=RefObjectMap(
                parent_triples_map="ExonMap1",
                joins=(JoinCondition("ACCESSION_NUMBER", "ACCESSION_NUMBER"),),
            ),
        )
        for i in range(3)
    )
    import dataclasses

    doc = MappingDocument(
        {
            "TriplesMap1": dataclasses.replace(base, poms=poms),
            "ExonMap1": parent,
        }
    )
    plan = planner.plan(doc)
    assert len(plan.pjtt_builds) == 1  # one build, three consumers
    assert sum(1 for op in plan.ops if op.kind == "OJM") == 3
