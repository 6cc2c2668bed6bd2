"""Joins on several ``rr:joinCondition``s: a composite foreign key.

A referencing object map with N conditions joins a child row to every
parent row whose N parent columns all equal the row's N child columns.
The key is the joined values of the columns, encoded in the shared
dictionary; a NULL (empty) part makes the key NULL, so the row joins
nothing.  Every engine (eager and streamed, mapping plan on and off)
gives the naive engine's KG and a plain Python join's.
"""

import collections

import numpy as np
import pytest

from repro import obs
from repro.core.executor import create_kg
from repro.rml import parser, serializer
from repro.rml.model import (
    JoinCondition,
    LogicalSource,
    MappingDocument,
    PredicateObjectMap,
    RefObjectMap,
    TermMap,
    TriplesMap,
)

PREFIXES = """@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
"""
CHILD_KEYS = ("a", "b", "c")
PARENT_KEYS = ("k1", "k2", "k3")


def _mapping(n_conditions: int) -> str:
    conds = " ; ".join(
        f'rr:joinCondition [ rr:child "{c}" ; rr:parent "{p}" ]'
        for c, p in zip(CHILD_KEYS[:n_conditions], PARENT_KEYS[:n_conditions])
    )
    return PREFIXES + f"""<#C> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "child.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/c/{{id}}" ; rr:class <http://x/C> ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/ref> ;
      rr:objectMap [ rr:parentTriplesMap <#P> ; {conds} ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/a> ; rr:objectMap [ rml:reference "a" ] ] .
<#P> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "parent.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/p/{{pid}}" ; rr:class <http://x/P> ] .
"""


def _tables(seed: int, n_child: int = 300, n_parent: int = 80):
    """Child rows and parent rows over small key domains: many child rows
    share a first key column with a parent row and differ in another, so
    a join on the first column alone gives more triples.  About one cell
    in twelve of every key column is empty (NULL)."""
    g = np.random.default_rng(seed)

    def col(n, hi, null_share=1 / 12):
        v = [str(x) for x in g.integers(0, hi, n)]
        return [("" if e else x) for x, e in zip(v, g.random(n) < null_share)]

    child = {"id": [str(i) for i in range(n_child)],
             "a": col(n_child, 5), "b": col(n_child, 4), "c": col(n_child, 3)}
    # a parent key repeats (pid is unique): a composite key has fan-out
    parent = {"pid": [f"P{i}" for i in range(n_parent)],
              "k1": col(n_parent, 5), "k2": col(n_parent, 4), "k3": col(n_parent, 3)}
    return child, parent


def _write(tmp_path, child, parent, n_conditions):
    for name, table in (("child", child), ("parent", parent)):
        cols = list(table)
        lines = [",".join(cols)] + [",".join(r) for r in zip(*table.values())]
        (tmp_path / f"{name}.csv").write_text("\n".join(lines) + "\n")
    return parser.parse(_mapping(n_conditions))


def _expected_refs(child, parent, n_conditions):
    """The ``<http://x/ref>`` triples of a plain Python join on the key
    tuple; an empty part matches nothing."""
    index = collections.defaultdict(set)
    for row in zip(parent["pid"], *(parent[k] for k in PARENT_KEYS[:n_conditions])):
        if "" not in row[1:]:
            index[row[1:]].add(row[0])
    out = set()
    for row in zip(child["id"], *(child[k] for k in CHILD_KEYS[:n_conditions])):
        if "" not in row[1:]:
            out.update(f"<http://x/c/{row[0]}> <http://x/ref> <http://x/p/{p}> ."
                       for p in index.get(row[1:], ()))
    return out


def _refs(kg):
    return {ln for ln in kg.iter_ntriples() if " <http://x/ref> " in ln}


# -- parser, serializer and classify ------------------------------------------


@pytest.mark.parametrize("n_conditions", [2, 3])
def test_parser_and_serializer_round_trip_join_conditions(n_conditions):
    doc = parser.parse(_mapping(n_conditions))
    ref = doc.triples_maps["C"].poms[0].object_map
    assert ref.joins == tuple(
        JoinCondition(c, p)
        for c, p in zip(CHILD_KEYS[:n_conditions], PARENT_KEYS[:n_conditions])
    )
    assert parser.parse(serializer.to_turtle(doc)) == doc
    assert doc.classify(doc.triples_maps["C"], doc.triples_maps["C"].poms[0]) == "OJM"


def test_classify_orm_without_conditions_ojm_with_any():
    src = LogicalSource(path="t.csv")
    parent = TriplesMap("P", src, TermMap(template="http://x/p/{k}"))
    for joins, kind in (((), "ORM"), ((JoinCondition("a", "k"),), "OJM"),
                        ((JoinCondition("a", "k"), JoinCondition("b", "j")), "OJM")):
        pom = PredicateObjectMap("http://x/r", RefObjectMap("P", joins))
        child = TriplesMap("C", src, TermMap(template="http://x/c/{id}"), poms=(pom,))
        doc = MappingDocument({"P": parent, "C": child})
        assert doc.classify(child, pom) == kind
        assert parser.parse(serializer.to_turtle(doc)) == doc


# -- the engines against the naive engine and a plain join ------------------------

ENGINES = [
    pytest.param(dict(stream=s, mapping_plan=p, block_rows=64),
                 id=f"{'stream' if s else 'eager'}-{'plan' if p else 'noplan'}")
    for s in (False, True) for p in (True, False)
]


@pytest.mark.parametrize("n_conditions", [2, 3])
@pytest.mark.parametrize("cfg", ENGINES)
def test_engines_join_exactly_on_every_condition(cfg, n_conditions, tmp_path):
    child, parent = _tables(seed=11 + n_conditions)
    doc = _write(tmp_path, child, parent, n_conditions)
    want = _expected_refs(child, parent, n_conditions)
    # the data tells a join on all conditions from one on the first alone
    assert len(want) > 20
    assert len(_expected_refs(child, parent, 1)) > len(want) + 20
    naive = create_kg(doc, str(tmp_path), engine="naive")
    kg = create_kg(doc, str(tmp_path), batch_size=128, **cfg)
    assert _refs(naive) == want
    assert _refs(kg) == want
    assert kg.sorted_ntriples() == naive.sorted_ntriples()


@pytest.mark.parametrize("stream", [False, True], ids=["eager", "stream"])
@pytest.mark.parametrize("null_part", ["first", "second"])
def test_a_null_key_part_joins_nothing(stream, null_part, tmp_path):
    """A child and a parent row whose keys agree but for an empty part
    give no triple; their non-NULL copies join."""
    child = {"id": ["1", "2", "3"], "a": ["x", "x", "x"], "b": ["y", "y", "y"], "c": ["", "", ""]}
    parent = {"pid": ["p", "q"], "k1": ["x", "x"], "k2": ["y", "y"], "k3": ["", ""]}
    at = 0 if null_part == "first" else 1
    child[("a", "b")[at]][1] = ""
    parent[("k1", "k2")[at]][1] = ""
    doc = _write(tmp_path, child, parent, 2)
    for engine in ("optimized", "naive"):
        kg = create_kg(doc, str(tmp_path), engine=engine,
                       stream=stream and engine == "optimized")
        assert _refs(kg) == {f"<http://x/c/{c}> <http://x/ref> <http://x/p/p> ."
                             for c in ("1", "3")}


# -- spans and counters ------------------------------------------------------------


def _traced(doc, root, **cfg):
    tracer = obs.get_tracer()
    was = tracer.enabled
    obs.enable_tracing()
    tracer.clear()
    reg = obs.get_registry()
    before = reg.snapshot()["counters"].get("ingest.join_key_rows", 0)
    try:
        create_kg(doc, root, **cfg)
    finally:
        if not was:
            tracer.disable()
    events = tracer.export()["traceEvents"]
    spans = [ev for ev in events if ev.get("name") == "ingest.join_key"]
    rows = reg.snapshot()["counters"]["ingest.join_key_rows"] - before
    return spans, rows


@pytest.mark.parametrize("cfg", ENGINES + [pytest.param(dict(engine="naive"), id="naive")])
def test_join_key_spans_and_rows_count_composite_keys_only(cfg, tmp_path):
    child, parent = _tables(seed=5, n_child=150, n_parent=40)
    spans, rows = _traced(_write(tmp_path, child, parent, 2), str(tmp_path), **cfg)
    assert spans and all(ev["args"]["columns"] in ("a,b", "k1,k2") for ev in spans)
    assert {ev["args"]["columns"] for ev in spans} <= {"a,b", "k1,k2"}
    # each composite join's child rows and its PJTT's parent rows, once
    assert rows == 150 + 40
    spans, rows = _traced(_write(tmp_path, child, parent, 1), str(tmp_path), **cfg)
    assert spans == [] and rows == 0
