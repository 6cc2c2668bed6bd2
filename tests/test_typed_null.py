"""Typed literals, term types, IRI-safe templates and NULL cells, in every
engine: the mapping constructs the GTFS-Madrid-Bench cell needs."""

import hashlib
import os
import sys

import numpy as np
import pytest

from repro import obs
from repro.core.executor import create_kg
from repro.data.terms import canonical_term, iri_safe
from repro.kg import persist
from repro.rml import parser, serializer
from repro.rml.model import IRI, LITERAL, TermMap

XSD = "http://www.w3.org/2001/XMLSchema#"
PREFIXES = """@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
@prefix xsd: <http://www.w3.org/2001/XMLSchema#> .
"""

# every engine path: (eager | streamed | naive) x (planner on | off)
ENGINES = [
    pytest.param(dict(engine=e, stream=s, mapping_plan=p), id=f"{n}-{'plan' if p else 'noplan'}")
    for n, e, s in (("eager", "optimized", False), ("stream", "optimized", True),
                    ("naive", "naive", False))
    for p in (True, False)
]

MAPPING = PREFIXES + """
<#C> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "c.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/c/{id}-{t}" ; rr:class <http://x/C> ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/n> ;
      rr:objectMap [ rml:reference "n" ; rr:datatype xsd:integer ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/u> ;
      rr:objectMap [ rml:reference "u" ; rr:termType rr:IRI ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/l> ;
      rr:objectMap [ rr:template "{n}/{t}" ; rr:termType rr:Literal ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/p> ;
      rr:objectMap [ rr:parentTriplesMap <#P> ;
                     rr:joinCondition [ rr:child "pk" ; rr:parent "k" ] ] ] .
<#P> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "p.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/p/{k}-{v}" ] .
"""
# row 2: NULL t (subject) -> none of its triples; row 3: NULL n (typed and
# template object) and NULL pk (child key); parent rows: NULL key, NULL v
# (parent subject); row 5 repeats row 1
CHILD = "id,t,n,u,pk\n1,08:00,5,http://a/b,k1\n2,,6,,k2\n3,09:00,,http://c,\n" \
        "4,10 h,7,,k3\n1,08:00,5,http://a/b,k1\n"
PARENT = "k,v\nk1,a\n,b\nk2,c\nk3,\nk1,a\n"
I = "<http://x/c/1-08%3A00>"
J = "<http://x/c/3-09%3A00>"
K = "<http://x/c/4-10%20h>"
TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
EXPECTED = {
    f"{I} {TYPE} <http://x/C> .", f"{J} {TYPE} <http://x/C> .", f"{K} {TYPE} <http://x/C> .",
    f'{I} <http://x/n> "5"^^<{XSD}integer> .', f'{K} <http://x/n> "7"^^<{XSD}integer> .',
    f"{I} <http://x/u> <http://a/b> .", f"{J} <http://x/u> <http://c> .",
    f'{I} <http://x/l> "5/08:00" .', f'{K} <http://x/l> "7/10 h" .',
    f"{I} <http://x/p> <http://x/p/k1-a> .",
}


@pytest.fixture
def data(tmp_path):
    (tmp_path / "c.csv").write_text(CHILD)
    (tmp_path / "p.csv").write_text(PARENT)
    return tmp_path


def _counter(name):
    return obs.get_registry().snapshot()["counters"].get(name, 0)


@pytest.mark.parametrize("cfg", ENGINES)
def test_null_typed_and_iri_safe_terms(cfg, data):
    before = _counter("ingest.null_terms")
    kg = create_kg(parser.parse(MAPPING), str(data), **cfg)
    assert set(kg.iter_ntriples()) == EXPECTED
    assert kg.n_triples == len(EXPECTED)
    # rows masked: row 2's NULL t in all five rules (class, n, u, l, p),
    # row 3's NULL n in n and l and its NULL pk in p, row 4's NULL u in u
    assert _counter("ingest.null_terms") - before == 5 + 2 + 1 + 1


def test_join_index_drops_null_keys_and_subjects():
    """Child keys NULL or matching only NULL-subject parents join nothing."""
    doc = parser.parse(MAPPING)
    c = {k: np.array(v, dtype=object) for k, v in {
        "id": ["1", "2", "3"], "t": ["a", "b", "c"], "n": ["1", "1", "1"],
        "u": ["http://u", "http://u", "http://u"], "pk": ["", "k3", "k1"]}.items()}
    p = {"k": np.array(["", "k3", "k1"], dtype=object), "v": np.array(["z", "", "y"], dtype=object)}
    kg = create_kg(doc, tables={"csv:c.csv": c, "csv:p.csv": p})
    joined = [ln for ln in kg.iter_ntriples() if "<http://x/p>" in ln]
    assert joined == ["<http://x/c/3-c> <http://x/p> <http://x/p/k1-y> ."]


@pytest.mark.parametrize("stream", [False, True], ids=["eager", "stream"])
def test_replay_counts_null_terms_once(stream, tmp_path):
    """A PTT sized at 8 candidates a slot overflows and its predicates are
    replayed; the rows they mask for a NULL term still count once."""
    rows = ["id,t,n,u,pk"] + [
        f"{i},{'' if i % 7 == 0 else i % 5},{'' if i % 3 == 0 else i},"
        f"{'' if i % 4 == 0 else f'http://u/{i}'},{'' if i % 6 == 0 else f'k{i % 50}'}"
        for i in range(600)
    ]
    (tmp_path / "c.csv").write_text("\n".join(rows) + "\n")
    (tmp_path / "p.csv").write_text(
        "k,v\n" + "".join(f"k{j},{'' if j % 9 == 0 else j}\n" for j in range(50))
    )
    doc = parser.parse(MAPPING)

    def run(**cfg):
        nulls, replays = _counter("ingest.null_terms"), _counter("ingest.replays")
        kg = create_kg(doc, str(tmp_path), stream=stream, **cfg)
        return (kg, _counter("ingest.null_terms") - nulls,
                _counter("ingest.replays") - replays)

    want, want_nulls, no_replays = run()
    got, got_nulls, replays = run(load_factor=8.0)
    assert no_replays == 0 and replays >= 1
    assert want_nulls > 0 and got_nulls == want_nulls
    assert got.stats == want.stats
    assert got.sorted_ntriples() == want.sorted_ntriples()


def test_datatype_and_term_type_roundtrip():
    doc = parser.parse(MAPPING)
    poms = {p.predicate: p.object_map for p in doc.triples_maps["C"].poms}
    assert poms["http://x/n"] == TermMap(reference="n", datatype=XSD + "integer")
    assert poms["http://x/u"] == TermMap(reference="u", term_type=IRI)
    assert poms["http://x/l"] == TermMap(template="{n}/{t}", term_type=LITERAL)
    again = parser.parse(serializer.to_turtle(doc))
    assert again == doc
    # typing is part of the term's evaluation identity
    assert len({poms["http://x/n"].key, TermMap(reference="n").key,
                TermMap(reference="n", term_type=IRI).key}) == 3


_MAP = PREFIXES + """<#T> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "c.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/{id}" %s] ;
  rr:predicateObjectMap [ rr:predicate <http://x/p> %s;
      rr:objectMap [ %s ] ] %s.
<#P> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "p.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/p/{k}" ] .
"""


@pytest.mark.parametrize("subject,pom,obj,tm,term", [
    ("", "", 'rml:reference "v" ; rr:language "es"', "", "language"),
    ("", "", 'rml:reference "v" ; rr:termType rr:BlankNode', "", "BlankNode"),
    ("; rr:graphMap [ rr:constant <http://g> ] ", "", 'rml:reference "v"', "", "graphMap"),
    ("", "; rr:graphMap [ rr:constant <http://g> ] ", 'rml:reference "v"', "", "graphMap"),
    ("", "", 'rml:reference "v"', "; rr:graphMap [ rr:constant <http://g> ] ", "graphMap"),
    # a second join condition is a composite key's; one without rr:parent is refused
    pytest.param(
        "", "", 'rr:parentTriplesMap <#P> ; rr:joinCondition [ rr:child "a" ; rr:parent "k" ] ; '
        'rr:joinCondition [ rr:child "b" ]', "", "joinCondition",
        id='--rr:parentTriplesMap <#P> ; rr:joinCondition [ rr:child "a" ; rr:parent "k" ] ; '
           'rr:joinCondition [ rr:child "b" ; rr:parent "k" ]--joinCondition'),
    ("; rr:class <http://x/A> ; rr:class <http://x/B> ", "", 'rml:reference "v"', "", "class"),
    ("", "", 'rml:reference "v" ; rr:termType rr:IRI ; rr:datatype xsd:integer', "", "datatype"),
    ("; rr:termType rr:Literal ", "", 'rml:reference "v"', "", "IRIs"),
])
def test_parser_refuses_what_it_does_not_implement(subject, pom, obj, tm, term):
    with pytest.raises(SyntaxError, match=term):
        parser.parse(_MAP % (subject, pom, obj, tm))


def test_subject_reference_gives_verbatim_iris(tmp_path):
    """R2RML §7.4: a subject map's reference gives IRIs, taken as they are;
    an empty cell gives no subject."""
    doc = parser.parse(PREFIXES + """<#S> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "s.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rml:reference "iri" ; rr:class <http://x/C> ] .
""")
    assert doc.triples_maps["S"].subject == TermMap(reference="iri", term_type=IRI)
    assert parser.parse(serializer.to_turtle(doc)) == doc
    (tmp_path / "s.csv").write_text("iri,n\nhttp://a/b c,1\n,2\n")
    kg = create_kg(doc, str(tmp_path))
    assert list(kg.iter_ntriples()) == [f"<http://a/b c> {TYPE} <http://x/C> ."]


def test_iri_safe_fast_and_escaped_paths():
    plain = "par_4_263-Sol.1~x"
    assert iri_safe(plain) is plain  # nothing to encode: the value itself
    assert iri_safe("08:15:00") == "08%3A15%3A00"
    assert iri_safe("a b/c?d#e%") == "a%20b%2Fc%3Fd%23e%25"
    assert iri_safe("Núñez \U0001F687") == "Núñez%20\U0001F687"  # ucschar stays
    assert iri_safe("\x7f \u00a0") == "%7F%20\u00a0"


def test_canonical_term_reads_typed_literals():
    assert canonical_term(f'"5"^^<{XSD}integer>') == f'"5"^^<{XSD}integer>'
    assert canonical_term(f'"a\\u0022b"^^<{XSD}string>') == f'"a\\"b"^^<{XSD}string>'
    assert canonical_term('"x"') == '"x"'
    with pytest.raises(ValueError):
        canonical_term('"x"@en')


def test_typed_literals_roundtrip_through_kgz(data, tmp_path):
    kg = create_kg(parser.parse(MAPPING), str(data))
    store = kg.to_store()
    path = str(tmp_path / "kg.kgz")
    persist.save(store, path)
    loaded = persist.load(path)
    assert sorted(loaded.iter_ntriples()) == sorted(EXPECTED)
    tid = loaded.term_id(canonical_term(f'"5"^^<{XSD}integer>'))
    assert tid is not None and loaded.decode_term(tid) == f'"5"^^<{XSD}integer>'
    assert loaded.term_id('"5"') is None  # the plain literal is another term


sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "bench"))
import testbed  # noqa: E402  (the benchmark's own copy of the paper's testbeds)

# sha256 of what the engine wrote before typed terms and NULL cells existed,
# for the paper's testbeds at 2,000 rows and seed 7: a KG with no typed
# literal and no empty cell keeps its bytes
DIGESTS = {
    ("OJM", "nt"): "637ff12d367ecfc59ed5a340cd6e2eadbe9745f1bd03022555bde16af37f8bc5",
    ("OJM", "kgz"): "dbd7605d6df6b88d435795ef3d66ec9bcf63a0ddb602823ab9b165fc3a41d599",
    ("SOM", "nt"): "ae5d2012344f8af916732e3993f4542a828a3760ce6a64f8ed2b747adea759ba",
    ("SOM", "kgz"): "1668788a1e10c31f337a1b74546de45c186f2501a4aef4096ccdf673a58df99a",
}


@pytest.mark.parametrize("kind,emit,stream", [
    ("OJM", "nt", False), ("OJM", "nt", True), ("OJM", "kgz", False),
    ("SOM", "nt", False), ("SOM", "nt", True), ("SOM", "kgz", False),
])
def test_untyped_testbeds_keep_their_bytes(kind, emit, stream, tmp_path):
    from repro.launch import rdfize

    dup, poms = (0.75, 2) if kind == "OJM" else (0.25, 4)
    mapping = testbed.make(kind, 2000, dup, poms, 7).write(str(tmp_path))
    out = str(tmp_path / f"kg.{emit}")
    rdfize.main(["--mapping", mapping, "--data-root", str(tmp_path), "--out", out, "--emit", emit]
                + (["--stream"] if stream else []))
    with open(out, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == DIGESTS[(kind, emit)]
