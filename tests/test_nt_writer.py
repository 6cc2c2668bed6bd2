"""The N-Triples writer against a plain per-line rendering: the same
bytes, in the same order, for every kind of term and at every chunk
size; its counters; and the batch renderer against ``render_term``."""

import os
import sys

import numpy as np
import pytest

from repro.core import executor
from repro.core.executor import KGResult, create_kg
from repro.data.encoder import Dictionary
from repro.data.terms import render_term, render_terms
from repro.obs import metrics
from repro.rml import generator, parser

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "bench"))
import gtfs  # noqa: E402  (the benchmark's GTFS-Madrid-Bench feed)

XSD = "http://www.w3.org/2001/XMLSchema#"
PREFIXES = """@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
@prefix xsd: <http://www.w3.org/2001/XMLSchema#> .
"""

# multi-column templates in an IRI and a literal, a typed and a plain
# literal, a verbatim IRI, and a column that is NULL in every row (its
# predicate has zero rows)
MAPPING = PREFIXES + """
<#T> a rr:TriplesMap ;
  rml:logicalSource [ rml:source "t.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://x/{a}/{b}-{c}" ; rr:class <http://x/C> ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/l> ;
      rr:objectMap [ rr:template "{a} and {b}" ; rr:termType rr:Literal ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/s> ;
      rr:objectMap [ rml:reference "c" ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/d> ;
      rr:objectMap [ rml:reference "c" ; rr:datatype xsd:string ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/u> ;
      rr:objectMap [ rml:reference "u" ; rr:termType rr:IRI ] ] ;
  rr:predicateObjectMap [ rr:predicate <http://x/z> ;
      rr:objectMap [ rml:reference "z" ] ] .
"""
# values that need percent-encoding in IRIs and escaping in literals
VALUES = ["plain", "08:15:00", "a b/c?d#e%", "100%0A", "Núñez \U0001F687", 'q"uote',
          "back\\slash", "new\nline", "tab\tcr\r", "ctl\x01\x08\x0c\x7f", "{}", "ü"]


def _tables():
    n = len(VALUES)
    col = np.array(VALUES, dtype=object)
    return {"csv:t.csv": {
        "a": col, "b": np.roll(col, 1), "c": np.roll(col, 2),
        "u": np.array([f"http://u/{k % 3}" for k in range(n)], dtype=object),
        "z": np.array([""] * n, dtype=object),
    }}


def _testbed(kind, stream):
    def build(tmp_path):
        dup, poms = (0.75, 2) if kind == "OJM" else (0.25, 4)
        tb = generator.make_testbed(kind, 600, dup, poms, seed=3)
        return create_kg(tb.doc, data_root=tb.write(str(tmp_path)), stream=stream)
    return build


def _gtfs(tmp_path):
    mapping = gtfs.make(200, 5).write(str(tmp_path))
    return create_kg(parser.parse_file(mapping), data_root=str(tmp_path), stream=True)


def _templates_and_escapes(tmp_path):
    kg = create_kg(parser.parse(MAPPING), tables=_tables())
    assert len(kg.triples["http://x/z"]["subj_val"]) == 0
    return kg


def _empty(tmp_path):
    return KGResult(Dictionary(), {}, {})


CASES = {
    "som-eager": _testbed("SOM", False), "som-stream": _testbed("SOM", True),
    "ojm-eager": _testbed("OJM", False), "ojm-stream": _testbed("OJM", True),
    "gtfs": _gtfs, "templates-and-escapes": _templates_and_escapes, "empty": _empty,
}


def _per_line(kg: KGResult) -> bytes:
    """The writer as it was: two ``render_term`` calls a line."""
    d, lines = kg.dictionary, []
    for pred, t in kg.triples.items():
        for i in range(len(t["subj_val"])):
            s = render_term(d, int(t["subj_pat"][i]), int(t["subj_val"][i]))
            o = render_term(d, int(t["obj_pat"][i]), int(t["obj_val"][i]))
            lines.append(f"{s} <{pred}> {o} .\n")
    return "".join(lines).encode("utf-8")


@pytest.mark.parametrize("case,chunk", [(c, None) for c in CASES]
                         + [("som-eager", 3), ("templates-and-escapes", 5)])
def test_writer_matches_the_per_line_rendering(case, chunk, tmp_path, monkeypatch):
    if chunk is not None:
        monkeypatch.setattr(executor, "_CHUNK_LINES", chunk)
    kg = CASES[case](tmp_path)
    out = tmp_path / "kg.nt"
    n = kg.write_ntriples(str(out))
    data = out.read_bytes()
    assert n == kg.n_triples
    assert data == _per_line(kg)
    assert list(kg.iter_ntriples()) == data.decode("utf-8").split("\n")[:-1]


def test_writer_counters(tmp_path, monkeypatch):
    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    kg = _gtfs(tmp_path)
    counters = metrics.get_registry().snapshot()["counters"]
    assert (counters["emit.lines"], counters["emit.terms_rendered"]) == (0, 0)
    n = kg.write_ntriples(str(tmp_path / "kg.nt"))
    pairs = {
        (int(p), int(v)) for t in kg.triples.values()
        for side in ("subj", "obj") for p, v in zip(t[f"{side}_pat"], t[f"{side}_val"])
    }
    counters = metrics.get_registry().snapshot()["counters"]
    assert counters["emit.lines"] == n == kg.n_triples
    assert counters["emit.terms_rendered"] == len(pairs) < 2 * n


def test_render_terms_matches_render_term():
    d = Dictionary()
    patterns = [
        "iri:http://x/{}", "iri:http://x/{}/{}-{}", "iri:http://x/{}?q", "ref:{}",
        "lit:{}", "lit:{} and {}", "lit:say \"{}\"\n", f"typ:{XSD}integer {{}}",
        f"typ:{XSD}string {{}}/{{}}", "iri:http://x/Const", 'lit:const "\\"',
        f"typ:{XSD}boolean true",
    ]
    pids = [d.encode_scalar(p) for p in patterns]
    values = VALUES + ["x\x1fy", "p q\x1fr:s\x1ft%u", "\x1f\x1f", "a\x1fb\nc"]
    rng = np.random.default_rng(0)
    # with a newline among an IRI pattern's values, and without
    for vals in (values, [v for v in values if "\n" not in v]):
        vids = [d.encode_scalar(v) for v in vals]
        pat = np.array([p for p in pids for _ in vids] + pids[::-1], np.int64)
        val = np.array([v for _ in pids for v in vids] + [0] * len(pids), np.int32)
        order = rng.permutation(len(pat))
        pat, val = pat[order], val[order]
        want = [render_term(d, int(p), int(v)) for p, v in zip(pat, val)]
        assert render_terms(d, pat, val) == want
    assert render_terms(d, pat[:0], val[:0]) == []
