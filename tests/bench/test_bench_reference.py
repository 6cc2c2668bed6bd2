"""The yardstick's pieces: the serve store against ``rdfize``, the
schedule's invariants, and the plain SPARQL reference."""

import os

import numpy as np
import pytest

from benchsteer import BENCH, REPO  # noqa: F401

import harness
import reference
import sparql
import testbed


def test_serve_store_renders_the_rdfize_kg(tmp_path):
    """At 10K rows the store the serve set-up builds holds exactly the
    triples of ``rdfize --emit kgz`` on the same seeded testbed."""
    import contextlib
    import io

    from repro.kg import persist
    from repro.launch import rdfize

    kind = harness.load_module(os.path.join(BENCH, "kinds", "serve.py"), "bench_kind_serve")
    tb = testbed.make("OJM", 10_000, 0.75, 2, 2_147_483_659)
    mapping = tb.write(str(tmp_path))
    out = str(tmp_path / "kg.kgz")
    with contextlib.redirect_stdout(io.StringIO()):
        rdfize.main(["--mapping", mapping, "--data-root", str(tmp_path),
                     "--out", out, "--emit", "kgz"])
    written = persist.load(out)
    built = kind.build_store(reference.ojm_kg(tb))
    assert built.n_triples == written.n_triples > 10_000
    assert list(built.iter_ntriples()) == list(written.iter_ntriples())
    assert sorted(reference.read_kgz(out)) == sorted(reference.ojm_kg(tb).lines())


def test_schedule_same_work_for_every_seed():
    kind = harness.load_module(os.path.join(BENCH, "kinds", "serve.py"), "bench_kind_serve")
    traffic = harness.load_json(os.path.join(BENCH, "traffic", "chain.json"))
    tb = testbed.make("OJM", 2000, 0.75, 2, 5)
    a = kind.make_schedule(tb, traffic, 5, 2.0, 300.0)
    b = kind.make_schedule(tb, traffic, 5, 2.0, 300.0)
    c = kind.make_schedule(tb, traffic, 2_147_483_701, 2.0, 300.0)
    assert a.texts == b.texts and (a.due == b.due).all()
    assert len(a.due) == 600 and 0 < a.due[0] and a.due[-1] < 2.1
    # the same gaps and shape counts, in another order
    gaps_a, gaps_c = np.diff(a.due, prepend=0), np.diff(c.due, prepend=0)
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_c), atol=1e-9)
    assert not np.allclose(gaps_a, gaps_c)
    assert np.bincount(a.shape_of).tolist() == np.bincount(c.shape_of).tolist() == [200] * 3
    assert not (a.shape_of == c.shape_of).all()


def test_zipf_ranks_are_skewed_quantiles():
    kind = harness.load_module(os.path.join(BENCH, "kinds", "serve.py"), "bench_kind_serve")
    r = kind.zipf_ranks(10_000, 1000, 0.99)
    counts = np.bincount(r, minlength=1000)
    assert counts[0] > counts[1] > counts[10] > counts[500]
    assert r.min() == 0 and r.max() <= 999


def _kg():
    a, b, c, d = "<http://x/a>", "<http://x/b>", "<http://x/c>", "<http://x/d>"
    p, q = "<http://x/p>", "<http://x/q>"
    triples = [(a, p, b), (a, p, c), (a, q, c), (d, p, b), (b, q, d)]
    terms = np.array(sorted({t for tr in triples for t in tr}))
    ids = {t: i for i, t in enumerate(terms.tolist())}
    cols = np.array([[ids[t] for t in tr] for tr in triples], np.int32)
    return reference.KG(terms, cols[:, 0], cols[:, 1], cols[:, 2])


@pytest.mark.parametrize("text, want_vars, want_rows", [
    ("SELECT * WHERE { <http://x/a> <http://x/p> ?o }", ["?o"],
     [("<http://x/b>",), ("<http://x/c>",)]),
    ("SELECT * WHERE { ?s <http://x/p> <http://x/b> . ?s <http://x/q> ?o }", ["?s", "?o"],
     [("<http://x/a>", "<http://x/c>")]),
    ("SELECT * WHERE { ?s <http://x/p> <http://x/b> OPTIONAL { ?s <http://x/q> ?o } }",
     ["?s", "?o"], [("<http://x/a>", "<http://x/c>"), ("<http://x/d>", None)]),
    ("SELECT * WHERE { ?s <http://x/p> <http://x/b> OPTIONAL { ?s <http://x/q> ?o } "
     "FILTER(?o != <http://x/d>) }", ["?s", "?o"], [("<http://x/a>", "<http://x/c>")]),
    ("SELECT * WHERE { { <http://x/a> <http://x/p> ?x } UNION { <http://x/a> <http://x/q> ?x } }",
     ["?x"], [("<http://x/b>",), ("<http://x/c>",), ("<http://x/c>",)]),
    ("SELECT ?p (COUNT(*) AS ?n) WHERE { ?s ?p <http://x/c> } GROUP BY ?p", ["?p", "?n"],
     [("<http://x/p>", 1), ("<http://x/q>", 1)]),
    ("SELECT ?s ?o WHERE { ?s <http://x/p> ?o } ORDER BY DESC(?s) LIMIT 2", ["?s", "?o"],
     [("<http://x/d>", "<http://x/b>"), ("<http://x/a>", "<http://x/b>")]),
])
def test_reference_answers(text, want_vars, want_rows):
    out_vars, rows = sparql.evaluate(sparql.Graph(_kg()), sparql.parse(text))
    assert out_vars == want_vars and rows == want_rows


def test_reference_refuses_what_it_cannot_read():
    for text in ("SELECT * WHERE { ?s ?p }", "SELECT * WHERE { ?s ?p ?o } LIMIT x",
                 "SELECT * WHERE { ?s ?p ?o FILTER(?s < ?o) }"):
        with pytest.raises(ValueError):
            sparql.parse(text)


def test_control_cap_changes_multi_row_answers():
    g = sparql.Graph(_kg(), cap=1)
    _v, rows = sparql.evaluate(g, sparql.parse("SELECT * WHERE { <http://x/a> <http://x/p> ?o }"))
    assert rows == [("<http://x/b>",)]
