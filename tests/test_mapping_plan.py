"""Mapping-level planner: column-set extraction, rule-group partitioning,
the planner-on == planner-off byte-identity property (eager, streamed, and
sharded), strict pushdown failure semantics, and the explain surface."""

import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # test image without hypothesis: seeded-example fallback
    from _hypothesis_shim import given, settings, st

from repro.core.executor import create_kg
from repro.rml import generator, parser, serializer
from repro.rml.plan import build_plan

EX = "http://example.com/"

WIDE_TTL = """
@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
@prefix ex: <http://example.com/> .

ex:GeneMap a rr:TriplesMap ;
  rml:logicalSource [ rml:source "gene.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://example.com/gene/{GENE_ID}" ; rr:class ex:Gene ] ;
  rr:predicateObjectMap [ rr:predicate ex:name ; rr:objectMap [ rml:reference "GENE_NAME" ] ] ;
  rr:predicateObjectMap [ rr:predicate ex:label ; rr:objectMap [ rr:template "http://example.com/lbl/{GENE_ID}" ] ] .

ex:MutMap a rr:TriplesMap ;
  rml:logicalSource [ rml:source "mut.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://example.com/mut/{MUT_ID}" ] ;
  rr:predicateObjectMap [ rr:predicate ex:inGene ;
    rr:objectMap [ rr:parentTriplesMap ex:GeneMap ;
                   rr:joinCondition [ rr:child "GENE" ; rr:parent "GENE_ID" ] ] ] .

ex:OtherMap a rr:TriplesMap ;
  rml:logicalSource [ rml:source "other.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://example.com/o/{OID}" ] ;
  rr:predicateObjectMap [ rr:predicate ex:val ; rr:objectMap [ rml:reference "V" ] ] .
"""


def _write_wide_testbed(out_dir, n_genes=120, n_muts=200, n_junk=8, seed=0):
    """gene.csv carries ``n_junk`` never-mapped columns — the pushdown
    target; mut.csv joins into it; other.csv is source-disjoint."""
    rng = np.random.default_rng(seed)
    with open(os.path.join(out_dir, "gene.csv"), "w") as f:
        junk_hdr = ",".join(f"JUNK{j}" for j in range(n_junk))
        f.write(f"GENE_ID,GENE_NAME,{junk_hdr}\n")
        for i in range(n_genes):
            junk = ",".join(f"j{i}_{j}" for j in range(n_junk))
            f.write(f"g{i},name{i % 37},{junk}\n")
    with open(os.path.join(out_dir, "mut.csv"), "w") as f:
        f.write("MUT_ID,GENE\n")
        for i in range(n_muts):
            f.write(f"m{i},g{rng.integers(0, int(n_genes * 1.2))}\n")
    with open(os.path.join(out_dir, "other.csv"), "w") as f:
        f.write("OID,V\n")
        for i in range(40):
            f.write(f"o{i},v{i % 5}\n")


# ---------------------------------------------------------------------------
# column-set extraction (one case per object-map kind)
# ---------------------------------------------------------------------------


def _plan_for(ttl):
    return build_plan(parser.parse(ttl))


def test_columns_template_subject_and_reference_object():
    plan = _plan_for(WIDE_TTL)
    sp = plan.sources["csv:gene.csv"]
    assert sp.columns == ("GENE_ID", "GENE_NAME")
    assert sp.strict


def test_columns_join_child_and_parent():
    plan = _plan_for(WIDE_TTL)
    assert plan.sources["csv:mut.csv"].columns == ("GENE", "MUT_ID")
    # the parent side needs join column + subject columns, nothing else
    assert "GENE_ID" in plan.sources["csv:gene.csv"].columns


def test_columns_class_and_constant_read_nothing():
    ttl = """
@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
@prefix ex: <http://example.com/> .
ex:M a rr:TriplesMap ;
  rml:logicalSource [ rml:source "t.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://example.com/{ID}" ; rr:class ex:Thing ] ;
  rr:predicateObjectMap [ rr:predicate ex:tag ; rr:objectMap [ rr:constant "fixed" ] ] .
"""
    plan = _plan_for(ttl)
    # CLASS + constant objects contribute no columns beyond the subject's
    assert plan.sources["csv:t.csv"].columns == ("ID",)


def test_columns_multi_placeholder_template():
    ttl = """
@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
@prefix ex: <http://example.com/> .
ex:M a rr:TriplesMap ;
  rml:logicalSource [ rml:source "t.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://example.com/{A}/{B}" ] ;
  rr:predicateObjectMap [ rr:predicate ex:p ; rr:objectMap [ rr:template "http://example.com/x/{C}-{D}" ] ] .
"""
    plan = _plan_for(ttl)
    assert plan.sources["csv:t.csv"].columns == ("A", "B", "C", "D")


def test_columns_orm_shared_source():
    tb = generator.make_testbed("ORM", 50, 0.25, n_poms=1, seed=1)
    plan = build_plan(tb.doc)
    src = next(iter(plan.sources.values()))
    # ORM: child subject columns + parent subject columns, one source
    assert len(plan.sources) == 1
    assert len(src.columns) >= 2


def test_json_sources_are_tolerant():
    ttl = WIDE_TTL.replace(
        'rml:source "other.csv" ; rml:referenceFormulation ql:CSV',
        'rml:source "other.json" ; rml:referenceFormulation ql:JSONPath',
    )
    plan = _plan_for(ttl)
    assert not plan.sources["json:other.json"].strict
    assert plan.sources["csv:gene.csv"].strict


# ---------------------------------------------------------------------------
# shared-term factoring and rule groups
# ---------------------------------------------------------------------------


def test_shared_subject_template_is_factored():
    plan = _plan_for(WIDE_TTL)
    # GENE_ID feeds: GeneMap subject (x3 rules: class/name/label), the label
    # object template, the PJTT key and the PJTT subject -> one shared term
    sh = plan.shared[("csv:gene.csv", ("GENE_ID",))]
    assert sh.n_uses >= 4
    assert any("gene/" in p for p in sh.patterns)  # canonical subj pattern


def test_unshared_terms_are_not_factored():
    plan = _plan_for(WIDE_TTL)
    # GENE_NAME is referenced by exactly one rule
    assert ("csv:gene.csv", ("GENE_NAME",)) not in plan.shared


def test_groups_split_independent_maps():
    plan = _plan_for(WIDE_TTL)
    assert len(plan.groups) == 2
    g0, g1 = plan.groups
    # join dependency keeps GeneMap and MutMap together
    assert set(g0.triples_maps) == {"ex:GeneMap", "ex:MutMap"}
    assert g1.triples_maps == ("ex:OtherMap",)
    # groups are disjoint in predicates and sources
    assert not set(g0.predicates) & set(g1.predicates)
    assert not set(g0.sources) & set(g1.sources)
    assert plan.group_of_predicate(EX + "val").index == 1


def test_groups_merge_on_shared_source():
    ttl = WIDE_TTL.replace('rml:source "other.csv"', 'rml:source "gene.csv"')
    plan = _plan_for(ttl)
    assert len(plan.groups) == 1


def test_groups_merge_on_shared_predicate():
    # PTT dedup state is per predicate: two maps emitting ex:name must
    # land in one group even with disjoint sources
    ttl = WIDE_TTL.replace("ex:val", "ex:name")
    plan = _plan_for(ttl)
    assert len(plan.groups) == 1


# ---------------------------------------------------------------------------
# the hard bar: byte-identical output, planner on vs off
# ---------------------------------------------------------------------------


def _nt(doc, data_root, **opts):
    return create_kg(doc, data_root=data_root, **opts).sorted_ntriples()


@settings(max_examples=4, deadline=None)
@given(
    n_genes=st.integers(min_value=3, max_value=150),
    n_junk=st.integers(min_value=0, max_value=12),
    block_rows=st.sampled_from([16, 1024]),
    seed=st.integers(min_value=0, max_value=999),
)
def test_planner_identity_property(n_genes, n_junk, block_rows, seed):
    """Random wide-source mappings with shared templates: planner on and
    off produce byte-identical KGs, eager and streamed."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        _write_wide_testbed(
            d, n_genes=n_genes, n_muts=2 * n_genes, n_junk=n_junk, seed=seed
        )
        doc = parser.parse(WIDE_TTL)
        ref = _nt(doc, d, mapping_plan=False)
        assert _nt(doc, d, mapping_plan=True) == ref
        assert _nt(doc, d, mapping_plan=True, stream=True,
                   block_rows=block_rows) == ref
        assert _nt(doc, d, mapping_plan=False, stream=True,
                   block_rows=block_rows) == ref


@pytest.mark.parametrize("kind", ["SOM", "ORM", "OJM"])
def test_planner_identity_on_generator_testbeds(kind, tmp_path):
    tb = generator.make_testbed(kind, 700, 0.5, n_poms=2, seed=7)
    tb.write(str(tmp_path))
    ref = _nt(tb.doc, str(tmp_path), mapping_plan=False)
    assert _nt(tb.doc, str(tmp_path), mapping_plan=True) == ref
    assert _nt(tb.doc, str(tmp_path), mapping_plan=True, stream=True,
               block_rows=128) == ref


def test_factoring_actually_happens(tmp_path):
    """plan.factored_rows counts cache-served slots; output is unchanged."""
    from repro import obs

    _write_wide_testbed(str(tmp_path))
    doc = parser.parse(WIDE_TTL)
    reg = obs.get_registry()
    reg.reset()
    on = _nt(doc, str(tmp_path), mapping_plan=True, stream=True)
    factored = reg.counter("plan.factored_rows").value
    assert factored > 0
    assert reg.counter("plan.columns_pruned").value > 0
    assert reg.gauge("plan.groups").value == 2
    reg.reset()
    off = _nt(doc, str(tmp_path), mapping_plan=False, stream=True)
    assert reg.counter("plan.factored_rows").value == 0
    assert on == off


# ---------------------------------------------------------------------------
# strict pushdown: missing mapped columns fail loudly at read time
# ---------------------------------------------------------------------------


def test_missing_mapped_column_raises_at_read(tmp_path):
    _write_wide_testbed(str(tmp_path))
    doc = parser.parse(WIDE_TTL.replace('rml:reference "V"',
                                        'rml:reference "NO_SUCH"'))
    with pytest.raises(KeyError, match="NO_SUCH"):
        create_kg(doc, data_root=str(tmp_path), mapping_plan=True,
                  stream=True)
    # planner-off keeps the same strict behavior via the downstream Project
    with pytest.raises(KeyError):
        create_kg(doc, data_root=str(tmp_path), mapping_plan=False,
                  stream=True)


def test_pushdown_prunes_csv_columns(tmp_path):
    """The reader accounts kept/pruned columns only when pushdown fires."""
    from repro import obs

    _write_wide_testbed(str(tmp_path), n_junk=6)
    doc = parser.parse(WIDE_TTL)
    reg = obs.get_registry()
    reg.reset()
    create_kg(doc, data_root=str(tmp_path), mapping_plan=True, stream=True)
    assert reg.counter("plan.columns_pruned").value >= 6
    reg.reset()
    create_kg(doc, data_root=str(tmp_path), mapping_plan=False, stream=True)
    assert reg.counter("plan.columns_pruned").value == 0


# ---------------------------------------------------------------------------
# explain surface
# ---------------------------------------------------------------------------


def test_explain_mapping_api(tmp_path):
    from repro import api

    _write_wide_testbed(str(tmp_path), n_junk=3)
    (tmp_path / "map.ttl").write_text(WIDE_TTL)
    tree = api.explain_mapping(str(tmp_path / "map.ttl"),
                               data_root=str(tmp_path))
    assert "mapping plan: " in tree and "-> 2 groups" in tree
    assert "pruned [JUNK0, JUNK1, JUNK2]" in tree
    assert "PJTT ex:GeneMap on GENE_ID" in tree
    assert "factored terms" in tree
    # also accepts a parsed document (no header peek -> kept only)
    tree2 = api.explain_mapping(parser.parse(WIDE_TTL))
    assert "kept [GENE_ID, GENE_NAME]" in tree2


def test_explain_mapping_cli(tmp_path, capsys, monkeypatch):
    from repro.launch import rdfize

    _write_wide_testbed(str(tmp_path))
    (tmp_path / "map.ttl").write_text(WIDE_TTL)
    monkeypatch.setattr(
        "sys.argv",
        ["rdfize", "--mapping", str(tmp_path / "map.ttl"),
         "--data-root", str(tmp_path), "--explain-mapping"],
    )
    rdfize.main()
    out = capsys.readouterr().out
    assert "mapping plan: " in out and "rules" in out
    assert "group 0" in out and "group 1" in out


def test_cli_no_mapping_plan_flag(tmp_path, capsys, monkeypatch):
    from repro.launch import rdfize

    tb = generator.make_testbed("SOM", 120, 0.25, n_poms=1)
    tb.write(str(tmp_path))
    serializer.write_turtle(tb.doc, str(tmp_path / "map.ttl"))
    out_nt = tmp_path / "kg.nt"
    monkeypatch.setattr(
        "sys.argv",
        ["rdfize", "--mapping", str(tmp_path / "map.ttl"),
         "--data-root", str(tmp_path), "--out", str(out_nt),
         "--no-mapping-plan"],
    )
    rdfize.main()
    out = capsys.readouterr().out
    assert "plan:" not in out  # summary line suppressed when disabled
    assert out_nt.read_text().count("\n") > 0


def test_cli_plan_summary_line(tmp_path, capsys, monkeypatch):
    from repro.launch import rdfize

    _write_wide_testbed(str(tmp_path))
    (tmp_path / "map.ttl").write_text(WIDE_TTL)
    monkeypatch.setattr(
        "sys.argv",
        ["rdfize", "--mapping", str(tmp_path / "map.ttl"),
         "--data-root", str(tmp_path)],
    )
    rdfize.main()
    assert "plan: 5 rules over 3 sources -> 2 groups" in \
        capsys.readouterr().out
