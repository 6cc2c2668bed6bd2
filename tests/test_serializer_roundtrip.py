"""Serializer round-trips: ``parse(to_turtle(parse(x)))`` equivalence across
all example mappings — every operator kind and POM width the generator
produces, plus JSON sources with iterators, join conditions, and constant /
template / reference object maps (satellite of the repro.kg PR)."""

import pytest

from repro.rml import generator, parser, serializer
from repro.rml.model import (
    JoinCondition,
    LogicalSource,
    MappingDocument,
    PredicateObjectMap,
    RefObjectMap,
    TermMap,
    TriplesMap,
)


def _assert_roundtrip(doc: MappingDocument) -> None:
    ttl = serializer.to_turtle(doc)
    doc2 = parser.parse(ttl)
    assert doc2.triples_maps == doc.triples_maps
    # fixpoint: serialize -> parse -> serialize -> parse is stable
    assert parser.parse(serializer.to_turtle(doc2)).triples_maps == doc.triples_maps


@pytest.mark.parametrize("kind", ["SOM", "ORM", "OJM"])
@pytest.mark.parametrize("n_poms", [1, 2, 5])
@pytest.mark.parametrize("seed", [0, 3])
def test_generator_testbeds_roundtrip(kind, n_poms, seed):
    tb = generator.make_testbed(kind, 50, 0.25, n_poms=n_poms, seed=seed)
    _assert_roundtrip(tb.doc)


def test_json_iterator_roundtrip():
    """JSON logical sources keep their referenceFormulation and iterator."""
    src = LogicalSource(path="data/items.json", fmt="json", iterator="$.items[*]")
    psrc = LogicalSource(path="data/owners.json", fmt="json", iterator="$.owners[*]")
    maps = {
        "OwnerMap": TriplesMap(
            name="OwnerMap",
            source=psrc,
            subject=TermMap(template="http://ex.org/owner/{oid}"),
            subject_class="http://ex.org/vocab/Owner",
        ),
        "ItemMap": TriplesMap(
            name="ItemMap",
            source=src,
            subject=TermMap(template="http://ex.org/item/{id}"),
            subject_class="http://ex.org/vocab/Item",
            poms=(
                PredicateObjectMap(
                    predicate="http://ex.org/vocab/label",
                    object_map=TermMap(reference="label"),
                ),
                PredicateObjectMap(
                    predicate="http://ex.org/vocab/ownedBy",
                    object_map=RefObjectMap(
                        parent_triples_map="OwnerMap",
                        joins=(JoinCondition(child="owner_id", parent="oid"),),
                    ),
                ),
            ),
        ),
    }
    doc = MappingDocument(maps)
    doc.validate()
    _assert_roundtrip(doc)
    reparsed = parser.parse(serializer.to_turtle(doc))
    item = reparsed.triples_maps["ItemMap"]
    assert item.source.fmt == "json"
    assert item.source.iterator == "$.items[*]"
    joins = item.poms[1].object_map.joins
    assert joins == (JoinCondition(child="owner_id", parent="oid"),)


def test_join_condition_roundtrip_multiple_parents():
    """Several OJM rules against distinct parents with distinct join columns."""
    child = LogicalSource(path="child.csv")
    maps = {}
    poms = []
    for i in range(3):
        pname = f"Parent{i}"
        maps[pname] = TriplesMap(
            name=pname,
            source=LogicalSource(path=f"parent{i}.csv"),
            subject=TermMap(template=f"http://ex.org/p{i}/{{K{i}}}"),
        )
        poms.append(
            PredicateObjectMap(
                predicate=f"http://ex.org/vocab/rel{i}",
                object_map=RefObjectMap(
                    parent_triples_map=pname,
                    joins=(JoinCondition(child=f"fk{i}", parent=f"K{i}"),),
                ),
            )
        )
    maps["Child"] = TriplesMap(
        name="Child",
        source=child,
        subject=TermMap(template="http://ex.org/c/{ID}"),
        poms=tuple(poms),
    )
    doc = MappingDocument(maps)
    doc.validate()
    _assert_roundtrip(doc)


def test_object_map_kinds_roundtrip():
    """template / reference / constant object maps, multi-column templates,
    and a subject map without a class."""
    tm = TriplesMap(
        name="T",
        source=LogicalSource(path="t.tsv", fmt="tsv"),
        subject=TermMap(template="http://ex.org/{A}/{B}"),
        poms=(
            PredicateObjectMap(
                predicate="http://ex.org/vocab/tpl",
                object_map=TermMap(template="http://ex.org/val/{C}"),
            ),
            PredicateObjectMap(
                predicate="http://ex.org/vocab/ref",
                object_map=TermMap(reference="D"),
            ),
            PredicateObjectMap(
                predicate="http://ex.org/vocab/const-iri",
                object_map=TermMap(constant="http://ex.org/thing"),
            ),
            PredicateObjectMap(
                predicate="http://ex.org/vocab/const-lit",
                object_map=TermMap(constant="a plain literal"),
            ),
        ),
    )
    doc = MappingDocument({"T": tm})
    doc.validate()
    ttl = serializer.to_turtle(doc)
    doc2 = parser.parse(ttl)
    # fmt "tsv" has no referenceFormulation of its own (serialized as ql:CSV);
    # everything else must survive exactly
    t2 = doc2.triples_maps["T"]
    assert t2.subject == tm.subject
    assert t2.poms == tm.poms
    assert parser.parse(serializer.to_turtle(doc2)).triples_maps == doc2.triples_maps
