"""Serialize a MappingDocument back to RML turtle (round-trips the parser)."""

from __future__ import annotations

from repro.rml.model import IRI, MappingDocument, RefObjectMap, TermMap, TriplesMap

_PREFIXES = """\
@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .

"""


def _term(om: TermMap, indent: str) -> str:
    if om.template is not None:
        out = f'{indent}rr:template "{om.template}"'
    elif om.reference is not None:
        out = f'{indent}rml:reference "{om.reference}"'
    else:
        out = f'{indent}rr:constant "{om.constant}"'
    if om.term_type is not None:
        out += " ; rr:termType " + ("rr:IRI" if om.term_type == IRI else "rr:Literal")
    if om.datatype is not None:
        out += f" ; rr:datatype <{om.datatype}>"
    return out


def _triples_map(tm: TriplesMap) -> str:
    ql = "ql:JSONPath" if tm.source.fmt == "json" else "ql:CSV"
    lines = [f"<#{tm.name}> a rr:TriplesMap ;"]
    src = f'    rml:logicalSource [ rml:source "{tm.source.path}" ; rml:referenceFormulation {ql}'
    if tm.source.iterator:
        src += f' ; rml:iterator "{tm.source.iterator}"'
    lines.append(src + " ] ;")
    subj = f"    rr:subjectMap [ {_term(tm.subject, '').strip()}"
    if tm.subject_class:
        subj += f" ; rr:class <{tm.subject_class}>"
    lines.append(subj + " ]" + (" ;" if tm.poms else " ."))
    for i, pom in enumerate(tm.poms):
        last = i == len(tm.poms) - 1
        om = pom.object_map
        if isinstance(om, RefObjectMap):
            inner = f"rr:parentTriplesMap <#{om.parent_triples_map}>"
            for jc in om.joins:
                inner += (
                    f' ; rr:joinCondition [ rr:child "{jc.child}" ;'
                    f' rr:parent "{jc.parent}" ]'
                )
        else:
            inner = _term(om, "").strip()
        lines.append(
            f"    rr:predicateObjectMap [ rr:predicate <{pom.predicate}> ;"
            f" rr:objectMap [ {inner} ] ]" + (" ." if last else " ;")
        )
    return "\n".join(lines) + "\n"


def to_turtle(doc: MappingDocument) -> str:
    return _PREFIXES + "\n".join(_triples_map(tm) for tm in doc.triples_maps.values())


def write_turtle(doc: MappingDocument, path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write(to_turtle(doc))
