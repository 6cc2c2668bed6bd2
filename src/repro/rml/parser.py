"""Parser for the RML turtle subset used by the paper's mappings.

Supports the constructs exercised in the paper's Figure 1 / testbeds:
``@prefix``, triples maps with ``rml:logicalSource``, ``rr:subjectMap``
(template + class), ``rr:predicateObjectMap`` with plain object maps
(``rr:template`` / ``rml:reference`` / ``rr:constant``), referencing object
maps (``rr:parentTriplesMap``) with any number of ``rr:joinCondition``s
(``rr:child`` / ``rr:parent``, all of which a joined pair meets),
``rr:datatype`` and ``rr:termType`` ``rr:IRI`` / ``rr:Literal``.
Blank-node property lists, ``;``/``,`` lists, IRIs, prefixed names and
string literals are handled by a small recursive-descent parser — enough
to round-trip every mapping in the bundled testbeds.

What the engine does not implement is refused with a ``SyntaxError`` that
names the term, never dropped: ``rr:language``, ``rr:termType
rr:BlankNode``, ``rr:graphMap``, and a second value of a single-valued
term (``rr:class``, ``rr:predicate``, ``rr:objectMap``, ``rr:datatype``,
...).
"""

from __future__ import annotations

import re

from repro.rml.model import (
    IRI,
    LITERAL,
    JoinCondition,
    LogicalSource,
    MappingDocument,
    PredicateObjectMap,
    RefObjectMap,
    TermMap,
    TriplesMap,
)

_TOKEN = re.compile(
    r"""
    (?P<comment>\#[^\n]*)
  | (?P<iri><[^>]*>)
  | (?P<string>"(?:[^"\\]|\\.)*")
  | (?P<punct>[\[\];,.])
  | (?P<prefixed>[A-Za-z_][\w\-]*:[\w\-./#]*)
  | (?P<kw>@prefix|a)
  | (?P<ws>\s+)
""",
    re.VERBOSE,
)


def _tokenize(text: str) -> list[str]:
    toks, pos = [], 0
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m:
            raise SyntaxError(f"RML parse error at: {text[pos:pos+40]!r}")
        pos = m.end()
        if m.lastgroup in ("ws", "comment"):
            continue
        toks.append(m.group())
    return toks


class _Parser:
    def __init__(self, toks: list[str]):
        self.toks = toks
        self.i = 0
        self.prefixes: dict[str, str] = {}

    def peek(self) -> str | None:
        return self.toks[self.i] if self.i < len(self.toks) else None

    def next(self) -> str:
        tok = self.toks[self.i]
        self.i += 1
        return tok

    def expect(self, tok: str) -> None:
        got = self.next()
        if got != tok:
            raise SyntaxError(f"expected {tok!r}, got {got!r}")

    # -- term expansion -----------------------------------------------------
    def expand(self, tok: str) -> str:
        if tok.startswith("<") and tok.endswith(">"):
            return tok[1:-1]
        if tok.startswith('"') and tok.endswith('"'):
            return tok[1:-1].encode().decode("unicode_escape")
        if ":" in tok:
            pfx, local = tok.split(":", 1)
            if pfx in self.prefixes:
                return self.prefixes[pfx] + local
        return tok

    # -- grammar ------------------------------------------------------------
    def parse(self) -> MappingDocument:
        maps: dict[str, TriplesMap] = {}
        while self.peek() is not None:
            if self.peek() == "@prefix":
                self.next()
                name = self.next().rstrip(":")
                iri = self.expand(self.next())
                self.expect(".")
                self.prefixes[name] = iri
            else:
                tm = self.parse_triples_map()
                maps[tm.name] = tm
        doc = MappingDocument(triples_maps=maps)
        doc.validate()
        return doc

    def parse_triples_map(self) -> TriplesMap:
        name_tok = self.next()
        name = name_tok[1:-1] if name_tok.startswith("<") else name_tok
        name = name.lstrip("#")
        props = self.parse_property_list()
        self.expect(".")
        return self.build_triples_map(name, props)

    def parse_property_list(self) -> list[tuple[str, object]]:
        """predicate object (',' object)* (';' predicate ...)*"""
        props: list[tuple[str, object]] = []
        while True:
            nxt = self.peek()
            if nxt in (None, ".", "]"):
                break
            pred_tok = self.next()
            pred = "rdf:type" if pred_tok == "a" else pred_tok
            while True:
                obj = self.parse_object()
                props.append((pred, obj))
                if self.peek() == ",":
                    self.next()
                    continue
                break
            if self.peek() == ";":
                self.next()
                continue
            break
        return props

    def parse_object(self):
        tok = self.peek()
        if tok == "[":
            self.next()
            inner = self.parse_property_list()
            self.expect("]")
            return inner
        return self.next()

    # -- model construction ---------------------------------------------------
    def _get(self, props, *keys):
        out = []
        for p, v in props:
            local = p.split(":", 1)[-1].lstrip("<").rstrip(">").split("#")[-1].split("/")[-1]
            if local in keys:
                out.append(v)
        return out

    def _one(self, props, key: str, where: str):
        """The value of a single-valued term, None when absent; two or more
        values are refused."""
        vals = self._get(props, key)
        if len(vals) > 1:
            raise SyntaxError(f"{where}: more than one rr:{key} is not supported")
        return vals[0] if vals else None

    def _refuse(self, props, where: str, *keys: str) -> None:
        for key in keys:
            if self._get(props, key):
                raise SyntaxError(f"{where}: rr:{key} is not supported")

    def build_term_map(self, props, where: str, subject: bool = False) -> TermMap:
        self._refuse(props, where, "language", "languageMap", "graphMap", "graph")
        given = [(k, self._one(props, k, where)) for k in ("template", "reference", "constant")]
        given = [(k, v) for k, v in given if v is not None]
        if len(given) != 1:
            raise SyntaxError(
                f"{where}: a term map needs exactly one of template/reference/constant"
            )
        kind, value = given[0]
        term_type = self._one(props, "termType", where)
        if term_type is not None:
            local = self.expand(term_type).rsplit("#", 1)[-1]
            if local not in ("IRI", "Literal"):
                raise SyntaxError(f"{where}: rr:termType rr:{local} is not supported")
            term_type = IRI if local == "IRI" else LITERAL
        elif subject and kind == "reference":
            term_type = IRI  # R2RML §7.4: a subject map gives IRIs by default
        datatype = self._one(props, "datatype", where)
        try:
            return TermMap(
                **{kind: self.expand(value)}, term_type=term_type,
                datatype=None if datatype is None else self.expand(datatype),
            )
        except ValueError as e:
            raise SyntaxError(f"{where}: {e}") from None

    def build_triples_map(self, name: str, props) -> TriplesMap:
        ls_props = self._get(props, "logicalSource")[0]
        src_tok = self._get(ls_props, "source")[0]
        fmt = "csv"
        rf = self._get(ls_props, "referenceFormulation")
        if rf and "JSON" in str(rf[0]).upper():
            fmt = "json"
        elif rf and "TSV" in str(rf[0]).upper():
            fmt = "tsv"  # ql:TSV — tab-delimited, same reader, different split
        iterator = None
        it = self._get(ls_props, "iterator")
        if it:
            iterator = self.expand(it[0])
        source = LogicalSource(path=self.expand(src_tok), fmt=fmt, iterator=iterator)

        self._refuse(props, name, "graphMap", "graph")
        sm_props = self._one(props, "subjectMap", name)
        if sm_props is None:
            raise SyntaxError(f"{name}: no rr:subjectMap")
        subject = self.build_term_map(sm_props, f"{name} subject map", subject=True)
        cls = self._one(sm_props, "class", f"{name} subject map")
        subject_class = self.expand(cls) if cls else None

        poms = []
        for pom_props in self._get(props, "predicateObjectMap"):
            where = f"{name} predicateObjectMap"
            self._refuse(pom_props, where, "graphMap", "graph")
            pred = self._one(pom_props, "predicate", where)
            om_props = self._one(pom_props, "objectMap", where)
            if pred is None or om_props is None:
                raise SyntaxError(f"{where}: needs rr:predicate and rr:objectMap")
            pred = self.expand(pred)
            where = f"{name} object map of <{pred}>"
            parent = self._one(om_props, "parentTriplesMap", where)
            if parent:
                pname = str(parent)
                pname = (pname[1:-1] if pname.startswith("<") else pname).lstrip("#")
                joins = []
                for jc in self._get(om_props, "joinCondition"):
                    child = self._one(jc, "child", where)
                    par = self._one(jc, "parent", where)
                    if child is None or par is None:
                        raise SyntaxError(
                            f"{where}: a rr:joinCondition needs rr:child and rr:parent"
                        )
                    joins.append(JoinCondition(self.expand(child), self.expand(par)))
                obj: TermMap | RefObjectMap = RefObjectMap(
                    parent_triples_map=pname, joins=tuple(joins)
                )
            else:
                obj = self.build_term_map(om_props, where)
            poms.append(PredicateObjectMap(predicate=pred, object_map=obj))

        try:
            return TriplesMap(
                name=name,
                source=source,
                subject=subject,
                subject_class=subject_class,
                poms=tuple(poms),
            )
        except ValueError as e:
            raise SyntaxError(str(e)) from None


def parse(text: str) -> MappingDocument:
    return _Parser(_tokenize(text)).parse()


def parse_file(path: str) -> MappingDocument:
    with open(path, encoding="utf-8") as f:
        return parse(f.read())
