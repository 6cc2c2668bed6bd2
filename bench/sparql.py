"""A plain SPARQL-subset evaluator: the reference answers for the serve
mixes.  It parses the query text itself and evaluates it over a
``reference.KG`` with sorted-array lookups; nothing here imports the
program.

Subset: ``SELECT`` ``*`` or variables and ``(COUNT(*|?v) AS ?n)``;
``WHERE`` with triple patterns, one ``{..} UNION {..}`` block,
``OPTIONAL {..}`` groups and ``FILTER (a = b | a != b)`` over IRIs and
variables; ``GROUP BY``, ``ORDER BY`` (``ASC``/``DESC``) and ``LIMIT``.

Semantics, as the served system states them for untyped IRIs: UNION arms
are a bag joined with the required patterns; OPTIONAL is a left join;
a comparison over an unbound variable is false; rows are ordered per
column (unbound first, then rendered term, counts by value), and ORDER
BY keys, applied last key first as stable sorts, reverse the whole key
for DESC (unbound last).
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

_TOKEN = re.compile(
    r'\s*(?:(?P<iri><[^>\s]*>)|(?P<var>\?\w+)|(?P<num>\d+)'
    r'|(?P<op>!=|=|[{}().*])|(?P<word>[A-Za-z]+))'
)


def _tokens(text: str) -> "list[tuple[str, str]]":
    out, pos = [], 0
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m or m.end() == pos:
            raise ValueError(f"cannot read query at {text[pos:pos + 30]!r}")
        kind = m.lastgroup
        val = m.group(kind)
        out.append((kind, val.upper() if kind == "word" else val))
        pos = m.end()
        while pos < len(text) and text[pos].isspace():
            pos += 1
    return out


@dataclasses.dataclass
class Query:
    select: "list[str] | None"          # None: SELECT *
    count: "tuple[str | None, str] | None"  # (counted var or None for *, alias)
    patterns: list
    unions: list
    optionals: list
    filters: list                        # (op, lhs, rhs)
    group_by: "list[str]"
    order_by: "list[tuple[str, bool]]"  # (var, ascending)
    limit: "int | None"

    def scope(self) -> "list[str]":
        seen: list = []
        for pats in [self.patterns, *self.unions, *self.optionals]:
            for pat in pats:
                for t in pat:
                    if t.startswith("?") and t not in seen:
                        seen.append(t)
        return seen

    def out_vars(self) -> "list[str]":
        return self.scope() if self.select is None else self.select


class _Reader:
    def __init__(self, text: str):
        self.toks = _tokens(text)
        self.i = 0

    def peek(self, k: int = 0):
        j = self.i + k
        return self.toks[j] if j < len(self.toks) else (None, None)

    def next(self):
        tok = self.peek()
        if tok[0] is None:
            raise ValueError("query ends too early")
        self.i += 1
        return tok

    def expect(self, kind: str, val: "str | None" = None) -> str:
        k, v = self.next()
        if k != kind or (val is not None and v != val):
            raise ValueError(f"expected {val or kind}, read {v!r}")
        return v

    def take(self, kind: str, val: str) -> bool:
        if self.peek() == (kind, val):
            self.i += 1
            return True
        return False


def _triples(r: _Reader) -> list:
    """Triple patterns up to a closing brace (which is consumed)."""
    pats = []
    while not r.take("op", "}"):
        pat = []
        for _ in range(3):
            k, v = r.next()
            if k not in ("iri", "var"):
                raise ValueError(f"expected a term, read {v!r}")
            pat.append(v)
        pats.append(tuple(pat))
        r.take("op", ".")
    return pats


def parse(text: str) -> Query:
    r = _Reader(text)
    r.expect("word", "SELECT")
    select, count = None, None
    if not r.take("op", "*"):
        select = []
        while r.peek()[0] == "var" or r.peek() == ("op", "("):
            if r.take("op", "("):
                r.expect("word", "COUNT")
                r.expect("op", "(")
                counted = None if r.take("op", "*") else r.expect("var")
                r.expect("op", ")")
                r.expect("word", "AS")
                alias = r.expect("var")
                r.expect("op", ")")
                count = (counted, alias)
                select.append(alias)
            else:
                select.append(r.next()[1])
    r.expect("word", "WHERE")
    r.expect("op", "{")
    q = Query(select, count, [], [], [], [], [], [], None)
    while not r.take("op", "}"):
        if r.take("word", "OPTIONAL"):
            r.expect("op", "{")
            q.optionals.append(_triples(r))
        elif r.take("word", "FILTER"):
            r.expect("op", "(")
            lhs = r.next()[1]
            op = r.expect("op")
            if op not in ("=", "!="):
                raise ValueError(f"unsupported comparison {op!r}")
            q.filters.append((op, lhs, r.next()[1]))
            r.expect("op", ")")
        elif r.take("op", "{"):
            q.unions.append(_triples(r))
            while r.take("word", "UNION"):
                r.expect("op", "{")
                q.unions.append(_triples(r))
        else:
            pat = tuple(r.next()[1] for _ in range(3))
            q.patterns.append(pat)
            r.take("op", ".")
    if r.take("word", "GROUP"):
        r.expect("word", "BY")
        while r.peek()[0] == "var":
            q.group_by.append(r.next()[1])
    if r.take("word", "ORDER"):
        r.expect("word", "BY")
        while True:
            if r.peek()[0] == "var":
                q.order_by.append((r.next()[1], True))
            elif r.peek()[1] in ("ASC", "DESC"):
                asc = r.next()[1] == "ASC"
                r.expect("op", "(")
                q.order_by.append((r.expect("var"), asc))
                r.expect("op", ")")
            else:
                break
    if r.take("word", "LIMIT"):
        q.limit = int(r.expect("num"))
    if r.peek()[0] is not None:
        raise ValueError(f"unread text from {r.peek()[1]!r}")
    return q


class Graph:
    """A ``reference.KG`` with subject-, predicate- and object-sorted row
    orders for lookups.  ``cap`` holds every pattern's matches to its
    first ``cap`` rows (the control's broken completeness)."""

    def __init__(self, kg, cap: "int | None" = None):
        self.kg = kg
        self.cap = cap
        self.cols = (kg.s, kg.p, kg.o)
        self.orders = []
        self.keys = []
        for c in self.cols:
            order = np.argsort(c, kind="stable")
            self.orders.append(order)
            self.keys.append(c[order])

    def match(self, pat, env: dict) -> "list[dict]":
        """Extensions of ``env`` by the rows matching ``pat``."""
        bound = []
        for pos, t in enumerate(pat):
            if t.startswith("?"):
                bound.append(env.get(t))
            else:
                tid = self.kg.term_id(t)
                if tid is None:
                    return []
                bound.append(tid)
        pos = next((i for i in (0, 2, 1) if bound[i] is not None), None)
        if pos is None:
            rows = np.arange(len(self.kg))
        else:
            k = self.keys[pos]
            lo = np.searchsorted(k, bound[pos], side="left")
            hi = np.searchsorted(k, bound[pos], side="right")
            rows = self.orders[pos][lo:hi]
        for i, b in enumerate(bound):
            if b is not None and i != pos:
                rows = rows[self.cols[i][rows] == b]
        if self.cap is not None:
            rows = rows[: self.cap]
        out = []
        vals = [c[rows].tolist() for c in self.cols]
        for j in range(len(rows)):
            e = dict(env)
            ok = True
            for i, t in enumerate(pat):
                if t.startswith("?"):
                    v = vals[i][j]
                    if e.get(t, v) != v:
                        ok = False
                        break
                    e[t] = v
            if ok:
                out.append(e)
        return out

    def bgp(self, pats, envs: "list[dict]") -> "list[dict]":
        for pat in pats:
            envs = [e2 for e in envs for e2 in self.match(pat, e)]
        return envs


def _const(graph: Graph, term: str):
    return graph.kg.term_id(term) if term.startswith("<") else term


def _filter_ok(graph: Graph, f, env: dict) -> bool:
    op, lhs, rhs = f
    a = env.get(lhs) if lhs.startswith("?") else _const(graph, lhs)
    b = env.get(rhs) if rhs.startswith("?") else _const(graph, rhs)
    if (lhs.startswith("?") and a is None) or (rhs.startswith("?") and b is None):
        return False
    return (a == b) if op == "=" else (a != b)


def evaluate(graph: Graph, q: Query) -> "tuple[list[str], list[tuple]]":
    """(output variables, rows) with terms rendered and counts as ints."""
    sols = graph.bgp(q.patterns, [{}])
    if q.unions:
        sols = [e2 for e in sols for arm in q.unions for e2 in graph.bgp(arm, [e])]
    for group in q.optionals:
        joined = []
        for e in sols:
            hits = graph.bgp(group, [e])
            joined.extend(hits if hits else [e])
        sols = joined
    sols = [e for e in sols if all(_filter_ok(graph, f, e) for f in q.filters)]
    out_vars = q.out_vars()
    terms = graph.kg.terms
    if q.count is not None or q.group_by:
        groups: dict = {}
        for e in sols:
            groups.setdefault(tuple(e.get(v) for v in q.group_by), []).append(e)
        if not q.group_by and not groups:
            groups[()] = []
        rows = []
        for key, members in groups.items():
            by_key = dict(zip(q.group_by, key))
            row = []
            for v in out_vars:
                if q.count is not None and v == q.count[1]:
                    counted = q.count[0]
                    row.append(len(members) if counted is None else
                               sum(1 for m in members if m.get(counted) is not None))
                else:
                    tid = by_key.get(v)
                    row.append(None if tid is None else str(terms[tid]))
            rows.append(tuple(row))
    else:
        rows = [tuple(None if e.get(v) is None else str(terms[e[v]])
                      for v in out_vars) for e in sols]
    return out_vars, order_rows(q, out_vars, rows)


def _cell_key(cell):
    if cell is None:
        return (0, 0, "")
    if isinstance(cell, int):
        return (1, cell, "")
    return (1, 0, cell)


def _order_key(cell):
    """ORDER BY's total order over IRIs and counts (the mixes order no
    literals): unbound, then counts by value and IRIs by rendered term."""
    if cell is None:
        return (-1, 0, "")
    if isinstance(cell, int):
        return (0, cell, "")
    if not cell.startswith("<"):
        raise ValueError("the reference orders no literals")
    return (0, 0, cell)


def order_rows(q: Query, out_vars, rows: list) -> list:
    rows = sorted(rows, key=lambda r: tuple(_cell_key(c) for c in r))
    for var, asc in reversed(q.order_by):
        i = out_vars.index(var)
        rows.sort(key=lambda r: _order_key(r[i]), reverse=not asc)
    return rows[: q.limit] if q.limit is not None else rows
