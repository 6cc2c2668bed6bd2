"""Shared RDF term rendering — N-Triples escaping and (pattern, value) decode.

A term leaves the engine as a *(pattern id, value id)* pair into the global
:class:`~repro.data.encoder.Dictionary`; this module is the single place that
turns the pair back into a concrete N-Triples term string.  It is shared by
``core.executor`` (the N-Triples dump, through the batch form
:func:`render_terms`) and ``repro.kg`` (query-time binding
decode), so both emit byte-identical — and *valid* — N-Triples: literals get
full string escaping (backslash, quote, and control characters), not just
``"``.  It lives beside the encoder in ``repro.data`` so the dependency DAG
stays one-directional (``data`` ← ``core`` ← ``kg``).
"""

from __future__ import annotations

import re

import numpy as np

from repro.data.encoder import _SEP, Dictionary, render_template

# RFC 3987 iunreserved (ALPHA / DIGIT / "-" / "." / "_" / "~" / ucschar), and
# the multi-column separator, which render_template splits on
_IUNRESERVED = (
    "A-Za-z0-9\\-._~\x1f\u00a0-\ud7ff\uf900-\ufdcf\ufdf0-\uffef"
    + "".join(f"\\U{p:04x}0000-\\U{p:04x}fffd" for p in range(1, 14))
    + "\\U000e1000-\\U000efffd"
)
_IRI_UNSAFE = re.compile(f"[^{_IUNRESERVED}]")

# N-Triples ECHAR escapes; everything else in the forbidden range goes \uXXXX.
_ESCAPES = {
    "\\": "\\\\",
    '"': '\\"',
    "\n": "\\n",
    "\r": "\\r",
    "\t": "\\t",
    "\b": "\\b",
    "\f": "\\f",
}
_NEEDS_ESCAPE = re.compile(r'[\x00-\x1f"\\\x7f]')
_UNESCAPE = re.compile(r"\\(u[0-9A-Fa-f]{4}|U[0-9A-Fa-f]{8}|.)")
_ECHAR_INV = {v[1]: k for k, v in _ESCAPES.items()}  # 'n' -> '\n', ...


def escape_literal(s: str) -> str:
    """Escape a raw string for an N-Triples STRING_LITERAL_QUOTE body."""
    if not _NEEDS_ESCAPE.search(s):
        return s

    def repl(m: re.Match) -> str:
        ch = m.group(0)
        e = _ESCAPES.get(ch)
        return e if e is not None else f"\\u{ord(ch):04X}"

    return _NEEDS_ESCAPE.sub(repl, s)


def unescape_literal(s: str) -> str:
    """Inverse of :func:`escape_literal` (accepts any valid ECHAR/UCHAR)."""

    def repl(m: re.Match) -> str:
        body = m.group(1)
        if body[0] in "uU":
            return chr(int(body[1:], 16))
        return _ECHAR_INV.get(body, body)

    return _UNESCAPE.sub(repl, s)


def iri_safe(value: str) -> str:
    """R2RML §7.3's IRI-safe form: every character outside RFC 3987
    ``iunreserved`` percent-encoded as UTF-8 octets.  A value that needs no
    encoding is returned as it is after one regex scan."""
    unsafe = set(_IRI_UNSAFE.findall(value))
    # '%' first: the codes that replace the others bring '%' in
    for ch in sorted(unsafe, key=lambda c: c != "%"):
        value = value.replace(ch, "".join(f"%{b:02X}" for b in ch.encode("utf-8")))
    return value


def _iri_safe_all(values: list[str]) -> list[str]:
    """:func:`iri_safe` of each value, as one pass over the values joined
    by a newline, which encodes to ``%0A``: a ``%0A`` of the output can
    only be a separator, since a value's own ``%`` becomes ``%25``."""
    joined = "\n".join(values)
    if joined.count("\n") != len(values) - 1:  # a value holds a newline
        return [iri_safe(v) for v in values]
    return iri_safe(joined).split("%0A")


def render_term(d: Dictionary, pat_id: int, val_id: int) -> str:
    """(pattern id, value id) -> concrete N-Triples term (``<iri>``,
    ``"literal"`` or ``"literal"^^<datatype>``).  Patterns are the
    planner's namespaced strings; ``{}`` slots take the dictionary value."""
    kind, pattern = d.decode_scalar(pat_id).split(":", 1)
    if kind == "typ":
        datatype, pattern = pattern.split(" ", 1)
    if "{}" in pattern:
        value = d.decode_scalar(val_id)
        pattern = render_template(pattern, iri_safe(value) if kind == "iri" else value)
    if kind in ("iri", "ref"):
        return f"<{pattern}>"
    if kind == "typ":
        return f'"{escape_literal(pattern)}"^^<{datatype}>'
    return '"' + escape_literal(pattern) + '"'


def render_terms(d: Dictionary, pat_ids: np.ndarray, val_ids: np.ndarray) -> list[str]:
    """:func:`render_term` of each ``(pat_ids[i], val_ids[i])`` pair, in
    order.  Each pattern is decoded and split once, and its values are
    filled in one pass: the writer's way of rendering a KG's distinct
    terms."""
    pat_ids, val_ids = np.asarray(pat_ids), np.asarray(val_ids)
    strings = d.strings()
    out = np.empty(len(pat_ids), dtype=object)
    order = np.argsort(pat_ids, kind="stable")
    cuts = np.flatnonzero(np.diff(pat_ids[order])) + 1
    for rows in np.split(order, cuts) if len(order) else ():
        kind, pattern = strings[pat_ids[rows[0]]].split(":", 1)
        if kind == "typ":
            datatype, pattern = pattern.split(" ", 1)
        chunks = pattern.split("{}")
        if len(chunks) == 1:
            out[rows] = render_term(d, int(pat_ids[rows[0]]), 0)
            continue
        values = [strings[v] for v in val_ids[rows].tolist()]
        if kind == "iri":
            values = _iri_safe_all(values)
        if len(chunks) == 2:
            head, tail = chunks
            filled = [head + v + tail if _SEP not in v else render_template(pattern, v)
                      for v in values]
        else:
            filled = [render_template(pattern, v) for v in values]
        if kind in ("iri", "ref"):
            out[rows] = [f"<{s}>" for s in filled]
        elif kind == "typ":
            out[rows] = [f'"{escape_literal(s)}"^^<{datatype}>' for s in filled]
        else:
            out[rows] = ['"' + escape_literal(s) + '"' for s in filled]
    return out.tolist()


def split_typed(token: str) -> "tuple[str, str] | None":
    """``'"body"^^<datatype>'`` -> (raw body, datatype); None for any
    other token."""
    at = token.rfind('"^^<')
    if not token.startswith('"') or at < 1 or not token.endswith(">"):
        return None
    return unescape_literal(token[1:at]), token[at + 4:-1]


def canonical_term(token: str) -> str:
    """Normalize a user-supplied constant term (``<iri>``, a quoted
    literal, possibly with escapes, or a typed literal ``"v"^^<dt>``) to the
    exact string :func:`render_term` produces, so it can key a
    rendered-term lookup."""
    token = token.strip()
    if token.startswith("<") and token.endswith(">"):
        return token
    typed = split_typed(token)
    if typed is not None:
        return f'"{escape_literal(typed[0])}"^^<{typed[1]}>'
    if token.startswith('"') and token.endswith('"') and len(token) >= 2:
        return '"' + escape_literal(unescape_literal(token[1:-1])) + '"'
    raise ValueError(f"not an N-Triples term: {token!r}")
