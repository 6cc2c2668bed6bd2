"""Shared RDF term rendering — N-Triples escaping and (pattern, value) decode.

A term leaves the engine as a *(pattern id, value id)* pair into the global
:class:`~repro.data.encoder.Dictionary`; this module is the single place that
turns the pair back into a concrete N-Triples term string.  It is shared by
``core.executor`` (the N-Triples dump) and ``repro.kg`` (query-time binding
decode), so both emit byte-identical — and *valid* — N-Triples: literals get
full string escaping (backslash, quote, and control characters), not just
``"``.  It lives beside the encoder in ``repro.data`` so the dependency DAG
stays one-directional (``data`` ← ``core`` ← ``kg``).
"""

from __future__ import annotations

import re

from repro.data.encoder import Dictionary, render_template

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


def _pct(m: re.Match) -> str:
    return "".join(f"%{b:02X}" for b in m.group(0).encode("utf-8"))


def iri_safe(value: str) -> str:
    """R2RML §7.3's IRI-safe form: every character outside RFC 3987
    ``iunreserved`` percent-encoded as UTF-8 octets.  A value that needs no
    encoding is returned as it is after one regex scan."""
    if _IRI_UNSAFE.search(value) is None:
        return value
    return _IRI_UNSAFE.sub(_pct, value)


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
