"""Plain references: the KG a testbed should give, and readers for what
the program wrote.  Nothing here imports the program.

The OJM KG: every child row's mutation is joined to every parent row
with the same accession number, once per join map, plus the class
triples of every mutation and of every exon map's subjects.  The SOM
KG: one class triple and one literal per mapped column for each
distinct child row.  Both are sets: a duplicate source row gives no
second triple.
"""

from __future__ import annotations

import dataclasses
import zipfile

import numpy as np

import testbed as T


@dataclasses.dataclass
class KG:
    """A graph as sorted distinct rendered terms and int32 id columns;
    term ids are ranks of rendered strings."""

    terms: np.ndarray
    s: np.ndarray
    p: np.ndarray
    o: np.ndarray

    def __len__(self) -> int:
        return len(self.s)

    def lines(self) -> np.ndarray:
        """Each triple as ``"<s> <p> <o>"``."""
        t = self.terms.astype(object)
        return (t[self.s] + " " + t[self.p] + " " + t[self.o]).astype(str)

    def term_id(self, term: str) -> int | None:
        i = int(np.searchsorted(self.terms, term))
        return i if i < len(self.terms) and self.terms[i] == term else None


def _iris(prefix: str, ids: np.ndarray) -> np.ndarray:
    return np.array([f"<{T.BASE}{prefix}{r}>" for r in ids.tolist()])


def _kg(chunks) -> KG:
    """``chunks``: triples as (subject, predicate, object) columns, each
    column a (term table, indexes into it) pair; the tables may overlap.
    Only the tables are sorted, never a column of strings per triple."""
    tables: dict = {}
    for chunk in chunks:
        for table, _idx in chunk:
            tables.setdefault(id(table), table)
    offsets, at = {}, 0
    for key, table in tables.items():
        offsets[key] = at
        at += len(table)
    every = np.concatenate(list(tables.values()))
    terms, inv = np.unique(every, return_inverse=True)
    inv = inv.astype(np.int32)
    cols = []
    for pos in range(3):
        cols.append(np.concatenate([
            inv[offsets[id(chunk[pos][0])] + chunk[pos][1]] for chunk in chunks
        ]).astype(np.int32))
    return KG(terms, *cols)


def _const(term: str, n: int):
    return (np.array([term]), np.zeros(n, np.int64))


def _rows(table: T.Table, distinct: bool) -> "tuple[np.ndarray, np.ndarray]":
    """A table's (row identity, accession) pairs: each distinct row once,
    or every row as the source holds it."""
    if not distinct:
        return table.row, table.enst
    rows, first = np.unique(table.row, return_index=True)
    return rows, table.enst[first]


def join_pairs(tb: T.Testbed, distinct: bool = True) -> "tuple[np.ndarray, np.ndarray]":
    """(child row, parent row) identity pairs that share an accession
    number: each distinct pair once, or once per pair of source rows."""
    crow, censt = _rows(tb.child, distinct)
    prow, penst = _rows(tb.parent, distinct)
    order = np.argsort(penst, kind="stable")
    sorted_enst = penst[order]
    lo = np.searchsorted(sorted_enst, censt, side="left")
    hi = np.searchsorted(sorted_enst, censt, side="right")
    counts = hi - lo
    child_at = np.repeat(np.arange(len(crow)), counts)
    starts = np.repeat(lo - np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    parent_at = order[starts + np.arange(len(child_at))]
    return crow[child_at], prow[parent_at]


def ojm_kg(tb: T.Testbed, distinct: bool = True) -> KG:
    crow = np.unique(tb.child.row)
    prow = np.unique(tb.parent.row)
    jc, jp = join_pairs(tb, distinct)
    jc, jp = np.searchsorted(crow, jc), np.searchsorted(prow, jp)
    mut = _iris("mutation/MUTATION_ID_", crow)
    cls_rows = np.searchsorted(crow, _rows(tb.child, distinct)[0])
    chunks = [((mut, cls_rows), _const(T.RDF_TYPE, len(cls_rows)),
               _const(f"<{T.BASE}vocab/Mutation>", len(cls_rows)))]
    exon_rows = np.searchsorted(prow, _rows(tb.parent, distinct)[0])
    for i in range(1, tb.n_poms + 1):
        exon = _iris(f"exon{i}/EXON_ID_", prow)
        chunks.append(((mut, jc), _const(f"<{T.BASE}vocab/in_exon_{i}>", len(jc)), (exon, jp)))
        chunks.append(((exon, exon_rows), _const(T.RDF_TYPE, len(exon_rows)),
                       _const(f"<{T.BASE}vocab/Exon>", len(exon_rows))))
    return _kg(chunks)


def som_kg(tb: T.Testbed, distinct: bool = True) -> KG:
    rows, enst = _rows(tb.child, distinct)
    table = T.Table(tb.child.columns, rows, enst)
    at = np.arange(len(rows))
    subj = (_iris("mutation/MUTATION_ID_", rows), at)
    chunks = [(subj, _const(T.RDF_TYPE, len(at)), _const(f"<{T.BASE}vocab/Mutation>", len(at)))]
    for col in T.som_columns(tb.n_poms):
        lits = np.array([f'"{escape_literal(v)}"' for v in table.cell_strings(col)])
        chunks.append((subj, _const(f"<{T.BASE}vocab/{col.lower()}>", len(at)), (lits, at)))
    return _kg(chunks)


def reference_kg(tb: T.Testbed, distinct: bool = True) -> KG:
    """The testbed's KG; with ``distinct=False``, the bag that every
    source row gives, duplicate rows included (the control)."""
    return ojm_kg(tb, distinct) if tb.kind == "OJM" else som_kg(tb, distinct)


# -- reading back what the program wrote ------------------------------------

_ECHAR = {"\\": "\\\\", '"': '\\"', "\n": "\\n", "\r": "\\r", "\t": "\\t",
          "\b": "\\b", "\f": "\\f"}


def escape_literal(body: str) -> str:
    """N-Triples STRING_LITERAL_QUOTE escaping (W3C N-Triples §2.4)."""
    out = []
    for ch in body:
        if ch in _ECHAR:
            out.append(_ECHAR[ch])
        elif ord(ch) < 0x20 or ord(ch) == 0x7F:
            out.append(f"\\u{ord(ch):04X}")
        else:
            out.append(ch)
    return "".join(out)


def read_nt(path: str) -> "list[str]":
    """N-Triples lines as ``"<s> <p> <o>"`` (the closing `` .`` dropped)."""
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    out = []
    for ln in lines:
        if not ln.strip():
            continue
        if not ln.endswith(" ."):
            raise ValueError(f"{path}: not an N-Triples line: {ln[:80]!r}")
        out.append(ln[:-2])
    return out


def read_kgz(path: str) -> "list[str]":
    """The triples of a ``.kgz`` snapshot, rendered from the file alone.

    The file is an uncompressed zip of ``.npy`` members: ``dict_blob`` and
    ``dict_off`` hold the dictionary's utf-8 strings end to end; a term is
    a (pattern, value) pair of dictionary ids (``term_pat``,
    ``term_val``); a pattern is ``iri:`` or ``lit:`` and a body in which
    ``{}`` slots take the value's ``\\x1f``-separated parts; ``s``, ``p``
    and ``o`` are the triples' term ids."""
    with zipfile.ZipFile(path) as zf:
        def member(name):
            with zf.open(name + ".npy") as f:
                return np.lib.format.read_array(f, allow_pickle=False)

        blob = member("dict_blob").tobytes()
        off = member("dict_off")
        pat, val = member("term_pat"), member("term_val")
        s, p, o = member("s"), member("p"), member("o")
    starts = np.concatenate([[0], off[:-1]])
    strings = [blob[a:b].decode("utf-8") for a, b in zip(starts.tolist(), off.tolist())]
    terms = []
    for pid, vid in zip(pat.tolist(), val.tolist()):
        kind, body = strings[pid].split(":", 1)
        if "{}" in body:
            parts = strings[vid].split("\x1f")
            chunks = body.split("{}")
            body = "".join(
                c + (parts[i] if i < len(parts) else "")
                for i, c in enumerate(chunks[:-1])
            ) + chunks[-1]
        terms.append(f"<{body}>" if kind == "iri" else f'"{escape_literal(body)}"')
    t = np.array(terms, dtype=object)
    return list(t[s] + " " + t[p] + " " + t[o])


def compare(got: "list[str]", want: np.ndarray) -> "dict[str, int]":
    """Multiset comparison of a written KG with the reference set:
    triples missing, triples extra, and copies beyond the first."""
    arr = np.asarray(got, dtype=str) if got else np.zeros(0, dtype=want.dtype)
    uniq, counts = np.unique(arr, return_counts=True)
    want = np.unique(want)
    return {
        "missing": int(len(np.setdiff1d(want, uniq, assume_unique=True))),
        "extra": int(len(np.setdiff1d(uniq, want, assume_unique=True))),
        "duplicates": int(np.sum(counts - 1)),
    }
