"""The paper's COSMIC-derived testbeds, made from a seed.

A copy of the construction in Iglesias et al., *SDM-RDFizer* (CIKM 2020,
arXiv:2008.07176) §5 (``rml/generator.py``), kept with the benchmark so
that no change to the program can change the data it is measured on: a
child table of mutations and, for OJM, a parent table of exons, each
with a given share of duplicate rows in groups of 20, joined on an ENST
accession number.

The run's seed does not draw a new structure: which rows repeat and how
many rows share an accession number come from the generator at seed 0,
and the seed renames the row identities and accession numbers (seeded
permutations) and shuffles the rows.  So every seed offers the same
sizes, duplicates and join fan-outs, hence the same work, in another
order and under other names; the generator's own draws differ by up to
a third in a 100K job's time from seed to seed.

Rows are kept as integers (a row identity and an accession index); the
CSV writer and the term renderers spell them out.  Same seed, same data.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

BASE = "http://repro.org/"
RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
CHILD_COLUMNS = (
    "MUTATION_ID",
    "GENE_NAME",
    "ACCESSION_NUMBER",
    "GENOMIC_MUTATION_ID",
    "MUTATION_CDS",
    "MUTATION_AA",
    "OMIXCORE_SCORE",
)
PARENT_COLUMNS = ("ACCESSION_NUMBER", "EXON_ID", "EXON_START", "EXON_END")
DUP_GROUP = 20  # the paper: each duplicated value is repeated 20 times


@dataclasses.dataclass
class Table:
    """One source table: ``row[i]`` is row i's identity (duplicate rows
    share it, and every column is a function of it) and ``enst[i]`` its
    accession index."""

    columns: tuple[str, ...]
    row: np.ndarray
    enst: np.ndarray

    def __len__(self) -> int:
        return len(self.row)

    def cell_strings(self, col: str) -> "list[str]":
        if col == "ACCESSION_NUMBER":
            return [f"ENST{e:011d}" for e in self.enst.tolist()]
        if col == "OMIXCORE_SCORE":
            return [f"{(r % 1000) / 1000.0:.3f}" for r in self.row.tolist()]
        return [f"{col}_{r}" for r in self.row.tolist()]

    def write_csv(self, path: str) -> None:
        cols = [self.cell_strings(c) for c in self.columns]
        with open(path, "w", encoding="utf-8") as f:
            f.write(",".join(self.columns) + "\n")
            f.write("\n".join(",".join(cells) for cells in zip(*cols)))
            f.write("\n")


def _dup_rows(n_rows: int, dup_rate: float, rng: np.random.Generator) -> np.ndarray:
    """Row identities, ``dup_rate`` of them duplicates in groups of 20."""
    n_dup = int(round(n_rows * dup_rate))
    n_groups = max(n_dup // DUP_GROUP, 1) if n_dup else 0
    n_uniq = n_rows - n_dup + n_groups  # each group keeps one original
    ids = np.arange(n_uniq, dtype=np.int64)
    if n_groups:
        group_ids = rng.choice(n_uniq, size=n_groups, replace=False)
        # spread the remainder round-robin so the length is exactly n_rows
        rem = n_dup - n_groups * (DUP_GROUP - 1)
        step = 1 if rem >= 0 else -1
        q, r = divmod(abs(rem), n_groups)
        reps = np.full(n_groups, DUP_GROUP - 1 + step * q, dtype=np.int64)
        reps[:r] += step
        ids = np.concatenate([ids, np.repeat(group_ids, reps)])
    rng.shuffle(ids)
    return ids[:n_rows]


def _table(columns, n_rows, dup_rate, seed, n_enst) -> Table:
    rng = np.random.default_rng(seed)
    row = _dup_rows(n_rows, dup_rate, rng)
    enst_of_row = rng.integers(0, n_enst, size=int(row.max()) + 1)
    return Table(columns, row, enst_of_row[row])


@dataclasses.dataclass
class Testbed:
    """A testbed as the configuration states it: ``kind`` is SOM or OJM."""

    kind: str
    n_poms: int
    child: Table
    parent: Table | None

    @property
    def source_rows(self) -> int:
        return len(self.child) + (len(self.parent) if self.parent else 0)

    def write(self, out_dir: str) -> str:
        """Write the CSVs and the RML mapping; returns the mapping path."""
        os.makedirs(out_dir, exist_ok=True)
        self.child.write_csv(os.path.join(out_dir, "child.csv"))
        if self.parent is not None:
            self.parent.write_csv(os.path.join(out_dir, "parent.csv"))
        path = os.path.join(out_dir, "mapping.ttl")
        with open(path, "w", encoding="utf-8") as f:
            f.write(mapping_turtle(self.kind, self.n_poms))
        return path


STRUCTURE_SEED = 0


def _rename(table: Table, rng: np.random.Generator, enst_names: np.ndarray) -> Table:
    """The table with its row identities renamed by a seeded permutation,
    its accession indexes by ``enst_names``, and its rows shuffled."""
    names = rng.permutation(int(table.row.max()) + 1)
    order = rng.permutation(len(table))
    return Table(table.columns, names[table.row][order], enst_names[table.enst][order])


def make(kind: str, rows: int, dup_rate: float, n_poms: int, seed: int) -> Testbed:
    """The paper's SOM or OJM testbed, renamed and shuffled by ``seed``.
    OJM draws its accession numbers from a pool of rows/4, so a child row
    meets about four parent rows."""
    rng = np.random.default_rng([seed, 0x7E57BED])
    if kind == "SOM":
        pool = max(rows // 16, 4)
        child = _table(CHILD_COLUMNS, rows, dup_rate, STRUCTURE_SEED, pool)
        return Testbed(kind, n_poms, _rename(child, rng, rng.permutation(pool)), None)
    if kind == "OJM":
        pool = max(rows // 4, 4)
        child = _table(CHILD_COLUMNS, rows, dup_rate, STRUCTURE_SEED, pool)
        parent = _table(PARENT_COLUMNS, rows, dup_rate, STRUCTURE_SEED + 1, pool)
        enst_names = rng.permutation(pool)
        return Testbed(kind, n_poms, _rename(child, rng, enst_names),
                       _rename(parent, rng, enst_names))
    raise ValueError(f"unknown testbed kind {kind!r}")


_PREFIXES = """@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
"""


def _source(path: str) -> str:
    return (f'rml:logicalSource [ rml:source "{path}" ; '
            f"rml:referenceFormulation ql:CSV ]")


def _subject_map(template: str, cls: str) -> str:
    return (f'rr:subjectMap [ rr:template "{BASE}{template}" ; '
            f"rr:class <{BASE}vocab/{cls}> ]")


def som_columns(n_poms: int) -> "list[str]":
    """The columns an SOM mapping of ``n_poms`` maps references."""
    return [c for c in CHILD_COLUMNS if c != "MUTATION_ID"][:n_poms]


def mapping_turtle(kind: str, n_poms: int) -> str:
    """The RML mapping of the testbed, in Turtle."""
    blocks = [_PREFIXES]
    poms = []
    if kind == "OJM":
        for i in range(1, n_poms + 1):
            blocks.append(
                f"<#ExonMap{i}> a rr:TriplesMap ;\n"
                f"    {_source('parent.csv')} ;\n"
                f"    {_subject_map(f'exon{i}/{{EXON_ID}}', 'Exon')} .\n"
            )
            poms.append(
                f"rr:predicateObjectMap [ rr:predicate <{BASE}vocab/in_exon_{i}> ; "
                f"rr:objectMap [ rr:parentTriplesMap <#ExonMap{i}> ; "
                f'rr:joinCondition [ rr:child "ACCESSION_NUMBER" ; '
                f'rr:parent "ACCESSION_NUMBER" ] ] ]'
            )
    else:
        for col in som_columns(n_poms):
            poms.append(
                f"rr:predicateObjectMap [ rr:predicate <{BASE}vocab/{col.lower()}> ; "
                f'rr:objectMap [ rml:reference "{col}" ] ]'
            )
    body = [_source("child.csv"), _subject_map("mutation/{MUTATION_ID}", "Mutation")]
    blocks.append(
        "<#TriplesMap1> a rr:TriplesMap ;\n    "
        + " ;\n    ".join(body + poms)
        + " .\n"
    )
    return "\n".join(blocks)
