"""repro.stream: Block/Dataset semantics, chunked readers, glob sharding,
and the streamed-engine equivalence guarantee (stream == optimized == naive).
"""

import json
import os

import numpy as np
import pytest

from repro.core.executor import create_kg
from repro.data import pipeline
from repro.data.sources import load_json
from repro.rml import generator
from repro.stream import Dataset, read_csv, read_json, read_source
from repro.stream.block import Block


def _write(path, text):
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)


# ------------------------------------------------------------- block basics


def test_block_select_fills_missing_columns():
    b = Block({"A": np.array(["1", "2"], object)})
    s = b.select(("A", "B"))
    assert list(s.columns["B"]) == ["", ""]
    assert s.schema == ("A", "B")


def test_block_from_records_unions_keys():
    b = Block.from_records([{"a": 1}, {"b": 2}, {"a": 3, "b": 4}])
    assert sorted(b.schema) == ["a", "b"]
    assert list(b.columns["a"]) == ["1", "", "3"]
    assert list(b.columns["b"]) == ["", "2", "4"]


# ------------------------------------------------- block-boundary coverage


def test_empty_csv_source(tmp_path):
    _write(tmp_path / "e.csv", "A,B\n")
    ds = read_csv(str(tmp_path / "e.csv"), block_rows=4)
    assert ds.count() == 0
    assert list(ds.iter_blocks()) == []


def test_headerless_empty_file(tmp_path):
    _write(tmp_path / "none.csv", "")
    assert read_csv(str(tmp_path / "none.csv")).count() == 0


@pytest.mark.parametrize("n,block_rows", [(3, 8), (8, 8), (16, 8), (17, 8), (1, 1)])
def test_csv_block_sizes(tmp_path, n, block_rows):
    """Single short block, exact multiples, and a one-row tail."""
    _write(tmp_path / "t.csv", "A\n" + "".join(f"{i}\n" for i in range(n)))
    blocks = list(read_csv(str(tmp_path / "t.csv"), block_rows=block_rows).iter_blocks())
    assert sum(b.n_rows for b in blocks) == n
    assert all(b.n_rows == block_rows for b in blocks[:-1])
    assert 0 < blocks[-1].n_rows <= block_rows
    got = np.concatenate([b.columns["A"] for b in blocks])
    assert list(got) == [str(i) for i in range(n)]


def test_rebatch_across_source_chunks():
    t = {"x": np.arange(25).astype(str).astype(object)}
    sizes = [
        b.n_rows
        for b in Dataset.from_table(t, block_rows=10).batch(4).iter_blocks()
    ]
    assert sizes == [4, 4, 4, 4, 4, 4, 1]


def test_padded_tail_validity_mask(tmp_path):
    """The engine pads the final short block; the mask must cover exactly the
    real rows and reconstruction must round-trip."""
    _write(tmp_path / "t.csv", "A\n" + "".join(f"{i}\n" for i in range(10)))
    ds = read_csv(str(tmp_path / "t.csv"), block_rows=4)
    recon = []
    for block in ds.iter_blocks():
        for batch in pipeline.batches(block.columns, 4):
            assert len(batch.arrays["A"]) == 4  # fixed jit shape
            assert batch.valid.sum() == block.n_rows
            recon.extend(batch.arrays["A"][batch.valid].tolist())
    assert recon == [str(i) for i in range(10)]


def test_take_and_materialize(tmp_path):
    _write(tmp_path / "t.csv", "A\n" + "".join(f"{i}\n" for i in range(9)))
    ds = read_csv(str(tmp_path / "t.csv"), block_rows=2)
    assert ds.take(4).n_rows == 4
    assert ds.materialize().n_rows == 9
    assert ds.schema() == ("A",)


# ------------------------------------------------------------ json reading


def test_json_lines_streamed(tmp_path):
    recs = [{"a": str(i), "b": str(i % 3)} for i in range(11)]
    _write(tmp_path / "t.jsonl", "".join(json.dumps(r) + "\n" for r in recs))
    blocks = list(read_json(str(tmp_path / "t.jsonl"), block_rows=4).iter_blocks())
    assert [b.n_rows for b in blocks] == [4, 4, 3]
    assert list(np.concatenate([b.columns["a"] for b in blocks])) == [
        str(i) for i in range(11)
    ]


def test_json_iterator_expansion(tmp_path):
    recs = [{"items": [{"v": "1"}, {"v": "2"}]}, {"items": [{"v": "3"}]}]
    _write(tmp_path / "t.jsonl", "".join(json.dumps(r) + "\n" for r in recs))
    ds = read_json(str(tmp_path / "t.jsonl"), block_rows=2, iterator="$.items")
    assert list(ds.materialize().columns["v"]) == ["1", "2", "3"]


def test_json_heterogeneous_keys_stream_and_eager_agree(tmp_path):
    """Records with extra/missing fields: the eager loader must union keys
    (the records[0]-only bug) and the streamed reader must match it."""
    recs = [{"a": "1"}, {"a": "2", "b": "x"}, {"b": "y", "c": "z"}]
    _write(tmp_path / "t.jsonl", "".join(json.dumps(r) + "\n" for r in recs))
    eager = load_json(str(tmp_path / "t.jsonl"))
    assert sorted(eager) == ["a", "b", "c"]
    assert list(eager["b"]) == ["", "x", "y"]
    assert list(eager["c"]) == ["", "", "z"]
    streamed = (
        read_json(str(tmp_path / "t.jsonl"), block_rows=2)
        .project("a", "b", "c")
        .materialize()
    )
    for k in ("a", "b", "c"):
        assert list(streamed.columns[k]) == list(eager[k])


# ------------------------------------------------------------ glob sharding


def test_glob_multi_file_sharding(tmp_path):
    for i in range(3):
        _write(tmp_path / f"part{i}.csv", "A,B\n" + f"{i}a,{i}b\n" + f"{i}c,{i}d\n")
    ds = read_source(str(tmp_path / "part*.csv"), fmt="csv", block_rows=2)
    assert ds.count() == 6
    # sorted path order => deterministic row order
    assert list(ds.materialize().columns["A"]) == ["0a", "0c", "1a", "1c", "2a", "2c"]


def test_glob_heterogeneous_schemas_union_on_project(tmp_path):
    _write(tmp_path / "s0.csv", "A,B\n1,2\n")
    _write(tmp_path / "s1.csv", "A,C\n3,4\n")
    ds = read_source(str(tmp_path / "s*.csv"), block_rows=4).project("A", "B", "C")
    m = ds.materialize()
    assert list(m.columns["A"]) == ["1", "3"]
    assert list(m.columns["B"]) == ["2", ""]
    assert list(m.columns["C"]) == ["", "4"]


def test_glob_no_match_raises(tmp_path):
    """A typo'd source path must fail loudly (the eager loader's open()
    would), never produce a silently empty KG."""
    with pytest.raises(FileNotFoundError, match="nope"):
        read_source(str(tmp_path / "nope*.csv")).count()
    with pytest.raises(FileNotFoundError, match="nope"):
        list(read_source(str(tmp_path / "nope*.csv")).iter_blocks())


def test_tsv_reader(tmp_path):
    _write(tmp_path / "t.tsv", "A\tB\n1\tx\n2\ty\n")
    m = read_source(str(tmp_path / "t.tsv"), fmt="tsv").materialize()
    assert list(m.columns["B"]) == ["x", "y"]


def test_read_csv_custom_delimiter(tmp_path):
    _write(tmp_path / "t.txt", "A;B\n1;x\n2;y\n")
    m = read_csv(str(tmp_path / "t.txt"), delimiter=";").materialize()
    assert m.schema == ("A", "B")
    assert list(m.columns["B"]) == ["x", "y"]


def test_strict_project_raises_on_missing_column():
    b = Block({"A": np.array(["1"], object)})
    with pytest.raises(KeyError, match="B"):
        b.select(("A", "B"), fill=None)


def test_stream_missing_mapping_column_fails_like_eager(tmp_path):
    """A mapping referencing a column absent from a fixed-schema CSV must
    fail loudly in stream mode (eager raises KeyError), not silently emit
    empty-term triples."""
    from repro.rml.model import (
        LogicalSource, MappingDocument, PredicateObjectMap, TermMap, TriplesMap,
    )

    _write(tmp_path / "t.csv", "A\n1\n2\n")
    doc = MappingDocument(
        {
            "T": TriplesMap(
                name="T",
                source=LogicalSource(path="t.csv"),
                subject=TermMap(template="http://x/{A}"),
                poms=(
                    PredicateObjectMap(
                        predicate="http://x/p",
                        object_map=TermMap(reference="TYPO_COLUMN"),
                    ),
                ),
            )
        }
    )
    with pytest.raises(KeyError, match="TYPO_COLUMN"):
        create_kg(doc, data_root=str(tmp_path))
    with pytest.raises(KeyError, match="TYPO_COLUMN"):
        create_kg(doc, data_root=str(tmp_path), stream=True, block_rows=2)


def test_stream_missing_json_column_fails_like_eager(tmp_path):
    """Union-fill sources (JSON) tolerate per-record heterogeneity, but a
    column absent from EVERY record is a mapping typo and must fail loudly
    in stream mode too (the eager key-union raises table[c] KeyError)."""
    from repro.rml.model import (
        LogicalSource, MappingDocument, PredicateObjectMap, TermMap, TriplesMap,
    )

    _write(tmp_path / "t.jsonl", '{"a": "1"}\n{"a": "2", "b": "x"}\n')
    doc = MappingDocument(
        {
            "T": TriplesMap(
                name="T",
                source=LogicalSource(path="t.jsonl", fmt="json"),
                subject=TermMap(template="http://x/{a}"),
                poms=(
                    PredicateObjectMap(
                        predicate="http://x/p",
                        object_map=TermMap(reference="TYPO_COLUMN"),
                    ),
                ),
            )
        }
    )
    with pytest.raises(KeyError, match="TYPO_COLUMN"):
        create_kg(doc, data_root=str(tmp_path))
    with pytest.raises(KeyError, match="TYPO_COLUMN"):
        create_kg(doc, data_root=str(tmp_path), stream=True, block_rows=2)
    # partial heterogeneity (column "b" in only some records) stays fine
    doc_ok = MappingDocument(
        {
            "T": TriplesMap(
                name="T",
                source=LogicalSource(path="t.jsonl", fmt="json"),
                subject=TermMap(template="http://x/{a}"),
                poms=(
                    PredicateObjectMap(
                        predicate="http://x/p",
                        object_map=TermMap(reference="b"),
                    ),
                ),
            )
        }
    )
    eager = create_kg(doc_ok, data_root=str(tmp_path)).sorted_ntriples()
    streamed = create_kg(
        doc_ok, data_root=str(tmp_path), stream=True, block_rows=1
    ).sorted_ntriples()
    assert eager == streamed


def test_stream_honors_batch_size(tmp_path):
    """batch_size bounds the jitted device batch even in stream mode
    (blocks are split into padded sub-batches)."""
    tb = generator.make_testbed("SOM", 600, 0.25, n_poms=1, seed=4)
    tb.write(str(tmp_path))
    eager = _kg_lines(tb.doc, str(tmp_path))
    streamed = _kg_lines(
        tb.doc, str(tmp_path), stream=True, block_rows=512, batch_size=64
    )
    assert streamed == eager


# ------------------------------------------- incremental dictionary encode


def test_incremental_encode_ids_stable_across_blocks(tmp_path):
    from repro.data.encoder import Dictionary

    _write(tmp_path / "t.csv", "A\n" + "x\ny\nx\nz\nx\n")
    d = Dictionary()
    blocks = list(
        read_csv(str(tmp_path / "t.csv"), block_rows=2).encode(d).iter_blocks()
    )
    ids = np.concatenate([b.columns["A"] for b in blocks])
    assert ids.dtype == np.int32
    assert ids[0] == ids[2] == ids[4]  # same string -> same id across blocks
    assert len({int(ids[0]), int(ids[1]), int(ids[3])}) == 3
    assert list(d.decode(ids)) == ["x", "y", "x", "z", "x"]


def test_literal_path_with_glob_chars(tmp_path):
    """A path that exists literally is one file even if it contains glob
    metacharacters (would otherwise silently read zero rows)."""
    d = tmp_path / "data[v2]"
    d.mkdir()
    _write(d / "t.csv", "A\n1\n2\n")
    assert read_csv(str(d / "t.csv"), block_rows=4).count() == 2


def test_unconsumed_iterator_starts_no_thread(tmp_path):
    """iter_blocks() results that are never drained must not leak a pump
    thread (it starts lazily on first consumption)."""
    import threading

    _write(tmp_path / "t.csv", "A\n1\n2\n")
    before = threading.active_count()
    it = read_csv(str(tmp_path / "t.csv"), block_rows=1).iter_blocks(prefetch=2)
    assert threading.active_count() == before
    assert sum(b.n_rows for b in it) == 2  # and it still works when drained


def test_invalid_block_rows_rejected(tmp_path):
    _write(tmp_path / "t.csv", "A\n1\n")
    with pytest.raises(ValueError, match="block_rows"):
        read_csv(str(tmp_path / "t.csv"), block_rows=0)
    tb = generator.make_testbed("SOM", 10, 0.25)
    with pytest.raises(ValueError, match="block_rows"):
        create_kg(tb.doc, tables={"csv:child.csv": tb.child}, stream=True,
                  block_rows=-1)


def test_constant_terms_stream_matches_eager(tmp_path):
    """Ops that read NO source columns (constant subject + rr:class, and a
    constant object) must still emit triples in stream mode — a zero-column
    projection would otherwise drop every block's row count."""
    from repro.rml.model import (
        LogicalSource, MappingDocument, PredicateObjectMap, TermMap, TriplesMap,
    )

    _write(tmp_path / "t.csv", "A\n1\n2\n3\n")
    doc = MappingDocument(
        {
            "T": TriplesMap(
                name="T",
                source=LogicalSource(path="t.csv"),
                subject=TermMap(constant="http://x/thing"),
                subject_class="http://x/Class",
                poms=(
                    PredicateObjectMap(
                        predicate="http://x/tag",
                        object_map=TermMap(constant="fixed"),
                    ),
                    PredicateObjectMap(
                        predicate="http://x/a",
                        object_map=TermMap(reference="A"),
                    ),
                ),
            )
        }
    )
    eager = create_kg(doc, data_root=str(tmp_path)).sorted_ntriples()
    streamed = create_kg(
        doc, data_root=str(tmp_path), stream=True, block_rows=2
    ).sorted_ntriples()
    assert streamed == eager
    assert any("x/Class" in t for t in eager)
    assert any('"fixed"' in t for t in eager)


def test_distinct_json_iterators_are_distinct_sources(tmp_path):
    """Two triples maps over the same JSON file with different iterators
    must each see their own record stream — in both engines."""
    from repro.rml.model import (
        LogicalSource, MappingDocument, PredicateObjectMap, TermMap, TriplesMap,
    )

    _write(
        tmp_path / "d.json",
        json.dumps(
            {"people": [{"id": "p1"}, {"id": "p2"}], "orders": [{"oid": "o1"}]}
        )
        + "\n",
    )
    maps = {
        "People": TriplesMap(
            name="People",
            source=LogicalSource(path="d.json", fmt="json", iterator="$.people"),
            subject=TermMap(template="http://x/person/{id}"),
            poms=(
                PredicateObjectMap(
                    predicate="http://x/id", object_map=TermMap(reference="id")
                ),
            ),
        ),
        "Orders": TriplesMap(
            name="Orders",
            source=LogicalSource(path="d.json", fmt="json", iterator="$.orders"),
            subject=TermMap(template="http://x/order/{oid}"),
            poms=(
                PredicateObjectMap(
                    predicate="http://x/oid", object_map=TermMap(reference="oid")
                ),
            ),
        ),
    }
    doc = MappingDocument(maps)
    eager = create_kg(doc, data_root=str(tmp_path)).sorted_ntriples()
    streamed = create_kg(
        doc, data_root=str(tmp_path), stream=True, block_rows=2
    ).sorted_ntriples()
    assert eager == streamed
    assert any("person/p1" in t for t in eager)
    assert any("person/p2" in t for t in eager)
    assert any("order/o1" in t for t in eager)
    assert not any("person/o1" in t or "order/p1" in t for t in eager)


# --------------------------------------------------- end-to-end equivalence


def _kg_lines(doc, data_root, **cfg):
    return create_kg(doc, data_root=data_root, **cfg).sorted_ntriples()


@pytest.mark.parametrize("kind", ["SOM", "ORM", "OJM"])
@pytest.mark.parametrize("dup", [0.25, 0.75])
def test_stream_engine_matches_eager_and_naive(tmp_path, kind, dup):
    tb = generator.make_testbed(kind, 1200, dup, n_poms=2, seed=7)
    tb.write(str(tmp_path))
    eager = _kg_lines(tb.doc, str(tmp_path), engine="optimized")
    naive = _kg_lines(tb.doc, str(tmp_path), engine="naive")
    streamed = _kg_lines(
        tb.doc, str(tmp_path), engine="optimized", stream=True, block_rows=256
    )
    assert streamed == eager == naive
    assert len(streamed) > 0


@pytest.mark.parametrize("block_rows", [64, 1200, 4096])
def test_stream_block_rows_invariance(tmp_path, block_rows):
    """Short blocks, exactly-one-block, and bigger-than-source blocks all
    produce the same KG."""
    tb = generator.make_testbed("OJM", 1200, 0.25, n_poms=1, seed=3)
    tb.write(str(tmp_path))
    eager = _kg_lines(tb.doc, str(tmp_path))
    streamed = _kg_lines(tb.doc, str(tmp_path), stream=True, block_rows=block_rows)
    assert streamed == eager


def test_stream_never_uses_eager_loaders(tmp_path, monkeypatch):
    """Out-of-core guarantee: stream mode must go through the chunked
    readers only — the eager full-table loaders are never invoked."""
    import repro.data.sources as sources

    tb = generator.make_testbed("OJM", 600, 0.25, n_poms=1, seed=5)
    tb.write(str(tmp_path))

    def boom(*a, **k):
        raise AssertionError("eager loader called in stream mode")

    monkeypatch.setattr(sources, "load_csv", boom)
    monkeypatch.setattr(sources, "load_json", boom)
    monkeypatch.setattr(sources, "load", boom)
    res = create_kg(tb.doc, data_root=str(tmp_path), stream=True, block_rows=128)
    assert res.n_triples > 0
    assert res.engine == "stream"


def test_stream_rejects_naive_engine():
    tb = generator.make_testbed("SOM", 50, 0.25)
    with pytest.raises(ValueError, match="stream"):
        create_kg(tb.doc, tables={"csv:child.csv": tb.child},
                  engine="naive", stream=True)


def test_stream_cli_flags(tmp_path, capsys, monkeypatch):
    from repro.launch import rdfize
    from repro.rml import serializer

    tb = generator.make_testbed("SOM", 300, 0.25, n_poms=1)
    tb.write(str(tmp_path))
    serializer.write_turtle(tb.doc, str(tmp_path / "map.ttl"))
    out = tmp_path / "kg.nt"
    monkeypatch.setattr(
        "sys.argv",
        ["rdfize", "--mapping", str(tmp_path / "map.ttl"),
         "--data-root", str(tmp_path), "--out", str(out),
         "--stream", "--block-rows", "128"],
    )
    rdfize.main()
    assert "stream engine" in capsys.readouterr().out
    assert out.read_text().count("\n") > 0


@pytest.mark.slow
def test_stream_100k_acceptance(tmp_path):
    """Acceptance: a 100K-row testbed through create_kg block-at-a-time,
    byte-identical (sorted triples) to the eager optimized engine."""
    tb = generator.make_testbed("SOM", 100_000, 0.75, n_poms=2, seed=1)
    tb.write(str(tmp_path))
    eager = _kg_lines(tb.doc, str(tmp_path))
    streamed = _kg_lines(tb.doc, str(tmp_path), stream=True, block_rows=1 << 13)
    assert streamed == eager
