"""Sharded KG ingestion — rendered triples -> N ``.kgz`` stores + manifest.

:func:`ingest_sharded` partitions rendered triples by subject hash
(:mod:`repro.shard.partition`), then builds and saves each shard store
in turn, in the calling process: that process holds the accelerator, and
each build's index sort runs there.  Each shard encodes with its **own
term dictionary** (term ids are ranks of rendered terms, so no
cross-shard id coordination is needed — rendered terms are the shared
key space).  Only after every shard store is on disk does it merge the
shards' term statistics into the manifest's ``dictionary`` section and
write the manifest, so a manifest on disk always names complete,
loadable shards.
"""

from __future__ import annotations

import os

from repro.kg import persist
from repro.shard.partition import PARTITION_SPEC, partition_triples


def shard_paths(manifest_path: str, n_shards: int) -> "list[str]":
    """The shard store filenames a manifest at ``manifest_path`` governs:
    ``kg.shards.json`` -> ``kg.shard0.kgz`` ... next to it."""
    base = os.path.basename(manifest_path)
    for suffix in (".shards.json", ".json"):
        if base.endswith(suffix):
            base = base[: -len(suffix)]
            break
    return [f"{base}.shard{i}.kgz" for i in range(n_shards)]


def _build_shard(bucket: list, path: str) -> dict:
    """Build one shard store from its triple bucket and save it."""
    from repro.kg.store import TripleStore

    store = TripleStore.from_ntriples(bucket)
    sid = persist.save(store, path)
    return {
        "n_triples": store.n_triples,
        "n_terms": store.n_terms,
        "snapshot_id": sid,
        "generation": 0,
    }


def ingest_sharded(triples, manifest_path: str, n_shards: int) -> dict:
    """Partition rendered ``(s, p, o)`` triples into ``n_shards`` stores
    next to ``manifest_path``, build/save them, and write the manifest
    once every shard is durable.  Returns the manifest dict (as loaded,
    with relative shard paths)."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    triples = [tuple(t) for t in triples]
    buckets = partition_triples(triples, n_shards)
    out_dir = os.path.dirname(os.path.abspath(manifest_path))
    os.makedirs(out_dir, exist_ok=True)
    rel_paths = shard_paths(manifest_path, n_shards)
    stats = [
        _build_shard(bucket, os.path.join(out_dir, rel))
        for bucket, rel in zip(buckets, rel_paths)
    ]
    # every shard .kgz exists: merge the per-shard term dictionaries'
    # stats and only now publish the manifest
    union_terms = set()
    for s, p, o in triples:
        union_terms.add(s)
        union_terms.add(p)
        union_terms.add(o)
    manifest = {
        "format": persist.MANIFEST_FORMAT,
        "n_shards": n_shards,
        "partition": dict(PARTITION_SPEC),
        "shards": [
            {"path": rel, **st} for rel, st in zip(rel_paths, stats)
        ],
        "dictionary": {
            "n_terms_union": len(union_terms),
            "n_terms_shards": sum(st["n_terms"] for st in stats),
            "n_triples": sum(st["n_triples"] for st in stats),
        },
    }
    persist.save_manifest(manifest_path, manifest)
    return manifest


def rendered_triples(store) -> "list[tuple[str, str, str]]":
    """Render a TripleStore back to ``(s, p, o)`` term-string tuples — the
    dictionary-independent form the shards exchange.  Each distinct term
    is rendered once."""
    terms = [store.decode_term(i) for i in range(store.n_terms)]
    return [
        (terms[s], terms[p], terms[o])
        for s, p, o in zip(
            store.s.tolist(), store.p.tolist(), store.o.tolist()
        )
    ]


def shard_store(store, manifest_path: str, n_shards: int) -> dict:
    """Partition an already-built :class:`~repro.kg.store.TripleStore`
    into a sharded KG on disk (the ``rdfize --shards N`` tail end)."""
    return ingest_sharded(rendered_triples(store), manifest_path, n_shards)
