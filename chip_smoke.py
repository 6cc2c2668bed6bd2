#!/usr/bin/env python3
"""Bring-up smoke test: ingestion and serving, end to end, on a TPU.

    python chip_smoke.py                # one chip: ingest (OJM, SOM) + serve
    python chip_smoke.py --four-chips   # four chips: sharded serving only

Everything runs in this one process — a chip belongs to one process at a
time — through the entry points a user calls:

1. ``device``: JAX must report a TPU; there is no CPU branch.
2. ``ingest_ojm``: the paper's hardest testbed (OJM: 1M child and 1M
   parent rows, 75% duplicates, two join predicate-object maps, ~8M
   candidate triples) through ``rdfize --emit kgz``; the KG's rendered
   triples must equal a host dict-join of the CSVs written here.
3. ``ingest_som``: SOM (1M rows, 25% duplicates, four maps) through
   ``rdfize --stream``; it must equal ``rdfize --engine naive``.
4. ``serve``: a ``KGServer`` over the OJM ``.kgz`` in a thread, queried
   through ``repro.api.connect("host:port")``: batch-1 chains of 1-3
   patterns, a concurrent burst of 64 same-signature queries, OPTIONAL +
   FILTER, UNION, GROUP BY-COUNT, and insert/query/delete/query/compact.
   Every answer must equal ``serve.oracle.oracle_select``, and the
   small-batch fast path must have dispatched.

``--four-chips`` runs the device check, builds the OJM KG as set-up,
splits it into four shard stores and serves them behind a
``Coordinator``, shard ``i`` on ``jax.devices()[i]``; the serve phase's
queries must answer byte-identically to the unsharded store.

Each phase prints a JSON line (wall and compile seconds, triple counts);
the last line is ``{"ok": true, "device": {...}}``.  Any failure or
mismatch exits non-zero without it.  Files go under ``--out``.
"""

from __future__ import annotations

import argparse
import collections
import csv
import json
import os
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the platform the smoke test demands (a test steers this to "cpu")
PLATFORM = "tpu"
# testbed rows: the paper's largest scale (a test steers this down)
ROWS = 1_000_000
SEED = 0
RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"


class SmokeFailure(RuntimeError):
    """A phase's answer differs from its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts its read), summed from JAX's monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0
        self._lock = threading.Lock()
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration


class Phase:
    """Times one phase on the wall clock and on the compile clock."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self) -> dict:
        self.t0 = time.perf_counter()
        self.c0 = self.clock.seconds
        self.record = {"phase": self.name}
        return self.record

    def __exit__(self, *exc) -> None:
        if exc[0] is None:
            self.record["wall_s"] = time.perf_counter() - self.t0
            self.record["compile_s"] = self.clock.seconds - self.c0
            emit(self.record)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    emit({"phase": "device", **info})
    if info["platform"] != PLATFORM:
        raise SmokeFailure(f"JAX found no {PLATFORM} (platform "
                           f"{info['platform']!r}); nothing to smoke-test")
    if info["count"] < n_chips:
        raise SmokeFailure(f"needs {n_chips} devices, JAX found {info['count']}")
    return info


def write_testbed(kind: str, rows: int, dup: float, n_poms: int,
                  out_dir: str) -> str:
    from repro.rml import generator, serializer

    tb = generator.make_testbed(kind, rows, dup, n_poms=n_poms, seed=SEED)
    tb.write(out_dir)
    mapping = os.path.join(out_dir, "mapping.ttl")
    serializer.write_turtle(tb.doc, mapping)
    return mapping


def rdfize(*argv: str) -> None:
    from repro.launch import rdfize as cli

    cli.main(list(argv))


def _read_csv(path: str, cols: "tuple[str, ...]"):
    with open(path, newline="", encoding="utf-8") as f:
        reader = csv.reader(f)
        header = next(reader)
        idx = [header.index(c) for c in cols]
        for row in reader:
            yield tuple(row[i] for i in idx)


def ojm_reference(data_dir: str, n_poms: int) -> "set[tuple[str, str, str]]":
    """The OJM testbed's KG from its CSVs, by a plain dict join: every
    child row's mutation joins every parent row sharing its
    ACCESSION_NUMBER, once per join map; plus the class triples of the
    child map and of every parent (exon) map."""
    from repro.rml.generator import BASE

    exons_of = collections.defaultdict(set)
    for acc, exon in _read_csv(os.path.join(data_dir, "parent.csv"),
                               ("ACCESSION_NUMBER", "EXON_ID")):
        exons_of[acc].add(exon)
    children = set(_read_csv(os.path.join(data_dir, "child.csv"),
                             ("MUTATION_ID", "ACCESSION_NUMBER")))
    mutation_cls = f"<{BASE}vocab/Mutation>"
    exon_cls = f"<{BASE}vocab/Exon>"
    ref: set = set()
    for mid, acc in children:
        s = f"<{BASE}mutation/{mid}>"
        ref.add((s, RDF_TYPE, mutation_cls))
        for i in range(1, n_poms + 1):
            pred = f"<{BASE}vocab/in_exon_{i}>"
            for exon in exons_of.get(acc, ()):
                ref.add((s, pred, f"<{BASE}exon{i}/{exon}>"))
    for exons in exons_of.values():
        for exon in exons:
            for i in range(1, n_poms + 1):
                ref.add((f"<{BASE}exon{i}/{exon}>", RDF_TYPE, exon_cls))
    return ref


def ingest_ojm_phase(out: str, rows: int, clock: CompileClock,
                     verify: bool = True):
    """OJM testbed -> ``rdfize --emit kgz``; returns (kgz path, store,
    rendered triples)."""
    from repro.kg import persist
    from repro.shard.ingest import rendered_triples

    data = os.path.join(out, "ojm")
    with Phase("ingest_ojm", clock) as rec:
        mapping = write_testbed("OJM", rows, 0.75, 2, data)
        kgz = os.path.join(data, "kg.kgz")
        t0 = time.perf_counter()
        rdfize("--mapping", mapping, "--data-root", data, "--out", kgz,
               "--emit", "kgz")
        rec["rdfize_s"] = time.perf_counter() - t0
        store = persist.load(kgz)
        triples = rendered_triples(store)
        rec["triples"] = len(triples)
        if verify:
            ref = ojm_reference(data, 2)
            rec["reference_triples"] = len(ref)
            check(len(set(triples)) == len(triples), "OJM KG holds duplicates")
            check(set(triples) == ref,
                  f"OJM KG != host join: {len(set(triples) - ref)} extra, "
                  f"{len(ref - set(triples))} missing")
    return kgz, store, triples


def _nt_lines(path: str) -> "list[str]":
    with open(path, encoding="utf-8") as f:
        return f.read().splitlines()


def ingest_som_phase(out: str, rows: int, clock: CompileClock) -> None:
    data = os.path.join(out, "som")
    with Phase("ingest_som", clock) as rec:
        mapping = write_testbed("SOM", rows, 0.25, 4, data)
        streamed = os.path.join(data, "stream.nt")
        naive = os.path.join(data, "naive.nt")
        t0 = time.perf_counter()
        rdfize("--mapping", mapping, "--data-root", data, "--out", streamed,
               "--stream")
        rec["stream_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        rdfize("--mapping", mapping, "--data-root", data, "--out", naive,
               "--engine", "naive")
        rec["naive_s"] = time.perf_counter() - t0
        a, b = _nt_lines(streamed), _nt_lines(naive)
        rec["triples"] = len(a)
        rec["naive_triples"] = len(b)
        check(len(set(a)) == len(a), "streamed SOM KG holds duplicates")
        check(set(a) == set(b) and len(a) == len(b),
              "streamed SOM KG != naive engine")


class _Rendered:
    """A fixed triple list behind the hook ``oracle_select`` reads a live
    store's triples through, so the oracle renders the graph only once."""

    def __init__(self, triples):
        self._triples = triples

    def rendered_triples(self):
        return self._triples


def serve_queries(triples) -> dict:
    """The serve phase's query mix, drawn from the graph itself: chain
    queries of 1-3 patterns, the burst, and the general-path shapes.
    Every query is anchored on a constant, so answers stay small."""
    from repro.rml.generator import BASE

    in1, in2 = f"<{BASE}vocab/in_exon_1>", f"<{BASE}vocab/in_exon_2>"
    # mutations with both join predicates, in a fixed order
    by_subject: dict = {}
    for s, p, o in triples:
        if p in (in1, in2):
            by_subject.setdefault(s, {}).setdefault(p, o)
    anchors = sorted(s for s, ps in by_subject.items() if len(ps) == 2)
    check(len(anchors) >= 16, "graph too small for the serve phase")
    m0 = anchors[0]
    e0 = by_subject[m0][in1]
    f0 = by_subject[m0][in2]
    chains = [
        f"SELECT * WHERE {{ {m0} {in1} ?e }}",
        f"SELECT * WHERE {{ ?m {in1} {e0} . ?m {in2} ?f }}",
        f"SELECT * WHERE {{ ?m {in1} {e0} . ?m {in2} ?f . ?m {RDF_TYPE} ?c }}",
    ]
    burst = [
        f"SELECT * WHERE {{ {anchors[i % 16]} {in1} ?e }}" for i in range(64)
    ]
    general = [
        f"SELECT * WHERE {{ ?m {in1} {e0} OPTIONAL {{ ?m {in2} ?f }} "
        f"FILTER(?f != {f0}) }}",
        f"SELECT * WHERE {{ {{ {m0} {in1} ?x }} UNION {{ {m0} {in2} ?x }} }}",
        f"SELECT ?p (COUNT(*) AS ?n) WHERE {{ ?m ?p {e0} }} GROUP BY ?p",
    ]
    new_s = f"<{BASE}mutation/chip_smoke_insert>"
    write = (new_s, in1, e0)
    return {"chains": chains, "burst": burst, "general": general,
            "write": write, "write_query": f"SELECT * WHERE {{ {new_s} ?p ?o }}"}


def _rows(res) -> "list[tuple]":
    return [tuple(r) for r in res.rows]


class Oracle:
    """``oracle_select`` over the graph's rendered triples, memoised per
    query text (the burst repeats texts)."""

    def __init__(self, triples):
        self.triples = triples
        self._memo: dict = {}

    def rows(self, text: str, extra=()) -> "list[tuple]":
        from repro.serve import oracle_select, parse_select

        key = (text, tuple(extra))
        if key not in self._memo:
            graph = _Rendered(self.triples + list(extra))
            self._memo[key] = oracle_select(graph, parse_select(text))
        return self._memo[key]


def answer_matches(res, want: "list[tuple]", text: str) -> None:
    check(res.n_total == len(want) and _rows(res) == want,
          f"answer != oracle for {text!r}: got {res.n_total} rows "
          f"{_rows(res)[:3]}..., oracle {len(want)} rows {want[:3]}...")


def _burst(address: str, texts: "list[str]") -> list:
    """Send ``texts`` at once, one connection each; returns the results."""
    from repro import api

    sessions = [api.connect(address, timeout=300.0) for _ in texts]
    results: list = [None] * len(texts)
    errors: list = []
    gate = threading.Barrier(len(texts))

    def run(i: int) -> None:
        try:
            gate.wait(timeout=60)
            results[i] = sessions[i].query(texts[i])
        except Exception as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(texts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    for s in sessions:
        s.close()
    check(not any(t.is_alive() for t in threads), "burst did not finish")
    if errors:
        raise errors[0]
    return results


def run_reads(address: str, qs: dict, expect) -> dict:
    """Drive the serve mix's queries through ``repro.api`` at ``address``;
    ``expect(text, extra)`` gives the reference rows."""
    from repro import api

    batch_sizes = []
    with api.connect(address, timeout=600.0) as sess:
        for text in qs["chains"] + qs["general"]:
            answer_matches(sess.query(text), expect(text, ()), text)
    for text, res in zip(qs["burst"], _burst(address, qs["burst"])):
        answer_matches(res, expect(text, ()), text)
        batch_sizes.append(res.batch_size)
    return {
        "queries": len(qs["chains"]) + len(qs["general"]) + len(qs["burst"]),
        "burst_max_batch": max(batch_sizes),
    }


def run_writes(address: str, qs: dict, expect) -> None:
    """insert -> query -> delete -> query -> compact -> query."""
    from repro import api

    write, wq = qs["write"], qs["write_query"]
    with api.connect(address, timeout=600.0) as sess:
        check(sess.insert([write])["inserted"] == 1, "insert not applied")
        answer_matches(sess.query(wq), expect(wq, (write,)), wq)
        check(sess.delete([write])["deleted"] == 1, "delete not applied")
        answer_matches(sess.query(wq), expect(wq, ()), wq)
        sess.compact()
        for text in (wq, qs["chains"][0]):
            answer_matches(sess.query(text), expect(text, ()), text)


def serve_phase(kgz: str, triples, clock: CompileClock) -> None:
    from repro.kg import persist
    from repro.live.delta import LiveStore
    from repro.obs import get_registry
    from repro.serve.server import KGServer

    reg = get_registry()
    oracle = Oracle(triples)
    with Phase("serve", clock) as rec:
        qs = serve_queries(triples)
        fast0 = reg.counter("exec.fastpath_dispatches").value
        t0 = time.perf_counter()
        server = KGServer(
            LiveStore(persist.load(kgz)), port=0, log=False, kg_path=kgz,
            warmup=True,
        ).start()
        rec["start_s"] = time.perf_counter() - t0
        address = f"127.0.0.1:{server.port}"
        try:
            rec.update(run_reads(address, qs, oracle.rows))
            run_writes(address, qs, oracle.rows)
        finally:
            server.stop()
        rec["fastpath_dispatches"] = (
            reg.counter("exec.fastpath_dispatches").value - fast0
        )
        rec["triples"] = len(triples)
        check(rec["fastpath_dispatches"] > 0, "the fast path never dispatched")


def four_chip_phase(out: str, store, triples, clock: CompileClock) -> None:
    """Four shard stores, one per chip, behind a ``Coordinator``; every
    answer must equal the unsharded store's, byte for byte."""
    import jax

    from repro import api
    from repro.live.delta import LiveStore
    from repro.shard.coordinator import Coordinator
    from repro.shard.ingest import ingest_sharded

    devices = jax.devices()
    with Phase("four_chips", clock) as rec:
        manifest = os.path.join(out, "ojm", "kg.shards.json")
        m = ingest_sharded(triples, manifest, 4)
        rec["shard_triples"] = [s["n_triples"] for s in m["shards"]]
        qs = serve_queries(triples)
        # the reference: the unsharded store, taking the same writes
        unsharded = api.connect(LiveStore(store))

        def expect(text: str, extra) -> "list[tuple]":
            if not extra:
                return _rows(unsharded.query(text))
            unsharded.insert(list(extra))
            try:
                return _rows(unsharded.query(text))
            finally:
                unsharded.delete(list(extra))

        coord = Coordinator.from_manifest(manifest, port=0, log=False).start()
        address = f"127.0.0.1:{coord.port}"
        try:
            rec.update(run_reads(address, qs, expect))
            placed = []
            for i, srv in enumerate(coord._servers):
                on = {a.device for a in jax.tree.leaves(srv.store._dev)
                      if isinstance(a, jax.Array)}
                placed.append(sorted(str(d) for d in on))
                check(srv.store.device == devices[i] and on == {devices[i]},
                      f"shard {i} arrays on {on}, want {devices[i]}")
            rec["placement"] = placed
            run_writes(address, qs, expect)
        finally:
            coord.stop()
        unsharded.close()


def peak_bytes() -> "int | None":
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving path on four chips")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for testbeds and KGs")
    args = ap.parse_args(argv)

    try:
        from repro.jaxcache import enable_compile_cache

        emit({"phase": "compile_cache", "dir": enable_compile_cache()})
        clock = CompileClock()
        info = device_phase(4 if args.four_chips else 1)
        os.makedirs(args.out, exist_ok=True)
        t0 = time.perf_counter()
        if args.four_chips:
            _kgz, store, triples = ingest_ojm_phase(
                args.out, ROWS, clock, verify=False
            )
            four_chip_phase(args.out, store, triples, clock)
        else:
            kgz, _store, triples = ingest_ojm_phase(args.out, ROWS, clock)
            ingest_som_phase(args.out, ROWS, clock)
            serve_phase(kgz, triples, clock)
        emit({"phase": "total", "wall_s": time.perf_counter() - t0,
              "compile_s": clock.seconds, "device_kind": info["kind"],
              "peak_bytes_in_use": peak_bytes()})
    except Exception as e:  # noqa: BLE001 — every failure ends the run non-zero
        traceback.print_exc()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"})
        return 1
    emit({"ok": True, "device": info})
    return 0


if __name__ == "__main__":
    sys.exit(main())
