"""Knowledge-graph creation driver — the SDM-RDFizer CLI.

    PYTHONPATH=src python -m repro.launch.rdfize \
        --mapping mappings.ttl --data-root data/ --out kg.nt \
        [--engine optimized|naive] [--join sorted|hash] \
        [--stream] [--block-rows N] [--emit nt|kgz] \
        [--explain-mapping] [--no-mapping-plan]

``--emit kgz`` writes a queryable ``repro.kg`` triple-store snapshot
(dictionary + SPO/POS/OSP indexes) instead of N-Triples text; serve it with
``python -m repro.launch.query --kg out.kgz '?s <p> ?o'``.

``--stream`` runs the optimized engine on the ``repro.stream`` block
subsystem: sources are read in ``--block-rows``-row chunks through a lazy
Dataset plan (read -> project -> encode -> batch) with bounded prefetch, so
the KG can exceed host RAM.  Output is identical to the eager engine.

Every run goes through the mapping-level planner (:mod:`repro.rml.plan`)
unless ``--no-mapping-plan``: projections are pushed into the streamed
reads, shared subject/join templates are evaluated once, and rules execute
group-by-group along the plan's DAG.  ``--explain-mapping`` prints the
planner's decisions as a tree — kept/pruned columns per source, factored
terms, rule groups — and exits without building anything.  With
``--shards N`` the KG is hash-partitioned into N ``.kgz`` shard stores
plus a manifest at ``--out``.

Mirrors the paper's tool: parse the RML document, plan, execute with the
PTT/PJTT operators, emit N-Triples, print the per-predicate φ statistics.
"""

from __future__ import annotations

import argparse


def _print_stats(stats) -> None:
    for pred, st in stats.items():
        print(
            f"  {st.kind:5s} {pred.rsplit('/', 1)[-1]:30s} "
            f"|N_p|={st.n_candidates:>9d} |S_p|={st.n_unique:>9d} "
            f"phi={int(st.phi_optimized()):>12d} "
            f"phi_naive={int(st.phi_naive()):>14d}"
        )


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mapping", required=True)
    ap.add_argument("--data-root", default=".")
    ap.add_argument("--out", default=None, help="N-Triples output path")
    ap.add_argument("--engine", default="optimized", choices=("optimized", "naive"))
    ap.add_argument("--join", default="sorted", choices=("sorted", "hash"))
    ap.add_argument("--batch-size", type=int, default=1 << 16)
    ap.add_argument("--stream", action="store_true",
                    help="block-streamed out-of-core ingestion (repro.stream)")
    ap.add_argument("--block-rows", type=int, default=1 << 14,
                    help="rows per streamed block (with --stream)")
    ap.add_argument("--explain-mapping", action="store_true",
                    help="print the mapping planner's decisions (kept/"
                         "pruned columns, factored terms, rule groups) "
                         "and exit without building the KG")
    ap.add_argument("--no-mapping-plan", action="store_true",
                    help="disable the mapping-level planner (no "
                         "projection pushdown, no shared-template "
                         "factoring, single flat rule group)")
    ap.add_argument("--emit", default="nt", choices=("nt", "kgz"),
                    help="output format: N-Triples text or a queryable "
                         "repro.kg .kgz snapshot")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="with --emit kgz: partition the KG by subject "
                         "hash into N shard stores plus a manifest at "
                         "--out (serve it with launch.serve, query it "
                         "with repro.api.connect)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Chrome trace-event JSON of the run "
                         "(per-block read/project/encode spans with "
                         "--stream; open in Perfetto / chrome://tracing)")
    args = ap.parse_args(argv)

    from repro.jaxcache import enable_compile_cache

    enable_compile_cache()

    from repro import obs
    from repro.core.executor import create_kg
    from repro.rml import parser

    if args.explain_mapping:
        from repro import api

        print(api.explain_mapping(args.mapping, data_root=args.data_root))
        return
    if args.trace:
        obs.enable_tracing()
    with obs.span("parse_mapping", cat="rdfize", path=args.mapping):
        doc = parser.parse_file(args.mapping)
    print(f"[rdfize] {len(doc.triples_maps)} triples maps from {args.mapping}")
    mapping_plan = not args.no_mapping_plan
    if mapping_plan:
        from repro.rml.plan import build_plan

        mplan = build_plan(doc)
        print(f"[rdfize] plan: {len(mplan.exec_plan.ops)} rules over "
              f"{len(mplan.sources)} sources -> {len(mplan.groups)} "
              f"groups ({len(mplan.shared)} shared terms factored)")
    if args.shards and args.emit != "kgz":
        ap.error("--shards needs --emit kgz (shard stores are .kgz snapshots)")

    with obs.span("create_kg", cat="rdfize", engine=args.engine,
                  stream=args.stream):
        result = create_kg(
            doc,
            data_root=args.data_root,
            engine=args.engine,
            join_strategy=args.join,
            batch_size=args.batch_size,
            stream=args.stream,
            block_rows=args.block_rows,
            mapping_plan=mapping_plan,
        )
    print(f"[rdfize] {result.n_triples} unique triples in "
          f"{result.wall_time_s:.2f}s ({result.engine} engine)")
    _print_stats(result.stats)
    if args.out:
        if args.emit == "kgz" and args.shards:
            from repro.shard.ingest import shard_store

            with obs.span("emit_sharded", cat="rdfize", out=args.out,
                          shards=args.shards):
                store = result.to_store()
                manifest = shard_store(store, args.out, args.shards)
            sizes = ", ".join(
                str(s["n_triples"]) for s in manifest["shards"]
            )
            print(f"[rdfize] wrote {store.n_triples}-triple sharded KG "
                  f"({args.shards} shards: {sizes} triples) — manifest "
                  f"at {args.out}")
        elif args.emit == "kgz":
            from repro.kg import persist

            with obs.span("emit_kgz", cat="rdfize", out=args.out):
                store = result.to_store()
                persist.save(store, args.out)
            print(f"[rdfize] wrote {store.n_triples}-triple .kgz snapshot "
                  f"({store.n_terms} terms) to {args.out}")
        else:
            with obs.span("emit_nt", cat="rdfize", out=args.out):
                n = result.write_ntriples(args.out)
            print(f"[rdfize] wrote {n} triples to {args.out}")
    if args.trace:
        n_ev = obs.save_trace(args.trace)
        print(f"[rdfize] wrote {n_ev}-event trace to {args.trace}")


if __name__ == "__main__":
    main()
