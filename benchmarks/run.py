"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  fig56/*      — paper Figures 5/6: KG-creation wall time, engine vs baseline
                 (derived = naive/optimized speedup)
  opmodel/*    — §III.iv operation-count model (derived = φ̂/φ ratio)
  kernels/*    — Pallas kernel micro-benches vs jnp reference paths
  dedup/*      — dedup_gather traffic/time vs plain gather
  stream/*     — streamed vs eager ingestion (rows/s, peak traced alloc)
  kg/*         — repro.kg store build + batched single-pattern queries/s
  live/*       — repro.live write path, overlay queries vs delta fraction,
                 and compaction (writes BENCH_live.json)
  shard/*      — repro.shard routed vs scatter-all query cost at 1/2/4
                 shards vs the unsharded baseline (writes BENCH_shard.json)
  roofline/*   — (when results/dryrun.json exists) the three terms per cell

The ``stream`` and ``kg`` sections also write machine-readable
``BENCH_stream.json`` / ``BENCH_kg.json`` (to ``--json-dir``, default the
current directory) so the perf trajectory can be tracked across commits.

``--full`` widens fig56 to the paper's 1M-row tier.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _row(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def _write_json(json_dir: str, name: str, payload: dict) -> None:
    path = os.path.join(json_dir, name)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", flush=True)


def bench_fig56(full: bool) -> None:
    from benchmarks import paper_figs

    sizes = (10_000, 100_000, 1_000_000) if full else (10_000, 100_000)
    n_poms = (1, 2, 4) if full else (1, 2)
    for kind in ("SOM", "ORM", "OJM"):
        for n in sizes:
            for dup in (0.25, 0.75):
                for npm in n_poms:
                    opt = paper_figs.run_cell(kind, n, dup, npm, "optimized", repeats=2)
                    nav = paper_figs.run_cell(kind, n, dup, npm, "naive", repeats=2)
                    name = f"fig56/{kind.lower()}{npm}-{n}-{int(dup*100)}"
                    if nav["status"] == "DNF":
                        _row(name, opt["time_s"] * 1e6, "naive=DNF")
                    else:
                        _row(
                            name, opt["time_s"] * 1e6,
                            f"speedup={nav['time_s']/opt['time_s']:.2f}x",
                        )
                    assert (
                        nav["status"] == "DNF"
                        or nav["n_triples"] == opt["n_triples"]
                    ), f"engine mismatch at {name}"


def bench_op_model() -> None:
    from benchmarks import op_model

    for r in op_model.run(sizes=(10_000,), dups=(0.25, 0.75)):
        _row(
            f"opmodel/{r['kind'].lower()}-{r['rows']}-{int(r['dup']*100)}",
            0.0,
            f"phi_ratio={r['ratio']:.1f}x",
        )


def bench_kernels() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import hashing
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    n = 1 << 16
    words = jnp.asarray(rng.integers(0, 2**31, (3, n)).astype(np.int32))

    def timeit(fn, *a, repeats=5):
        jax.block_until_ready(fn(*a))
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e6

    t_kernel = timeit(lambda w: ops.fused_hash_mix(w), words)
    t_ref = timeit(jax.jit(lambda w: hashing.mix64([w[0], w[1], w[2]])), words)
    _row("kernels/hash_mix_pallas", t_kernel, f"jnp_ref_us={t_ref:.1f}")

    vals = rng.integers(0, 5000, n).astype(np.int32)
    hi, lo = hashing.mix64([jnp.asarray(vals)])
    valid = jnp.ones(n, bool)

    table = ops.make_radix_table(4 * n, 8)
    t_radix = timeit(
        lambda h, l, v: ops.radix_dedup_insert(ops.make_radix_table(4 * n, 8), h, l, v)[1],
        hi, lo, valid,
    )
    from repro.core import hashset

    t_flat = timeit(
        jax.jit(lambda h, l, v: hashset.insert_masked(hashset.make(4 * n), h, l, v).is_new),
        hi, lo, valid,
    )
    _row("kernels/radix_dedup_pallas", t_radix, f"flat_hashset_us={t_flat:.1f}")

    pk = jnp.asarray(rng.integers(0, 128, 4096).astype(np.int32))
    ps = jnp.asarray(rng.integers(0, 10**6, 4096).astype(np.int32))
    ck = jnp.asarray(rng.integers(0, 128, 2048).astype(np.int32))
    K = int(np.bincount(np.asarray(pk)).max()) + 1
    t_join = timeit(lambda a, b, c: ops.blocked_nested_join(a, b, c, K)[0], pk, ps, ck)
    from repro.core import pjtt

    idx = pjtt.build_sorted(pk, ps)
    t_pjtt = timeit(
        jax.jit(lambda s, u, c: pjtt.probe_sorted(pjtt.PJTTSorted(s, u), c, K).subjects),
        idx.skeys, idx.ssubj, ck,
    )
    _row("kernels/nested_join_pallas", t_join, f"pjtt_index_join_us={t_pjtt:.1f}")


def bench_dedup_gather() -> None:
    from benchmarks import dedup_gather_bench

    for r in dedup_gather_bench.run(n=65_536, dup_factors=(1, 8, 64)):
        _row(
            f"dedup/x{r['dup_factor']}",
            r["t_dedup_s"] * 1e6,
            f"plain_us={r['t_plain_s']*1e6:.1f};traffic={r['traffic_saving']:.1f}x",
        )


_WIDE_TTL = """
@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
@prefix ex: <http://example.com/> .
ex:Wide a rr:TriplesMap ;
  rml:logicalSource [ rml:source "wide.csv" ; rml:referenceFormulation ql:CSV ] ;
  rr:subjectMap [ rr:template "http://example.com/r/{C0}" ] ;
  rr:predicateObjectMap [ rr:predicate ex:p1 ; rr:objectMap [ rml:reference "C1" ] ] ;
  rr:predicateObjectMap [ rr:predicate ex:p2 ; rr:objectMap [ rml:reference "C2" ] ] ;
  rr:predicateObjectMap [ rr:predicate ex:p3 ; rr:objectMap [ rml:reference "C3" ] ] ;
  rr:predicateObjectMap [ rr:predicate ex:p4 ; rr:objectMap [ rml:reference "C4" ] ] ;
  rr:predicateObjectMap [ rr:predicate ex:p5 ; rr:objectMap [ rml:reference "C5" ] ] .
"""


def bench_stream(json_dir: str = ".") -> None:
    """Streaming vs eager ingestion over the generator's 10K/100K CSV
    testbeds: rows/s and peak traced allocation (tracemalloc covers numpy
    buffers; RSS is monotonic per process and useless for per-phase peaks).
    The streamed path reads + dictionary-encodes block-at-a-time, the eager
    path materializes the whole table first.  A second family of cells
    runs full streamed ``create_kg`` over a 40-column/6-mapped CSV with
    the mapping planner's projection pushdown on vs off — the MapSDI win
    condition: pruned columns are never accumulated, so rows/s rises and
    peak allocation falls.  Results also land in ``BENCH_stream.json``."""
    import tempfile
    import tracemalloc

    from repro.data.encoder import Dictionary
    from repro.data.sources import load_csv
    from repro.rml import generator
    from repro.stream import read_csv

    report: dict[str, dict] = {}
    for n in (10_000, 100_000):
        tb = generator.make_testbed("SOM", n, 0.75, n_poms=2, seed=0)
        with tempfile.TemporaryDirectory() as d:
            tb.write(d)
            path = os.path.join(d, "child.csv")
            cols = list(tb.child)

            def eager():
                dct = Dictionary()
                table = load_csv(path)
                for c in cols:
                    dct.encode(table[c])

            def streamed():
                dct = Dictionary()
                ds = read_csv(path, block_rows=1 << 13).encode(dct)
                for block in ds.iter_blocks():
                    assert block.n_rows > 0

            for name, fn in (("stream", streamed), ("eager", eager)):
                tracemalloc.start()
                t0 = time.perf_counter()
                fn()
                dt = time.perf_counter() - t0
                _, peak = tracemalloc.get_traced_memory()
                tracemalloc.stop()
                _row(
                    f"stream/{name}-{n}",
                    dt * 1e6,
                    f"rows_per_s={n / dt:.0f};peak_alloc_mb={peak / 1e6:.1f}",
                )
                report[f"{name}-{n}"] = {
                    "rows": n,
                    "wall_s": dt,
                    "rows_per_s": n / dt,
                    "peak_alloc_mb": peak / 1e6,
                }

    # ---- wide-source ingestion: 40 columns, 6 mapped, pushdown on/off
    from repro.core.executor import create_kg
    from repro.rml import parser as rml_parser

    n, n_cols = 40_000, 40
    doc = rml_parser.parse(_WIDE_TTL)
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "wide.csv"), "w") as f:
            f.write(",".join(f"C{j}" for j in range(n_cols)) + "\n")
            for i in range(n):
                f.write(",".join(f"v{i % 997}_{j}" for j in range(n_cols)) + "\n")
        create_kg(doc, data_root=d, stream=True)  # jit warmup, untimed
        for label, on in (("pushdown-off", False), ("pushdown-on", True)):
            tracemalloc.start()
            t0 = time.perf_counter()
            res = create_kg(doc, data_root=d, stream=True, mapping_plan=on)
            dt = time.perf_counter() - t0
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            _row(
                f"stream/wide40x6-{label}",
                dt * 1e6,
                f"rows_per_s={n / dt:.0f};peak_alloc_mb={peak / 1e6:.1f}",
            )
            report[f"wide40x6-{label}"] = {
                "rows": n,
                "n_triples": res.n_triples,
                "wall_s": dt,
                "rows_per_s": n / dt,
                "peak_alloc_mb": peak / 1e6,
            }
    report["wide40x6-pushdown-speedup"] = round(
        report["wide40x6-pushdown-on"]["rows_per_s"]
        / report["wide40x6-pushdown-off"]["rows_per_s"],
        2,
    )
    _write_json(json_dir, "BENCH_stream.json", report)


def bench_kg(json_dir: str = ".") -> None:
    """The ``repro.kg`` serving benchmark on the paper's 100K-row testbed:
    KG creation -> ``to_store()`` (term re-key + three jax lexsorts) ->
    batched single-pattern queries/s through the jitted range-scan path.
    Writes ``BENCH_kg.json``."""
    import tracemalloc

    from repro.core.executor import create_kg
    from repro.kg.bench import bench_single_pattern
    from repro.rml import generator

    n = 100_000
    tb = generator.make_testbed("SOM", n, 0.75, n_poms=2, seed=0)
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    t0 = time.perf_counter()
    kg = create_kg(tb.doc, tables=tables)
    t_create = time.perf_counter() - t0
    tracemalloc.start()
    t0 = time.perf_counter()
    store = kg.to_store()
    t_build = time.perf_counter() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    report = bench_single_pattern(store, n_queries=50_000, batch=4096)
    report.update(
        {
            "testbed_rows": n,
            "create_s": t_create,
            "store_build_s": t_build,
            "store_build_peak_alloc_mb": peak / 1e6,
        }
    )
    _row(
        f"kg/build-{n}", t_build * 1e6,
        f"triples={store.n_triples};peak_alloc_mb={peak / 1e6:.1f}",
    )
    _row(
        f"kg/query-{n}",
        report["wall_s"] / report["n_queries"] * 1e6,
        f"queries_per_s={report['queries_per_s']:.0f};batch={report['batch']};"
        f"p99_ms={report['latency_p99_ms']:.3f}",
    )
    _write_json(json_dir, "BENCH_kg.json", report)


def bench_serve(json_dir: str = ".") -> None:
    """The ``repro.serve`` pipeline benchmark on the same 100K-row testbed
    store as the ``kg`` section (numbers directly comparable): end-to-end
    queries/s AND per-dispatch latency p50/p99 through the fused jitted
    executor for point lookups, a 3-pattern star BGP, an OPTIONAL+FILTER
    query, a 2-arm UNION, an ORDER BY DESC, and a GROUP BY-COUNT, each at
    batch sizes 1/64/4096 — plus the ``smallbatch`` section: the
    chain-eligible classes at batch 1/8/64 through the fused scan-join
    fast path.  Writes ``BENCH_serve.json`` (``queries_per_s``
    and ``latency_p99_ms`` gated in CI by ``benchmarks/compare.py``
    against the committed baseline — see ``benchmarks/README.md``) plus
    the run's dispatch trace (``TRACE_serve.json``, Perfetto-loadable)
    and metrics snapshot (``METRICS_serve.json``) as CI artifacts."""
    from repro import obs
    from repro.core.executor import create_kg
    from repro.rml import generator
    from repro.serve.bench import bench_serve as run_serve_bench

    n = 100_000
    tb = generator.make_testbed("SOM", n, 0.75, n_poms=2, seed=0)
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    store = create_kg(tb.doc, tables=tables).to_store()
    obs.enable_tracing()
    report = run_serve_bench(store)
    obs.get_tracer().disable()
    report["testbed_rows"] = n
    for name, cls in report["classes"].items():
        for batch, r in cls["batches"].items():
            _row(
                f"serve/{name}-b{batch}",
                r["wall_s"] / r["n_queries"] * 1e6,
                f"queries_per_s={r['queries_per_s']:.0f};"
                f"p50_ms={r['latency_p50_ms']:.3f};"
                f"p99_ms={r['latency_p99_ms']:.3f}",
            )
    # the interactive regime: per-dispatch tails through the fused
    # scan-join fast path at batch 1/8/64 (see repro.serve.fastpath)
    for name, cls in report["smallbatch"].items():
        for batch, r in cls["batches"].items():
            _row(
                f"serve/smallbatch-{name}-b{batch}",
                r["wall_s"] / r["n_queries"] * 1e6,
                f"p50_ms={r['latency_p50_ms']:.3f};"
                f"p99_ms={r['latency_p99_ms']:.3f};"
                f"fastpath={r['fastpath_dispatches']}",
            )
    _write_json(json_dir, "BENCH_serve.json", report)
    _write_json(json_dir, "TRACE_serve.json", obs.get_tracer().export())
    _write_json(json_dir, "METRICS_serve.json", obs.get_registry().snapshot())


def bench_live(json_dir: str = ".") -> None:
    """The ``repro.live`` mutable-store benchmark on a 20K-row testbed
    (small enough that the per-level overlay pipelines compile inside the
    CI budget): insert/delete rows/s through the overlay log, fused
    ``base ⊕ delta`` query throughput + latency at delta fractions
    0/1%/10%, and one compaction.  Writes ``BENCH_live.json``
    (``queries_per_s`` / ``latency_p99_ms`` gated by
    ``benchmarks/compare.py``)."""
    from repro.core.executor import create_kg
    from repro.live.bench import bench_live as run_live_bench
    from repro.rml import generator

    n = 20_000
    tb = generator.make_testbed("SOM", n, 0.75, n_poms=2, seed=0)
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    store = create_kg(tb.doc, tables=tables).to_store()
    report = run_live_bench(store)
    report["testbed_rows"] = n
    for op in ("insert", "delete"):
        w = report["write"][op]
        _row(
            f"live/{op}", w["wall_s"] / w["rows"] * 1e6,
            f"rows_per_s={w['rows_per_s']:.0f}",
        )
    for label, r in report["query"].items():
        _row(
            f"live/query-{label}",
            r["wall_s"] / r["n_queries"] * 1e6,
            f"queries_per_s={r['queries_per_s']:.0f};"
            f"p50_ms={r['latency_p50_ms']:.3f};"
            f"p99_ms={r['latency_p99_ms']:.3f}",
        )
    _row(
        "live/compact", report["compaction"]["compact_ms"] * 1e3,
        f"triples={report['compaction']['triples']}",
    )
    _write_json(json_dir, "BENCH_live.json", report)


def bench_shard(json_dir: str = ".") -> None:
    """The ``repro.shard`` scatter/gather benchmark on a 20K-row testbed
    (shard stores are rebuilt in-process at 1/2/4 shards, so the testbed
    stays small enough to re-encode three times inside the CI budget):
    routed bound-subject lookups and scatter-all 3-pattern star BGPs
    through the in-process shard session, per shard count, against the
    unsharded baseline.  Writes ``BENCH_shard.json``
    (``queries_per_s`` / ``latency_p99_ms`` gated by
    ``benchmarks/compare.py``; the ``criteria`` section carries the
    routed-overhead and scatter-cost acceptance ratios)."""
    from repro.core.executor import create_kg
    from repro.rml import generator
    from repro.shard.bench import bench_shard as run_shard_bench

    n = 20_000
    tb = generator.make_testbed("SOM", n, 0.75, n_poms=2, seed=0)
    tables = {"csv:child.csv": tb.child}
    if tb.parent is not None:
        tables["csv:parent.csv"] = tb.parent
    store = create_kg(tb.doc, tables=tables).to_store()
    report = run_shard_bench(store)
    report["testbed_rows"] = n
    for name, cls in report["classes"].items():
        for config, r in cls["configs"].items():
            _row(
                f"shard/{name}-{config}",
                r["wall_s"] / r["n_queries"] * 1e6,
                f"queries_per_s={r['queries_per_s']:.0f};"
                f"p50_ms={r['latency_p50_ms']:.3f};"
                f"p99_ms={r['latency_p99_ms']:.3f};"
                f"fanout={r['fanout_per_query']:.1f}",
            )
    for key, v in report.get("criteria", {}).items():
        _row(f"shard/criteria-{key}", 0.0, f"ratio={v:.2f}")
    _write_json(json_dir, "BENCH_shard.json", report)


def bench_roofline() -> None:
    from benchmarks import roofline

    path = os.path.join(roofline.RESULTS, "dryrun.json")
    if not os.path.exists(path):
        print("# roofline: results/dryrun.json missing (run repro.launch.dryrun)",
              flush=True)
        return
    for r in roofline.derive(path):
        if r.get("status") != "ok":
            continue
        _row(
            f"roofline/{r['cell']}",
            r["t_bound_s"] * 1e6,
            f"bound={r['bound']};frac={r.get('roofline_frac', 0)*100:.1f}%",
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    choices=(None, "fig56", "opmodel", "kernels", "dedup",
                             "stream", "kg", "serve", "live", "shard",
                             "roofline"))
    ap.add_argument("--json-dir", default=".",
                    help="where BENCH_*.json reports are written")
    args = ap.parse_args()

    from repro.jaxcache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    sections = {
        "fig56": lambda: bench_fig56(args.full),
        "opmodel": bench_op_model,
        "kernels": bench_kernels,
        "dedup": bench_dedup_gather,
        "stream": lambda: bench_stream(args.json_dir),
        "kg": lambda: bench_kg(args.json_dir),
        "serve": lambda: bench_serve(args.json_dir),
        "live": lambda: bench_live(args.json_dir),
        "shard": lambda: bench_shard(args.json_dir),
        "roofline": bench_roofline,
    }
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", flush=True)
        fn()


if __name__ == "__main__":
    main()
