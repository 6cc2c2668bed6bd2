import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh:

    lowered  = jax.jit(fn, donate_argnums=...).lower(*shaped_args)
    compiled = lowered.compile()
    memory_analysis()   -> proves the cell fits per-device HBM
    cost_analysis()     -> FLOPs / bytes for the roofline (§Roofline)
    collective bytes    -> parsed from the compiled HLO text

Results stream into results/dryrun.json incrementally, so re-runs skip
completed cells (--force to redo).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun                # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b
    PYTHONPATH=src python -m repro.launch.dryrun --cell qwen2.5-3b/train_4k \
        --mesh multi
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.launch.mesh import make_production_mesh

RESULTS = os.path.abspath(os.path.join(os.path.dirname(__file__), "../../..", "results"))

_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=?"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _tensor_bytes(type_str: str) -> int:
    """Bytes of one HLO shape like 'bf16[256,4096,2048]' (tuples summed)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum *output* shape bytes of every collective op in the HLO module.

    Output-shape accounting: for all-gather the output is the gathered
    (larger) tensor, for reduce-scatter the input is larger — we take the
    max of lhs/result shapes per instruction as 'bytes touched by the
    collective', the quantity the ICI link actually moves (up to the
    algorithm factor, which the roofline treats separately).
    """
    out: dict[str, int] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        # type may be a TUPLE with /*index=N*/ comments (shard_map emits
        # multi-operand collectives), so allow anything between '=' and the
        # op token as long as the op token starts the call
        m = re.search(
            r"=\s*(\(?.*?)\s"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(?:-start)?[.\d]*\(",
            line,
        )
        if not m:
            continue
        if re.search(r"(all-gather|all-to-all|all-reduce|reduce-scatter|collective-permute)-done", line):
            continue  # -done pairs with -start; count once
        kind = m.group(2)
        lhs_bytes = _tensor_bytes(m.group(1))
        args = line[m.end():].split("metadata=")[0]
        arg_bytes = _tensor_bytes(args)
        b = max(lhs_bytes, arg_bytes)
        out[kind] = out.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes_by_kind": out, "counts": counts,
            "total_bytes": sum(out.values())}


def _compile_costs(spec, mesh) -> dict:
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(spec.fn, donate_argnums=spec.donate)
        lowered = jitted.lower(*spec.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    mem_out = {}
    for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(mem, k, None)
        if v is not None:
            mem_out[k] = int(v)
    return {
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_out,
        "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        "collectives": coll,
    }


def _extrapolate(c1: dict, c2: dict, n_layers: int) -> dict:
    """Per-layer marginal cost from unrolled L=1 and L=2 compiles:
    total(L) = cost(1) + (L-1) * (cost(2) - cost(1)).

    Needed because XLA cost_analysis counts a scan body once regardless of
    trip count; the deployable (scanned) compile provides memory numbers,
    this provides the compute/traffic numbers.
    """
    def ext(a, b):
        return a + (n_layers - 1) * max(b - a, 0.0)

    kinds = set(c1["collectives"]["bytes_by_kind"]) | set(
        c2["collectives"]["bytes_by_kind"]
    )
    coll = {
        k: int(
            ext(
                c1["collectives"]["bytes_by_kind"].get(k, 0),
                c2["collectives"]["bytes_by_kind"].get(k, 0),
            )
        )
        for k in kinds
    }
    return {
        "flops": ext(c1["flops"], c2["flops"]),
        "bytes_accessed": ext(c1["bytes_accessed"], c2["bytes_accessed"]),
        "collectives": {
            "bytes_by_kind": coll,
            "total_bytes": sum(coll.values()),
            "counts": {
                k: int(
                    ext(
                        c1["collectives"]["counts"].get(k, 0),
                        c2["collectives"]["counts"].get(k, 0),
                    )
                )
                for k in kinds
            },
        },
    }


def run_cell(arch: str, shape: str, mesh, mesh_name: str) -> dict:
    from repro.configs import registry

    spec = registry.build_cell(arch, shape, mesh)
    if isinstance(spec, str):
        return {"status": "skipped", "reason": spec}

    base = _compile_costs(spec, mesh)
    out = {
        "status": "ok",
        "mesh": mesh_name,
        "devices": mesh.devices.size,
        "kind": spec.kind,
        "note": spec.note,
        **base,
    }

    entry = registry.get_arch(arch)
    if entry.family == "lm" and entry.config().scan_layers:
        # marginal-layer extrapolation for honest whole-program costs
        s1 = registry.build_cell(arch, shape, mesh, n_layers_override=1)
        s2 = registry.build_cell(arch, shape, mesh, n_layers_override=2)
        c1 = _compile_costs(s1, mesh)
        c2 = _compile_costs(s2, mesh)
        n_layers = entry.config().n_layers
        out["scan_body_once"] = {
            "flops": base["flops"],
            "collectives_total": base["collectives"]["total_bytes"],
        }
        out.update(_extrapolate(c1, c2, n_layers))
        out["cost_method"] = "unrolled L=1/L=2 marginal extrapolation"
    else:
        out["cost_method"] = "direct (no scan)"
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None, help="arch/shape")
    ap.add_argument("--mesh", default="both", choices=("single", "multi", "both"))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--extra", action="store_true", help="include rdfizer cells")
    ap.add_argument("--out", default=os.path.join(RESULTS, "dryrun.json"))
    args = ap.parse_args()

    from repro.configs import registry

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    results = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    cells = []
    for a in registry.ARCHS.values():
        if args.arch and a.name != args.arch:
            continue
        for s in a.shapes:
            cells.append((a.name, s))
    if args.cell:
        arch, shape = args.cell.split("/")
        cells = [(arch, shape)]

    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            key = f"{arch}/{shape}@{mesh_name}"
            if key in results and results[key].get("status") in ("ok", "skipped") and not args.force:
                print(f"[cached] {key}: {results[key]['status']}")
                continue
            print(f"[dryrun] {key} ...", flush=True)
            try:
                res = run_cell(arch, shape, mesh, mesh_name)
            except Exception as e:  # noqa: BLE001
                res = {
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:],
                }
            results[key] = res
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            stat = res["status"]
            extra = ""
            if stat == "ok":
                mem = res["memory"].get("temp_size_in_bytes", 0) / (1 << 30)
                extra = (
                    f" flops={res['flops']:.3e}"
                    f" temp={mem:.2f}GiB/dev coll={res['collectives']['total_bytes']:.3e}B"
                    f" compile={res['compile_s']}s"
                )
            elif stat == "error":
                extra = " " + res["error"][:200]
            print(f"[dryrun] {key}: {stat}{extra}", flush=True)

    if args.extra:
        for mesh_name, mesh in meshes:
            for spec in registry.build_extra_cells(mesh):
                key = f"{spec.name}@{mesh_name}"
                if key in results and not args.force:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    with jax.set_mesh(mesh):
                        jitted = jax.jit(spec.fn, donate_argnums=spec.donate)
                        lowered = jitted.lower(*spec.args)
                        compiled = lowered.compile()
                        res = {
                            "status": "ok",
                            "mesh": mesh_name,
                            "kind": spec.kind,
                            "flops": float((compiled.cost_analysis() or {}).get("flops", 0)),
                            "collectives": collective_bytes(compiled.as_text()),
                            "memory": {
                                "temp_size_in_bytes": int(
                                    getattr(compiled.memory_analysis(), "temp_size_in_bytes", 0)
                                )
                            },
                        }
                except Exception as e:  # noqa: BLE001
                    res = {"status": "error", "error": f"{type(e).__name__}: {e}"}
                results[key] = res
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                print(f"[dryrun] {key}: {res['status']}", flush=True)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        for k, r in results.items():
            if r["status"] == "error":
                print(f"  ERROR {k}: {r['error'][:300]}")


if __name__ == "__main__":
    main()
