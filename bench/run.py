#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, program start, every shape the window uses
warmed) is ``setup_s``; then the window runs for ``--seconds``; then the
program is stopped and what the window produced is compared with a plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiled window.  The last line
of standard output is the result, as JSON; without the device the cell
asks for, the run exits non-zero and prints none.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from harness import BenchError  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a kind's set-up and a layer metric's reader are given."""

    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    workdir: str
    # filled in as the run goes
    window: dict = dataclasses.field(default_factory=dict)
    registry_before: dict = dataclasses.field(default_factory=dict)
    registry_after: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    device: "object | None" = None       # devtrace.Reduction
    compiles: int = 0

    def histogram(self, name: str) -> dict:
        return harness.histogram_delta(self.registry_before, self.registry_after, name)

    def span_seconds(self, *names: str) -> "list[float]":
        return [ev["dur"] / 1e6 for ev in self.spans if ev.get("name") in names]


def _read_layer_metrics(ctx: Context) -> dict:
    out = {}
    for m in ctx.cell.per_layer:
        path = os.path.join(harness.BENCH, "layer_metrics", f"{m['name']}.py")
        value = harness.load_module(path, "bench_layer_" + m["name"].replace(".", "_")).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def prepare(workload: str, root: str = harness.ROOT):
    """The cell, its kind's module and the device, with the program on
    the path and the compile cache in the checkout; raises without the
    program or the device the cell asks for."""
    cell = harness.find_cell(workload, root)
    kind = cell.kind()
    sys.path.insert(0, os.path.join(root, "src"))
    import repro  # noqa: F401 — the program under test, or no run at all

    harness.use_checkout_cache(root)
    return cell, kind, harness.check_device(cell.chips)


def context(cell: harness.Cell, seed: int, seconds: float, trace: bool = False) -> Context:
    ctx = Context(cell, seed, seconds, trace, os.path.join(harness.WORKDIR, cell.name))
    os.makedirs(ctx.workdir, exist_ok=True)
    return ctx


def run(workload: str, seed: int, seconds: float, trace: bool, root: str = harness.ROOT) -> int:
    cell, kind, device = prepare(workload, root)
    compiles = harness.CompileCounter()
    ctx = context(cell, seed, seconds, trace)

    from repro import obs

    t0 = time.perf_counter()
    state = kind.setup(ctx)
    setup_s = time.perf_counter() - t0
    try:
        if trace:
            import jax

            import devtrace

            obs.enable_tracing()
            obs.get_tracer().clear()
        ctx.registry_before = obs.get_registry().snapshot()
        c0 = compiles.count
        if trace:
            tracedir = os.path.join(ctx.workdir, "trace")
            with devtrace.capture(tracedir), kind.annotate(state):
                with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                    ctx.window = kind.window(state, seconds)
        else:
            ctx.window = kind.window(state, seconds)
        ctx.compiles = compiles.count - c0
        ctx.registry_after = obs.get_registry().snapshot()
        device["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    finally:
        kind.release(state)
    checks = kind.check(state, ctx.window)
    correct = all(value <= limit for _n, value, limit in checks)
    result = {
        "correct": correct,
        "attempted": ctx.window["attempted"],
        "failed": ctx.window["failed"],
        "metrics": {},
        "device": device,
    }
    if trace:
        ctx.spans = obs.get_tracer().export()["traceEvents"]
        ctx.device = devtrace.reduce(devtrace.find(tracedir))
        if ctx.device is not None:
            device["busy_s"] = ctx.device.busy_s
            device["window_s"] = ctx.device.window_s
            result["breakdown"] = ctx.device.breakdown()
        result["metrics"] = _read_layer_metrics(ctx)
    else:
        values = dict(ctx.window["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise BenchError(f"the {cell.traffic['kind']} kind gives no {m['name']}")
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    harness.emit_result(result, checks)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 — any failure ends the run without a result
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
