"""What every cell shares: finding a cell's files by name, the device
check, compile counting, the window's histogram deltas, and the result
line.  A cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json``, the mix's ``kind`` names the general
generator ``bench/kinds/<kind>.py``, and each per-layer metric is read by
``bench/layer_metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import threading

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the platform a run demands (tests steer this to "cpu")
PLATFORM = "tpu"
# where runs keep their testbeds, outputs and traces (inside the checkout)
WORKDIR = os.path.join(ROOT, "bench-out")


class BenchError(RuntimeError):
    """The run cannot produce a result."""


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The module at ``path``, loaded once under ``name``."""
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise BenchError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: "list[dict]"   # the BENCHMARK.json entries this cell reports
    per_layer: "list[dict]"

    def kind(self):
        kind = self.traffic["kind"]
        return load_module(os.path.join(BENCH, "kinds", f"{kind}.py"), f"bench_kind_{kind}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    for wl in bench["workloads"]:
        if wl["name"] == name:
            break
    else:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return Cell(
        name=name,
        chips=wl["chips"],
        config=load_json(os.path.join(root, cfg["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic", f"{wl['traffic']}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def use_checkout_cache(root: str = ROOT) -> str:
    """Keep JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, whatever the environment says: the
    program's own entry points (``rdfize``) take the directory from
    ``JAX_COMPILATION_CACHE_DIR``, so it is set for them too."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def check_device(chips: int) -> dict:
    """The device as JAX reports it; refuses any other platform, and
    fewer devices than the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != PLATFORM:
        raise BenchError(f"JAX found no {PLATFORM} (platform {info['platform']!r})")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} devices, JAX found {info['count']}")
    return info


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the cell's devices."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Programs JAX lowers (each a jit-cache miss: compiled anew or read
    from the persistent cache), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1


# -- histogram deltas --------------------------------------------------------
# The program's latency histograms are log2 octaves split into 16 linear
# sub-buckets; a snapshot lists bucket index -> count.  The window's
# quantile is taken from the difference of two snapshots, nearest-rank,
# as the upper edge of the bucket that holds it.

SUBBUCKETS = 16


def _bucket_upper(idx: int) -> float:
    e, sub = divmod(idx, SUBBUCKETS)
    return math.ldexp(0.5 + (sub + 1) / (2 * SUBBUCKETS), e)


def histogram_delta(before: dict, after: dict, name: str) -> dict:
    a = after.get("histograms", {}).get(name)
    if a is None:
        return {"count": 0, "zero": 0, "buckets": {}}
    b = before.get("histograms", {}).get(name) or {"count": 0, "zero": 0, "buckets": {}}
    buckets = {int(k): v - b["buckets"].get(k, 0) for k, v in a["buckets"].items()}
    return {"count": a["count"] - b["count"], "zero": a.get("zero", 0) - b.get("zero", 0),
            "buckets": {k: v for k, v in buckets.items() if v}}


def histogram_quantile(h: dict, q: float):
    if h["count"] <= 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * h["count"]))
    seen = h["zero"]
    if rank <= seen:
        return 0.0
    for idx in sorted(h["buckets"]):
        seen += h["buckets"][idx]
        if rank <= seen:
            return _bucket_upper(idx)
    return None


# -- exact quantiles of host-clock samples ----------------------------------

def quantile(values, q: float) -> float:
    """Nearest-rank quantile of all the samples."""
    vals = sorted(values)
    if not vals:
        raise BenchError("no samples")
    return vals[max(1, math.ceil(q / 100.0 * len(vals))) - 1]


# -- the result ----------------------------------------------------------------

def emit_result(result: dict, checks: "list[tuple[str, float, float]]") -> None:
    """Print the checks as the last lines of standard error and the
    result as the last line of standard output, the checks last in it."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(result), flush=True)
