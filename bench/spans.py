#!/usr/bin/env python3
"""The program's own spans and counters, per job, and the idle gaps of a
capture named by the program's spans.

- Self time: a span's duration less what the spans of its family
  (``ingest.*``) nested in it on its thread cover, from the Chrome trace
  events that ``repro.obs`` exports (``tid``, ``ts``, ``dur`` in µs).
- Counters: the window's difference of two registry snapshots.
- Idle gaps: as ``devtrace.reduce_planes`` puts them, but under the
  innermost ``bench.*`` or ``repro.*`` host span that covers each gap's
  middle; ``repro.<name>`` is the profiler's copy of the program's span
  ``<name>``.  With the device lag: how long after each
  ``repro.ingest.step`` opens the device's next op starts.

    python bench/spans.py <capture directory or .xplane.pb>

prints the named gaps and the lag as JSON.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402

FAMILY = "ingest."
HOST_PREFIXES = ("bench.", "repro.")
_EPS_US = 1e-3


def self_seconds(events, name: str, family: str = FAMILY) -> float:
    """Summed self time of every ``name`` span: its duration less the
    union of the ``family`` spans nested in it on the same thread."""
    by_thread = collections.defaultdict(list)
    for ev in events:
        if ev.get("ph", "X") == "X" and (ev["name"] == name or ev["name"].startswith(family)):
            by_thread[(ev.get("pid"), ev.get("tid"))].append(ev)
    total = 0.0
    for evs in by_thread.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        starts = [e["ts"] for e in evs]
        for i, ev in enumerate(evs):
            if ev["name"] != name:
                continue
            end = ev["ts"] + ev["dur"]
            covered, reach = 0.0, ev["ts"]
            for child in evs[i + 1:bisect.bisect_left(starts, end)]:
                c_end = child["ts"] + child["dur"]
                if not child["name"].startswith(family) or c_end > end + _EPS_US:
                    continue
                lo = max(child["ts"], reach)
                if c_end > lo:
                    covered += c_end - lo
                    reach = c_end
            total += max(ev["dur"] - covered, 0.0)
    return total / 1e6


def self_seconds_per_job(ctx, name: str):
    """``name``'s self time per job of the window; None where the window
    recorded no such span (a program without it) or ran no job."""
    jobs = ctx.window.get("jobs", 0)
    if not jobs or not any(ev.get("name") == name for ev in ctx.spans):
        return None
    return self_seconds(ctx.spans, name) / jobs


def counter_per_job(ctx, name: str):
    """The window's increase of counter ``name`` per job; None where the
    program keeps no such counter or the window ran no job."""
    after = ctx.registry_after.get("counters", {})
    jobs = ctx.window.get("jobs", 0)
    if name not in after or not jobs:
        return None
    return (after[name] - ctx.registry_before.get("counters", {}).get(name, 0)) / jobs


def _host_events(planes):
    out = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [e for e in devtrace._events(ln) if e[0].startswith(HOST_PREFIXES)]
    return out


def _first_device_ops(planes):
    devices = sorted((p for p in planes if p.name.startswith("/device:")
                      and any(ln.name == "XLA Ops" for ln in p.lines)), key=lambda p: p.name)
    if not devices:
        return None
    ops = next(ln for ln in devices[0].lines if ln.name == "XLA Ops")
    return devtrace._events(ops)


def idle_gaps(planes) -> "dict[str, float] | None":
    """Idle seconds of device 0 inside the window, by the innermost
    ``bench.*`` or ``repro.*`` host span covering each gap's middle."""
    planes = list(planes)
    ops = _first_device_ops(planes)
    if not ops:
        return None
    host = _host_events(planes)
    window = [e for e in host if e[0] == devtrace.WINDOW]
    if window:
        lo, hi = window[0][1], window[0][2]
    else:
        lo, hi = min(e[1] for e in ops), max(e[2] for e in ops)
    busy = devtrace._union(devtrace._clip(ops, lo, hi))
    gaps = collections.Counter()
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [e for e in host if e[1] <= mid <= e[2]]
        label = min(cover, key=lambda e: e[2] - e[1])[0] if cover else "outside bench spans"
        gaps[label] += (b - a) / 1e9
    return dict(gaps)


def device_lag_ms(planes, span: str = "repro.ingest.step") -> "list[float]":
    """For each ``span`` on the host, milliseconds from its start to the
    start of the first device op at or after it."""
    planes = list(planes)
    ops = _first_device_ops(planes) or []
    starts = sorted(e[1] for e in ops)
    lags = []
    for _n, a, _b in (e for e in _host_events(planes) if e[0] == span):
        i = bisect.bisect_left(starts, a)
        if i < len(starts):
            lags.append((starts[i] - a) / 1e6)
    return lags


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".xplane.pb") else devtrace.find(args[0])
    planes = list(ProfileData.from_file(path).planes)
    gaps = idle_gaps(planes) or {}
    lags = sorted(device_lag_ms(planes))
    print(json.dumps({
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1]),
        "device_lag_ms": {"n": len(lags), "min": lags[0], "median": lags[len(lags) // 2],
                          "max": lags[-1]} if lags else None,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
