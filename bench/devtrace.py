"""Profiler capture and its reduction to device busy time, per-program
device time and labelled idle gaps.

A capture records one ``.xplane.pb``.  Device planes are the
``/device:<KIND>:<n>`` planes that carry an ``XLA Ops`` line; their
``XLA Modules`` line names each jitted program.  The host plane carries
the benchmark's ``jax.profiler.TraceAnnotation`` spans, all named
``bench.*``; ``bench.window`` bounds the measured window.

- busy: the union of the ``XLA Ops`` intervals inside the window, per
  device, averaged over the devices;
- per-program time: ``XLA Modules`` durations inside the window, by
  program name with the ``(hash)`` suffix dropped, averaged likewise;
- idle gaps: the stretches of the window in which device 0 runs no op,
  each put under the innermost ``bench.*`` span that covers its middle.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import os
import re
import shutil

WINDOW = "bench.window"
_HASH = re.compile(r"\(\d+\)$")


@contextlib.contextmanager
def capture(logdir: str):
    """Trace the enclosed block (no Python-function tracing) into
    ``logdir``, emptied first."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


@dataclasses.dataclass
class Reduction:
    n_devices: int
    window_s: float
    busy_s: float                       # mean over devices
    programs: "dict[str, float]"        # name -> device seconds, mean over devices
    idle_gaps: "dict[str, float]"       # bench span -> idle seconds on device 0

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": best(self.programs), "idle_gaps": best(self.idle_gaps)}


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]


def _union(events) -> "list[tuple[float, float]]":
    out: list = []
    for _n, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(path: str) -> "Reduction | None":
    """The reduction of one capture; None where it holds no device op."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_planes(planes) -> "Reduction | None":
    """The reduction of a capture's planes (each with ``name`` and
    ``lines``; a line with ``name`` and ``events``; an event with
    ``name``, ``start_ns`` and ``duration_ns``)."""
    host, devices = [], []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [e for e in _events(ln) if e[0].startswith("bench.")]
    devices.sort(key=lambda d: d[0])
    if not devices:
        return None
    spans = [e for e in host if e[0] == WINDOW]
    if spans:
        lo, hi = spans[0][1], spans[0][2]
    else:
        every = [e for _p, ls in devices for e in _events(ls["XLA Ops"])]
        if not every:
            return None
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    busy, programs = [], collections.Counter()
    first_busy = None
    for _name, lines in devices:
        ops = _union(_clip(_events(lines["XLA Ops"]), lo, hi))
        busy.append(sum(b - a for a, b in ops))
        if first_busy is None:
            first_busy = ops
        if "XLA Modules" in lines:
            for n, a, b in _clip(_events(lines["XLA Modules"]), lo, hi):
                programs[_HASH.sub("", n)] += (b - a) / 1e9 / len(devices)
    if not any(busy):
        return None
    gaps = collections.Counter()
    edges = [lo] + [x for ab in first_busy for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [e for e in host if e[1] <= mid <= e[2]]
        label = min(cover, key=lambda e: e[2] - e[1])[0] if cover else "outside bench spans"
        gaps[label] += (b - a) / 1e9
    return Reduction(
        n_devices=len(devices),
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        programs=dict(programs),
        idle_gaps=dict(gaps),
    )
