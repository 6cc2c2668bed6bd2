"""The reduction from a profiler trace to busy time, per-program device
time and labelled idle gaps."""

import os
from types import SimpleNamespace as NS

import pytest

from benchsteer import BENCH  # noqa: F401  (puts bench/ on the path)

import devtrace

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tpu_v5e_trace.xplane.pb")


def test_recorded_tpu_trace():
    """A capture made on one TPU v5e: inside ``bench.window``, a
    ``bench.job`` ran a sort, slept 20 ms under ``bench.sleep`` and ran a
    cumsum; then the window slept 10 ms and ran the sort again."""
    r = devtrace.reduce(TRACE)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.033157796, abs=1e-12)
    assert r.busy_s == pytest.approx(48.794e-6, abs=1e-12)
    # the device clock runs about a millisecond behind the host's, so the
    # first sort falls before the window opens
    assert r.programs == pytest.approx({"jit__lambda": 48.822e-6}, abs=1e-12)
    assert r.idle_gaps == pytest.approx(
        {"bench.sleep": 0.020615698, "bench.window": 0.012493304}, abs=1e-9)
    assert r.busy_s + sum(r.idle_gaps.values()) == pytest.approx(r.window_s, abs=1e-9)
    b = r.breakdown()
    assert b["device_ops"] == [["jit__lambda", r.programs["jit__lambda"]]]
    assert [name for name, _s in b["idle_gaps"]] == ["bench.sleep", "bench.window"]


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_overlaps_devices_and_labels():
    host = _plane("/host:CPU", python=[
        _ev("bench.window", 0, 1000), _ev("bench.job", 100, 500),
        _ev("bench.create_kg", 150, 100), _ev("PjitFunction(f)", 0, 1000),
    ])
    dev0 = _plane("/device:TPU:0",
                  XLA_Ops=[_ev("a", 300, 100), _ev("b", 350, 100), _ev("c", 900, 200)],
                  XLA_Modules=[_ev("jit_step(123)", 300, 150), _ev("jit_other(9)", 900, 200)])
    dev1 = _plane("/device:TPU:1", XLA_Ops=[_ev("a", 0, 500)],
                  XLA_Modules=[_ev("jit_step(123)", 0, 500)])
    r = devtrace.reduce_planes([host, dev0, dev1, _plane("/host:metadata")])
    assert r.n_devices == 2
    assert r.window_s == pytest.approx(1e-6)
    # device 0: [300, 450) and [900, 1000) after clipping; device 1: [0, 500)
    assert r.busy_s == pytest.approx((250 + 500) / 2 / 1e9)
    assert r.programs == pytest.approx({"jit_step": 650 / 2 / 1e9, "jit_other": 100 / 2 / 1e9})
    # device 0's gaps: [0, 300) mid 150 in create_kg, [450, 900) mid 675 in the window
    assert r.idle_gaps == pytest.approx({"bench.create_kg": 300e-9, "bench.window": 450e-9})


def test_no_device_no_reduction():
    host = _plane("/host:CPU", python=[_ev("bench.window", 0, 1000)])
    assert devtrace.reduce_planes([host]) is None
