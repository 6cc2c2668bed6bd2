"""The readers of the program's own spans and counters, and the idle gaps
named by program spans."""

import os
from types import SimpleNamespace as NS

import pytest

from benchsteer import run_cell, steer

import devtrace
import harness
import spans

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tpu_v5e_trace.xplane.pb")

ENGINE_METRICS = ["ingest.read_s", "ingest.encode_s", "ingest.sync_s", "ingest.compact_s",
                  "ingest.host_syncs", "ingest.replays"]


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def _planes():
    host = _plane("/host:CPU", python=[
        _ev("bench.window", 0, 1000), _ev("bench.job", 100, 500),
        _ev("bench.create_kg", 150, 400), _ev("repro.create_kg", 160, 380),
        _ev("repro.ingest.step", 180, 300), _ev("repro.ingest.sync", 200, 150),
        _ev("PjitFunction(f)", 190, 100),
    ])
    dev = _plane("/device:TPU:0",
                 XLA_Ops=[_ev("z", 0, 100), _ev("a", 360, 40), _ev("b", 900, 50)],
                 XLA_Modules=[_ev("jit_step(1)", 360, 40), _ev("jit_b(2)", 900, 50)])
    return [host, dev]


def test_gap_goes_to_the_innermost_program_span():
    """A gap under ``repro.ingest.sync``, inside ``bench.create_kg``, is
    the sync's; ``PjitFunction(f)``, shorter and around the gap's middle
    too, is no label; the benchmark's own reduction names the gap by the
    bench span alone."""
    planes = _planes()
    # gaps: [100, 360) mid 230 under the sync; [400, 900) and [950, 1000)
    # in the window alone
    assert spans.idle_gaps(planes) == pytest.approx(
        {"repro.ingest.sync": 260e-9, "bench.window": 550e-9})
    assert devtrace.reduce_planes(planes).idle_gaps == pytest.approx(
        {"bench.create_kg": 260e-9, "bench.window": 550e-9})


def test_recorded_trace_gaps_as_devtrace():
    """The recorded v5e capture has no program span: the gaps are
    devtrace's."""
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(TRACE).planes
    assert spans.idle_gaps(planes) == pytest.approx(devtrace.reduce(TRACE).idle_gaps, abs=1e-12)
    assert spans.device_lag_ms(planes) == []


def test_device_lag():
    # the step opens at 180 ns, the device's next op at 360 ns
    assert spans.device_lag_ms(_planes()) == pytest.approx([180e-6])


def _chrome(name, ts, dur, tid=1):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": 1, "tid": tid}


def test_self_time_less_nested_family_spans():
    events = [
        _chrome("create_kg", 0.0, 100.0),
        _chrome("ingest.step", 10.0, 50.0),
        _chrome("ingest.sync", 15.0, 20.0),       # nested in the step
        _chrome("ingest.compact", 40.0, 10.0),
        _chrome("plan_group", 5.0, 90.0),         # not of the family
        _chrome("ingest.encode", 70.0, 20.0),
        _chrome("ingest.read", 75.0, 5.0),
        _chrome("ingest.read", 0.0, 500.0, tid=2),  # another thread
        _chrome("ingest.step", 200.0, 30.0),
    ]
    us = 1e-6
    assert spans.self_seconds(events, "create_kg") == pytest.approx((100 - 50 - 20) * us)
    assert spans.self_seconds(events, "ingest.step") == pytest.approx((50 - 30 + 30) * us)
    assert spans.self_seconds(events, "ingest.encode") == pytest.approx(15 * us)
    assert spans.self_seconds(events, "ingest.read") == pytest.approx(505 * us)
    assert spans.self_seconds(events, "ingest.sync") == pytest.approx(20 * us)


def _ctx(events=(), before=None, after=None, jobs=2):
    return NS(spans=list(events), window={"jobs": jobs},
              registry_before=before or {}, registry_after=after or {})


@pytest.mark.parametrize("metric", ENGINE_METRICS)
def test_readers_give_nothing_without_the_program_instrumentation(metric):
    """A program that records no ``ingest.*`` span or counter (one older
    than them) gives no value, and no error."""
    mod = harness.load_module(os.path.join(harness.BENCH, "layer_metrics", metric + ".py"),
                              "bench_layer_" + metric.replace(".", "_"))
    old = _ctx([_chrome("create_kg", 0.0, 10.0)], {"counters": {}}, {"counters": {"x": 1}})
    assert mod.read(old) is None


def test_counter_reader_per_job():
    ctx = _ctx(before={"counters": {"ingest.host_syncs": 10}},
               after={"counters": {"ingest.host_syncs": 70, "ingest.replays": 0}})
    assert spans.counter_per_job(ctx, "ingest.host_syncs") == 30
    assert spans.counter_per_job(ctx, "ingest.replays") == 0


def test_traced_ojm_run_reports_the_engine_metrics(tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, "ojm-100k-ingest", seed=31, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    # a CPU trace has no device plane: only the host-side metrics come
    assert set(result["metrics"]) == {"ingest.engine_s", "ingest.emit_s", "ingest.compiles",
                                      *ENGINE_METRICS}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["ingest.replays"] == 0 and got["ingest.host_syncs"] > 0
    assert all(got[m] > 0 for m in ENGINE_METRICS if m.endswith("_s"))
    assert sum(got[m] for m in ENGINE_METRICS if m.endswith("_s")) < got["ingest.engine_s"]


def test_the_som_cell_reads_the_engine_metrics(tmp_path, monkeypatch, capsys):
    """The streamed engine gives the same six, were the SOM cell to
    report them."""
    steer(monkeypatch, tmp_path)
    find = harness.find_cell

    def with_engine_metrics(name, root=harness.ROOT):
        cell = find(name, root)
        cell.per_layer += [m for m in harness.load_json(os.path.join(root, "BENCHMARK.json"))
                           ["per_layer"] if m["name"] in ENGINE_METRICS]
        return cell

    monkeypatch.setattr(harness, "find_cell", with_engine_metrics)
    rc, result, err = run_cell(capsys, "som-100k-ingest", seed=32, trace=1)
    assert rc == 0, err[-3000:]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(ENGINE_METRICS) <= set(got)
    assert got["ingest.replays"] == 0 and got["ingest.host_syncs"] > 0
