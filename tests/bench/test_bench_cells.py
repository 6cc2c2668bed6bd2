"""Each cell end to end at a tiny size on the CPU, and its refusals."""

import os
import shutil
import subprocess
import sys

import pytest

from benchsteer import BENCH, CELLS, REPO, RESULT_KEYS, run_cell, steer


@pytest.mark.parametrize("workload", CELLS)
def test_cell_end_to_end(workload, tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, workload)
    assert rc == 0, err[-3000:]
    assert list(result) == RESULT_KEYS + ["checks"]
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] >= 1
    want = ({"ingest_rows_per_s"} if "ingest" in workload
            else {"query_p50_ms", "query_p95_ms"})
    assert set(result["metrics"]) == want | {"setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # the checks are the last lines of standard error, each with its limit
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(ln.startswith("check ") and "limit" in ln for ln in tail)


@pytest.mark.parametrize("workload", ["som-100k-ingest", "ojm-1m-kg-general"])
def test_traced_run_reports_layer_metrics(workload, tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    rc, result, err = run_cell(capsys, workload, seed=31, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    # a CPU trace has no device plane: only the host-side metrics come
    want = ({"ingest.engine_s", "ingest.emit_s", "ingest.compiles"}
            if "ingest" in workload else
            {"loadgen.late_p95_ms", "serve.queue_wait_p95_ms",
             "serve.dispatch_p50_ms", "serve.compiles"})
    assert set(result["metrics"]) == want
    assert "busy_s" not in result["device"]


def test_refuses_without_the_chip(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr("harness.WORKDIR", str(tmp_path))
    rc, result, err = run_cell(capsys, "ojm-100k-ingest")
    assert rc != 0 and result is None
    assert "tpu" in err


def test_refuses_an_unknown_cell(tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    rc, result, _err = run_cell(capsys, "no-such-cell")
    assert rc != 0 and result is None


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    files runs nothing."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    steered = ("import sys; sys.path.insert(0, 'bench'); import harness, run; "
               "harness.PLATFORM = 'cpu'; sys.exit(run.main(['--workload', "
               "'ojm-100k-ingest', '--seed', '1', '--seconds', '1', '--trace', '0']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", steered], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env=dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
