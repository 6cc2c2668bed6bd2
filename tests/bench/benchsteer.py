"""Helpers for the benchmark's CPU tests: steer a run to the CPU, to a
tiny testbed and to a scratch directory, and read its result line."""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

import harness  # noqa: E402
import run  # noqa: E402

CELLS = ("ojm-100k-ingest", "som-100k-ingest", "ojm-1m-kg-chain", "ojm-1m-kg-general")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


# The serve cells' entries, kept for the CPU tests until the chip has
# measured them (see PERF.md, Open questions).
SERVE_CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_cells.json")


def serve_cell(name: str, root: str = harness.ROOT) -> "harness.Cell":
    with open(SERVE_CELLS, encoding="utf-8") as f:
        entries = json.load(f)
    wl = next(w for w in entries["workloads"] if w["name"] == name)
    cfg = next(c for c in entries["configs"] if c["name"] == wl["config"])
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    return harness.Cell(
        name=name, chips=wl["chips"],
        config=harness.load_json(os.path.join(root, cfg["file"])),
        traffic=harness.load_json(os.path.join(BENCH, "traffic", wl["traffic"] + ".json")),
        end_to_end=[m for m in entries["end_to_end"] + bench["end_to_end"]
                    if "workloads" not in m or name in m["workloads"]],
        per_layer=[m for m in entries["per_layer"] if name in m["workloads"]],
    )


def steer(monkeypatch, tmp_path, rows: int = 1500, max_pad: int = 4) -> None:
    """Runs in this process use the CPU, ``rows``-row testbeds, batch
    pads up to ``max_pad`` and ``tmp_path`` for their files."""
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    monkeypatch.setattr(harness, "WORKDIR", str(tmp_path))
    find = harness.find_cell

    def small(name, root=harness.ROOT):
        try:
            cell = find(name, root)
        except harness.BenchError:
            if name not in CELLS:
                raise
            cell = serve_cell(name, root)
        cell.config["testbed"]["rows"] = rows
        if "max_pad" in cell.traffic:
            cell.traffic["max_pad"] = max_pad
        return cell

    monkeypatch.setattr(harness, "find_cell", small)


def run_cell(capsys, workload: str, seed: int = 2_147_483_659, seconds: float = 1.0,
             trace: int = 0):
    """(exit code, result line or None, standard error)."""
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)])
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return rc, result, err
