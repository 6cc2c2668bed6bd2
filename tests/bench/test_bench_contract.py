"""``BENCHMARK.json`` keeps to the benchmark's contract, and a new mix,
configuration or per-layer metric comes as new files alone."""

import json
import os
import re
import shutil
import subprocess
import sys

from benchsteer import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection|head|expan)|(_dim|_rank)$")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_the_contract():
    b = _bench()
    assert list(b) == ["command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"]
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench", "tests/bench"]
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    used = {w["config"] for w in b["workloads"]}
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used and _line(c["source"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        cfg = json.load(open(os.path.join(REPO, c["file"]), encoding="utf-8"))
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) and not WIDTHS.search(k) for k in c["reduced"])
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.load(open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "kinds", traffic["kind"] + ".py"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(b["workloads"]) // 2)
    names = [w["name"] for w in b["workloads"]]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", names)) <= set(names)
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        # every cell that reports the metric reports what it moves
        moved = e2e[m["moves"]].get("workloads", names)
        assert set(m.get("workloads", names)) <= set(moved)
    for w in names:
        reported = [m for m in b["end_to_end"] if w in m.get("workloads", names)]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", names) for m in b["per_layer"])


_DUMMY_READER = '''
def read(ctx):
    return ctx.window.get("answered")
'''

_STEER = """
import sys
sys.path.insert(0, {bench!r})
import harness, run
harness.PLATFORM = "cpu"
sys.exit(run.main(["--workload", "tiny-kg-point", "--seed", "3", "--seconds", "1",
                   "--trace", "1"]))
"""


def test_a_new_cell_is_new_files(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus new ``BENCHMARK.json`` entries run with no edit to any
    file the benchmark has."""
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), tmp_path / "src")
    bench = _bench()
    cfg = json.load(open(os.path.join(BENCH, "configs", "sdm-ojm-1m-kg.json")))
    cfg.update(name="tiny-kg", testbed={"kind": "OJM", "rows": 800, "dup_rate": 0.75, "n_poms": 2},
               reduced={"rows": {"source": 1000000, "here": 800, "why": "a test"}})
    (tmp_path / "bench" / "configs" / "tiny-kg.json").write_text(json.dumps(cfg))
    mix = {"kind": "serve", "about": "one point lookup", "rate_qps": 50, "zipf_s": 0.99,
           "clients": 2, "max_pad": 2, "drain_s": 30,
           "shapes": [{"name": "point", "anchor": "mutation",
                       "query": "SELECT * WHERE { $mutation ?p ?o }"}]}
    (tmp_path / "bench" / "traffic" / "point.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "layer_metrics" / "dummy.answered.py").write_text(_DUMMY_READER)
    bench["configs"].append({"name": "tiny-kg", "source": "a test", "file": "bench/configs/tiny-kg.json",
                             "reduced": ["rows"], "why": "a test"})
    bench["workloads"].append({"name": "tiny-kg-point", "config": "tiny-kg", "traffic": "point",
                               "chips": 1, "why": "a test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "query_p50_ms" not in e2e:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_cells.json")) as f:
            e2e["query_p50_ms"] = next(m for m in json.load(f)["end_to_end"] if m["name"] == "query_p50_ms")
        bench["end_to_end"].append(e2e["query_p50_ms"])
    e2e["query_p50_ms"]["workloads"] = ["tiny-kg-point"] + [
        w for w in e2e["query_p50_ms"]["workloads"] if w in {x["name"] for x in bench["workloads"]}]
    bench["per_layer"].append({"name": "dummy.answered", "unit": "queries", "better": "higher",
                               "source": "host_clock", "layer": "test", "moves": "query_p50_ms",
                               "workloads": ["tiny-kg-point"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, "-c", _STEER.format(bench=str(tmp_path / "bench"))],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["dummy.answered"]["value"] == result["attempted"] == 50
