"""``chip_smoke.py`` end to end at a tiny size on the CPU.

The script demands a TPU; these tests steer its platform check to the
CPU, run its phases on small testbeds, and check that it refuses to run
without the platform it demands.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _lines(out: str) -> "list[dict]":
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


def test_refuses_without_the_chip(tmp_path, capsys):
    assert chip_smoke.main(["--out", str(tmp_path)]) == 1
    last = _lines(capsys.readouterr().out)[-1]
    assert last["ok"] is False and "tpu" in last["error"]


def test_one_chip_phases(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "ROWS", 2000)
    assert chip_smoke.main(["--out", str(tmp_path)]) == 0
    lines = _lines(capsys.readouterr().out)
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}
    }
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert phases["ingest_ojm"]["triples"] == phases["ingest_ojm"][
        "reference_triples"
    ] > 0
    assert phases["ingest_som"]["triples"] == phases["ingest_som"][
        "naive_triples"
    ] > 0
    assert phases["serve"]["fastpath_dispatches"] > 0
    assert phases["serve"]["queries"] == 70


def test_ojm_reference_catches_a_lost_triple(tmp_path):
    """The host join is a real check: the engine's KG minus one triple
    no longer matches it."""
    clock = chip_smoke.CompileClock()
    _kgz, _store, triples = chip_smoke.ingest_ojm_phase(
        str(tmp_path), 500, clock
    )
    ref = chip_smoke.ojm_reference(str(tmp_path / "ojm"), 2)
    assert set(triples) == ref
    assert set(triples[1:]) != ref


_FOUR = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke
chip_smoke.PLATFORM = "cpu"
chip_smoke.ROWS = 800
sys.exit(chip_smoke.main(["--four-chips", "--out", {out!r}]))
"""


def test_four_chip_phase_on_virtual_devices(tmp_path):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(ROOT, "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=ROOT, out=str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = _lines(proc.stdout)
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == 4
    four = next(ln for ln in lines if ln.get("phase") == "four_chips")
    assert four["placement"] == [[f"TFRT_CPU_{i}"] for i in range(4)]


@pytest.mark.parametrize("argv", [["--four-chips"], []])
def test_alone_without_the_repo_fails(tmp_path, argv):
    """Copied away from the repository, the script cannot import it and
    exits non-zero without a result line."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(chip_smoke.__file__, encoding="utf-8").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(lone), *argv], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
