"""A run with the timed path broken underneath must come out not
correct; the control must fail the check that sound runs pass."""

import numpy as np
import pytest

from benchsteer import run_cell, steer

import harness


def _drop_one_triple_kgz(monkeypatch):
    from repro.kg import persist
    from repro.kg.store import TripleStore

    save = persist.save

    def save_short(store, path, **kw):
        short = TripleStore.build(store.dictionary, store.term_pat, store.term_val,
                                  store.s[1:], store.p[1:], store.o[1:])
        return save(short, path, **kw)

    monkeypatch.setattr(persist, "save", save_short)


def _drop_one_triple_nt(monkeypatch):
    from repro.core.executor import KGResult

    write = KGResult.write_ntriples

    def write_short(self, path):
        n = write(self, path)
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()
        with open(path, "w", encoding="utf-8") as f:
            f.writelines(lines[1:])
        return n - 1

    monkeypatch.setattr(KGResult, "write_ntriples", write_short)


@pytest.mark.parametrize("workload, fault", [
    ("ojm-100k-ingest", _drop_one_triple_kgz),
    ("som-100k-ingest", _drop_one_triple_nt),
])
def test_dropped_triple_is_not_correct(workload, fault, tmp_path, monkeypatch, capsys):
    steer(monkeypatch, tmp_path)
    fault(monkeypatch)
    rc, result, err = run_cell(capsys, workload)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    assert result["checks"]["missing_triples"]["value"] >= 1
    assert result["checks"]["bad_jobs"]["value"] == result["attempted"]


@pytest.mark.parametrize("workload", ["ojm-1m-kg-chain", "ojm-1m-kg-general"])
def test_dropped_row_is_not_correct(workload, tmp_path, monkeypatch, capsys):
    """One row left out of the answers where the executor produces them."""
    from repro.serve.exec import BatchResult

    steer(monkeypatch, tmp_path)
    rows = BatchResult.rows

    def rows_short(self, i, limit=None):
        out = list(rows(self, i, limit=limit))
        return out[1:]

    monkeypatch.setattr(BatchResult, "rows", rows_short)
    rc, result, err = run_cell(capsys, workload)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] >= 1


@pytest.mark.parametrize("workload", ["ojm-100k-ingest", "som-100k-ingest",
                                      "ojm-1m-kg-chain", "ojm-1m-kg-general"])
def test_control_fails_the_check(workload, tmp_path, monkeypatch):
    """The control (the plain reference with one guarantee broken) fails
    a limit that the program's own output meets."""
    import run

    steer(monkeypatch, tmp_path)
    cell = harness.find_cell(workload)  # steered: the serve cells come from serve_cells.json
    kind = cell.kind()
    ctx = run.context(cell, 2_147_483_701, 1.0)
    state = kind.setup(ctx)
    try:
        win = kind.window(state, 1.0)
    finally:
        kind.release(state)
    program = kind.check(state, win)
    control = kind.control(state)
    assert all(v <= lim for _n, v, lim in program)
    assert any(v > lim for _n, v, lim in control)
    assert [n for n, _v, _l in program] == [n for n, _v, _l in control]
    assert np.isfinite([v for _n, v, _l in control]).all()
