"""Each fault a cell can have, planted under a whole run at a tiny size,
turns ``correct`` false.  The runs skip only the look for a chip."""
import numpy as np
import pytest

from bench.lib import check
from bench.tests.conftest import run_cell
from repro.core.smla import engine

CELLS = ("smla4-mp16", "smla8-policy")


@pytest.fixture
def every_cell_checked(monkeypatch):
    """Compare every finished cell, so that a fault in any row shows."""
    monkeypatch.setattr(check, "SAMPLE", 10**6)


@pytest.fixture
def fresh_executables():
    """Executables built around a planted fault must not outlive it."""
    engine._compiled.cache_clear()
    yield
    engine._compiled.cache_clear()


def _wrap_outputs(monkeypatch, change):
    real = engine.batched_simulate

    def broken(*args, **kwargs):
        out = {k: np.array(v) for k, v in real(*args, **kwargs).items()}
        return change(out)
    monkeypatch.setattr(engine, "batched_simulate", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tiny, one_job, every_cell_checked):
    out = run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["cells_checked"]["value"] == out["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(cell, tiny, one_job,
                                               every_cell_checked,
                                               fresh_executables,
                                               monkeypatch):
    monkeypatch.setattr(engine, "_STAGES", ())
    out = run_cell(cell)
    assert not out["correct"]
    assert out["checks"]["failed_cells"]["value"] == out["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out(cell, tiny, one_job, every_cell_checked,
                                 monkeypatch):
    def half(out):
        n = len(out["served"])
        keep = max(n // 2, 1)
        for k, v in out.items():
            mean = v[:keep].mean(axis=0)
            v[keep:] = mean.astype(v.dtype) if v.dtype != bool else mean > .5
        return out
    _wrap_outputs(monkeypatch, half)
    out = run_cell(cell)
    assert not out["correct"]
    assert out["checks"]["int_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(cell, tiny, one_job,
                                       every_cell_checked, monkeypatch):
    def bump(out):
        out["n_act"] = out["n_act"] + 1
        return out
    _wrap_outputs(monkeypatch, bump)
    out = run_cell(cell)
    assert not out["correct"]
    assert out["checks"]["int_mismatch"]["value"] == out["attempted"]
