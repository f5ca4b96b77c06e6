"""Golden regression harness for the SMLA cycle engine.

Pins every scalar metric (plus per-core served/ipc) of the tiny grid in
`repro.core.smla.golden` — 2 workloads x 5 configs x {2,4} layers, with
writes, fast refresh, and power-down all exercised — to checked-in values,
so silent numeric drift in the engine fails CI with a per-cell, per-metric
diff.  Integer metrics must match exactly; floats to `golden.RTOL`.

Regenerate after an *intentional* engine change with:

    PYTHONPATH=src python -m pytest tests/test_golden.py --update-golden

and commit the new `tests/golden/smla_small_grid.json` alongside the
engine change that explains it.  (No hypothesis dependency — this module
must run in a bare environment.)
"""
import json
import pathlib

import pytest

from repro.core.smla import engine, golden, sweep
from repro.core.smla.config import paper_configs
from repro.core.smla.engine import SimOptions

GOLDEN_PATH = pathlib.Path(__file__).parent / "golden" / "smla_small_grid.json"


def _run_grid() -> dict:
    cells = golden.grid_cells()
    c0 = engine.compile_count()
    res = sweep.run_sweep(sweep.SweepSpec(
        tuple(cells), options=SimOptions(horizon=golden.HORIZON)))
    compiles = engine.compile_count() - c0
    assert compiles <= len(set(res.chunks)), \
        f"golden grid is one static shape group (x auto-chunk widths), " \
        f"took {compiles} compiles"
    return golden.pinned_metrics(res)


def test_golden_small_grid(request):
    got = _run_grid()
    if request.config.getoption("--update-golden"):
        GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "meta": {"horizon": golden.HORIZON, "n_req": golden.N_REQ,
                     "seed": golden.SEED,
                     "workloads": [w.name for w in golden.GRID_WORKLOADS],
                     "note": "regenerate: PYTHONPATH=src python -m pytest "
                             "tests/test_golden.py --update-golden"},
            "cells": got,
        }
        GOLDEN_PATH.write_text(json.dumps(payload, indent=1, sort_keys=True)
                               + "\n")
        pytest.skip(f"golden file regenerated at {GOLDEN_PATH}")

    assert GOLDEN_PATH.exists(), \
        "golden file missing — run pytest tests/test_golden.py --update-golden"
    want = json.loads(GOLDEN_PATH.read_text())["cells"]
    errors = golden.mismatches(got, want)
    assert not errors, "engine drifted from golden:\n" + "\n".join(errors)


def test_golden_exercises_new_machinery():
    """The pinned grid must actually cover writes, refresh, and power-down,
    otherwise the golden file can't protect those paths."""
    cells = json.loads(GOLDEN_PATH.read_text())["cells"]
    assert any(c["n_wr"] > 0 for c in cells.values())
    assert any(c["refresh_cycles"] > 0 for c in cells.values())
    assert any(c["pd_cycles"] > 0 for c in cells.values())
    slotted = [c for n, c in cells.items() if "cascaded_slr" in n]
    assert slotted and all(c["n_slot_grants"] == c["n_grants"]
                           for c in slotted)
    # the default-policy grid must pin the refresh/power machinery OFF
    assert all(c["sr_cycles"] == 0 and c["ref_debt_max"] == 0
               for c in cells.values())
    # the refresh accounting fix, pinned at grid level: per-cycle accrual
    # never exceeds one count per rank per makespan cycle
    for name, c in cells.items():
        layers_s, cname = name.split("/")[:2]
        sc = paper_configs(int(layers_s[1:]))[cname]
        mk_cyc = c["makespan_ns"] / sc.unit_ns
        assert c["refresh_cycles"] <= mk_cyc * sc.n_ranks, name
