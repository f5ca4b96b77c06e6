"""The golden grid: a tiny sweep whose every metric is pinned to
checked-in values (``tests/golden/smla_small_grid.json``).

2 workloads x 5 IO models x {2,4} layers = 20 cells, with writes, fast
refresh and power-down all exercised.  ``tests/test_golden.py`` compares
the engine against the file on the CPU, and ``chip_smoke.py`` does the
same on the TPU, so both read the grid and the comparison from here.

Integer metrics must match exactly; floats to `RTOL` (engine arithmetic
is deterministic, but float reductions may reassociate across
platforms).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.smla import sweep
from repro.core.smla.config import paper_configs
from repro.core.smla.traces import WORKLOADS

HORIZON = 4_000
N_REQ = 80
SEED = 13
#: one low-intensity read-heavy and one high-intensity write-heavy workload
GRID_WORKLOADS = (WORKLOADS[4], WORKLOADS[26])      # low.05, stream.1

INT_METRICS = ("n_act", "n_row_conflicts", "n_wr", "bus_cycles",
               "wr_bus_cycles", "refresh_cycles", "pd_cycles", "n_grants",
               "n_slot_grants", "n_enqueued", "n_outstanding",
               # refresh/power subsystem counters — identically zero under
               # the default policy, pinned so the golden grid also guards
               # the new machinery's bit-identity when disabled
               "ref_postponed", "ref_pulled_in", "ref_debt_max",
               "ref_debt_end", "sr_cycles", "n_sr_exit")
FLOAT_METRICS = ("bandwidth_gbps", "bus_util", "pd_frac", "sr_frac",
                 "makespan_ns", "horizon_ns")
RTOL = 1e-6


def grid_cells() -> list[sweep.SweepCell]:
    cells = []
    for layers in (2, 4):
        for cname, sc in paper_configs(layers).items():
            # fast refresh so tREFI/tRFC paths are pinned inside the tiny
            # horizon; everything else is the stock configuration
            sc = dataclasses.replace(sc, t_refi_ns=1200.0)
            for w in GRID_WORKLOADS:
                cells.append(sweep.make_cell(
                    f"L{layers}/{cname}/{w.name}", sc, [w, w], N_REQ,
                    seed=SEED))
    return cells


def pinned_metrics(res: sweep.SweepResult) -> dict:
    """{cell name: the pinned metrics} of a sweep over `grid_cells()`,
    in the golden file's JSON form."""
    out = {}
    for name, m in zip(res.names, res.cells):
        cell = {k: int(np.asarray(m[k])) for k in INT_METRICS}
        cell.update({k: float(np.asarray(m[k])) for k in FLOAT_METRICS})
        cell["served"] = np.asarray(m["served"]).astype(int).tolist()
        cell["ipc"] = np.asarray(m["ipc"]).astype(float).tolist()
        out[name] = cell
    return out


def mismatches(got: dict, golden: dict) -> list[str]:
    """One line per (cell, metric) where `got` departs from `golden`."""
    if sorted(got) != sorted(golden):
        return [f"grid cell set changed: got {sorted(got)}, "
                f"want {sorted(golden)}"]
    errors = []
    for name, g in golden.items():
        m = got[name]
        for k in INT_METRICS:
            if m[k] != g[k]:
                errors.append(f"{name}:{k} got {m[k]} want {g[k]}")
        if m["served"] != g["served"]:
            errors.append(f"{name}:served got {m['served']} "
                          f"want {g['served']}")
        for k in FLOAT_METRICS:
            if not np.isclose(m[k], g[k], rtol=RTOL, atol=0.0):
                errors.append(f"{name}:{k} got {m[k]!r} want {g[k]!r}")
        if not np.allclose(m["ipc"], g["ipc"], rtol=RTOL, atol=0.0):
            errors.append(f"{name}:ipc got {m['ipc']} want {g['ipc']}")
    return errors
