"""Paper Fig. 11: single-core performance + energy across 31 workloads,
both rank organisations.  Synthetic-trace stand-ins (see core/smla/traces):
suite means are the comparison target; paper values in the footer.

The whole 31-workload x 5-config grid runs as ONE vmapped jit via the
batched sweep engine (at most one compile), instead of 155 separate
compile+scan invocations."""
import time

import numpy as np

from benchmarks._util import FigureRecord, perf_block, scaled
from repro.core.smla import engine, sweep
from repro.core.smla.analytic import default_horizon
from repro.core.smla.config import paper_configs
from repro.core.smla.energy import energy_from_metrics
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WORKLOADS


def run(n_req: int = 600, horizon: int | None = None) -> list[str]:
    n_req = scaled(n_req, 80)
    cfgs = paper_configs(4)
    workloads = [(w.name, [w], 0) for w in WORKLOADS]
    cells = sweep.paper_grid(workloads, layers=(4,), n_req=n_req)
    if horizon is None:
        # analytic worst case for the full run; smoke keeps the historic
        # tiny horizon so its numbers stay comparable across commits
        horizon = scaled(default_horizon(cells), 6_000)

    spec = sweep.SweepSpec(tuple(cells), options=SimOptions(horizon=horizon))
    c0, t0 = engine.compile_count(), time.perf_counter()
    res = sweep.run_sweep(spec)
    wall = time.perf_counter() - t0
    compiles = engine.compile_count() - c0
    # one shape group; the auto-chunk ladder may add one compile per
    # distinct bucket width (cached across runs), never more
    assert compiles <= len(set(res.chunks)), \
        f"fig11 grid took {compiles} compiles " \
        f"(want <= {len(set(res.chunks))} chunk widths)"

    def metrics(cname, wname):
        return res[f"L4/{cname}/{wname}"]

    rows = ["workload,mpki,dio_slr,cio_slr,dio_mlr,cio_mlr,"
            "E_dio_slr,E_cio_slr"]
    per = {k: [] for k in ("dio_slr", "cio_slr", "dio_mlr", "cio_mlr",
                           "e_dio", "e_cio")}
    table = []
    for w in WORKLOADS:
        base = metrics("baseline", w.name)
        base_e = energy_from_metrics(cfgs["baseline"], base).total_nj

        def ws(cname):
            m = metrics(cname, w.name)
            return float(np.mean(m["ipc"] / np.maximum(base["ipc"], 1e-9)))

        def erel(cname):
            return energy_from_metrics(cfgs[cname],
                                       metrics(cname, w.name)).total_nj / base_e

        vals = {
            "dio_slr": ws("dedicated_slr"), "cio_slr": ws("cascaded_slr"),
            "dio_mlr": ws("dedicated_mlr"), "cio_mlr": ws("cascaded_mlr"),
            "e_dio": erel("dedicated_slr"), "e_cio": erel("cascaded_slr"),
        }
        for k, v in vals.items():
            per[k].append(v)
        table.append(dict(workload=w.name, mpki=w.mpki, **vals))
        rows.append(f"{w.name},{w.mpki},{vals['dio_slr']:.3f},"
                    f"{vals['cio_slr']:.3f},{vals['dio_mlr']:.3f},"
                    f"{vals['cio_mlr']:.3f},{vals['e_dio']:.3f},"
                    f"{vals['e_cio']:.3f}")
    gm = lambda v: float(np.exp(np.mean(np.log(np.maximum(v, 1e-9)))))
    rows.append(f"GEOMEAN,,{gm(per['dio_slr']):.3f},{gm(per['cio_slr']):.3f},"
                f"{gm(per['dio_mlr']):.3f},{gm(per['cio_mlr']):.3f},"
                f"{gm(per['e_dio']):.3f},{gm(per['e_cio']):.3f}")
    rows.append("# paper (SPEC/TPC/STREAM): SLR +19.2% DIO / +23.9% CIO; "
                "MLR +8.8%; energy +8.6%/+4.6% (single-core)")
    # write / refresh / power-down residency over the whole grid (the
    # energy relatives above already price these via the measured metrics)
    scal = res.scalars()
    rows.append(f"# traffic: {int(scal['n_wr'].sum())} writes retired, "
                f"mean pd_frac {float(scal['pd_frac'].mean()):.3f}, "
                f"{int(scal['refresh_cycles'].sum())} refresh cycles")
    perf = perf_block(wall, res, horizon)
    rows.append(f"# sweep: {len(cells)} cells, {compiles} compiles, "
                f"{wall:.1f}s wall, {perf['cells_per_s']:.1f} cells/s, "
                f"early-exit saved {perf['early_exit_frac']:.0%} of chunks")
    FigureRecord.from_sweep("fig11", res, wall, horizon=horizon,
                            compiles=compiles, extra={
        "n_req": n_req,
        "geomean": {k: gm(v) for k, v in per.items()},
        "total_n_wr": int(scal["n_wr"].sum()),
        "mean_pd_frac": float(scal["pd_frac"].mean()),
        "total_refresh_cycles": int(scal["refresh_cycles"].sum()),
        "rows": table,
    }).emit()

    return rows


if __name__ == "__main__":
    print("\n".join(run()))
