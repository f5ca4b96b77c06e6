"""Paper Fig. 12: multi-programmed weighted speedup + energy, 4/8/16 cores.

Channel model: the paper's 16-core system has 4 channels -> 4 cores/channel;
we simulate one channel with cores/4 cores and report per-config means over
`n_mixes` random mixes (paper: 16 mixes/pool).

The full grid (3 core counts x mixes x 5 configs) runs through the batched
sweep engine — cells sharing a core count share one vmapped jit, so the
whole figure costs at most one compile per core count.  One grid cell is
cross-checked bit-for-bit against a standalone `simulate()` call."""
import time

import numpy as np

from benchmarks._util import FigureRecord, perf_block, scaled
from repro.core.smla import engine, sweep
from repro.core.smla.analytic import default_horizon
from repro.core.smla.config import paper_configs
from repro.core.smla.energy import energy_from_metrics
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WORKLOADS

SMLA = ("dedicated_slr", "cascaded_slr", "dedicated_mlr", "cascaded_mlr")
CORES = (4, 8, 16)


def grid_cells(n_mixes: int = 6, n_req: int = 500, seed: int = 0):
    """The figure's cells, 'c{cores}/m{mix}/{config}', and each mix's
    workload names: per core count, `n_mixes` random mixes x the 5 IO
    configurations."""
    rng = np.random.default_rng(seed)
    cfgs = paper_configs(4)
    cells, mixes = [], {}
    for cores in CORES:
        per_chan = max(cores // 4, 1)
        for m in range(n_mixes):
            specs = [WORKLOADS[i] for i in
                     rng.choice(len(WORKLOADS), per_chan, replace=False)]
            mixes[(cores, m)] = [s.name for s in specs]
            for cname, sc in cfgs.items():
                cells.append(sweep.make_cell(
                    f"c{cores}/m{m}/{cname}", sc, specs, n_req,
                    seed=seed + m))
    return cells, mixes


def compile_bound(res: sweep.SweepResult) -> int:
    """One shape group per core count, times the auto-chunk ladder widths
    actually used (each cached across runs)."""
    return len(CORES) * max(len(set(res.chunks)), 1)


def probe_mismatches(cells, res: sweep.SweepResult,
                     horizon: int) -> list[str]:
    """Metrics in which the grid's first cell differs from a standalone
    `simulate()` of it.  Chunk width is an execution detail, so every
    metric but `chunks_run` must be equal."""
    probe = cells[0]
    ref = engine.simulate(probe.stack, probe.traces,
                          SimOptions(horizon=horizon))
    return [k for k in ref if k != "chunks_run"
            and not np.array_equal(np.asarray(ref[k]), res[probe.name][k])]


def run(n_mixes: int = 6, n_req: int = 500, horizon: int | None = None,
        seed: int = 0) -> list[str]:
    n_mixes = scaled(n_mixes, 2)
    n_req = scaled(n_req, 80)
    cfgs = paper_configs(4)
    cells, mixes = grid_cells(n_mixes, n_req, seed)
    if horizon is None:
        horizon = scaled(default_horizon(cells), 6_000)

    spec = sweep.SweepSpec(tuple(cells), options=SimOptions(horizon=horizon))
    c0, t0 = engine.compile_count(), time.perf_counter()
    res = sweep.run_sweep(spec)
    wall = time.perf_counter() - t0
    compiles = engine.compile_count() - c0
    bound = compile_bound(res)
    assert compiles <= bound, \
        f"fig12 grid took {compiles} compiles (want <= {bound})"

    # acceptance cross-check: one cell must equal the per-config path exactly
    bad = probe_mismatches(cells, res, horizon)
    assert not bad, f"sweep metrics diverge from per-config simulate(): {bad}"

    rows = ["cores,config,ws_vs_baseline,energy_vs_baseline,"
            "pd_frac,wr_share"]
    table = []
    for cores in CORES:
        acc = {k: ([], [], [], []) for k in SMLA}
        for m in range(n_mixes):
            base = res[f"c{cores}/m{m}/baseline"]
            base_e = energy_from_metrics(cfgs["baseline"], base).total_nj
            for k in acc:
                mm = res[f"c{cores}/m{m}/{k}"]
                acc[k][0].append(float(np.mean(
                    mm["ipc"] / np.maximum(base["ipc"], 1e-9))))
                acc[k][1].append(
                    energy_from_metrics(cfgs[k], mm).total_nj / base_e)
                acc[k][2].append(float(mm["pd_frac"]))
                acc[k][3].append(int(mm["n_wr"])
                                 / max(int(np.asarray(mm["served"]).sum()),
                                       1))
        for k, (ws, en, pd, wshare) in acc.items():
            rows.append(f"{cores},{k},{np.mean(ws):.3f},{np.mean(en):.3f},"
                        f"{np.mean(pd):.3f},{np.mean(wshare):.3f}")
            table.append(dict(cores=cores, config=k,
                              ws=float(np.mean(ws)),
                              energy=float(np.mean(en)),
                              pd_frac=float(np.mean(pd)),
                              wr_share=float(np.mean(wshare))))
    rows.append("# paper: 16-core SLR ws +50.4% DIO / +55.8% CIO; "
                "energy -17.9% (CIO SLR); MLR below SLR")
    perf = perf_block(wall, res, horizon)
    rows.append(f"# sweep: {len(cells)} cells, {compiles} compiles, "
                f"{wall:.1f}s wall, early-exit saved "
                f"{perf['early_exit_frac']:.0%} of chunks")
    FigureRecord.from_sweep("fig12", res, wall, horizon=horizon,
                            compiles=compiles, include_scalars=False,
                            extra={
        "n_mixes": n_mixes, "n_req": n_req,
        "mixes": {f"c{c}/m{m}": v for (c, m), v in mixes.items()},
        "rows": table,
    }).emit()
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
