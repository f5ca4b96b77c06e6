"""The control of a cell's comparison, at the cell's own size.

    python3 bench/control.py --workload <name> --seeds 11 12 13

The control is the configuration's reference (``registry.reference``)
put in the program's place with its instruction counts in bfloat16, the
precision below the float32 the configuration states.  For each seed:
as many cells of the seed's job 0 as a run compares, the longest among
them, each simulated by the reference in float32 and by the control,
and compared as a run compares the program.  Prints one JSON line per
seed with the numbers a run compares, beside the limits ``check.py``
holds them to.  Neither needs a chip; the benchmark's runs never run
this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import ml_dtypes
    import numpy as np

    from bench.lib import check, gen, registry

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bm = registry.benchmark()
    wl = registry.workload(bm, args.workload)
    cfg = registry.config(bm, wl["config"])
    simulate_many = registry.reference(cfg).simulate_many
    traffic = registry.traffic(wl["traffic"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        pairs = gen.make_job(cfg, traffic, seed, 0).expanded()
        cells = [check.reference_cell(cfg, traffic, c, p) for c, p in pairs]
        want = simulate_many(cells)
        longest = max(range(len(pairs)),
                      key=lambda i: float(want[i]["makespan_ns"]))
        rest = [i for i in range(len(pairs)) if i != longest]
        rng = gen.job_rng(seed, 2**32)
        picked = [longest] + sorted(int(i) for i in rng.choice(
            rest, min(check.SAMPLE - 1, len(rest)), replace=False))
        control = simulate_many([cells[i] for i in picked],
                                ml_dtypes.bfloat16)
        gaps = [check.compare(got, want[i])
                for got, i in zip(control, picked)]
        failed = sum(not (np.all(o["served"] == traffic["n_req"])
                          and int(o["ref_debt_end"]) == 0)
                     for o in control)
        print(json.dumps({
            "workload": wl["name"], "seed": seed,
            "cells": [f"{pairs[i][0].name}|{pairs[i][1]['name']}"
                      for i in picked],
            "failed_cells": failed,
            "int_mismatch": sum(g[0] for g in gaps),
            "float_rel_gap": max(g[1] for g in gaps),
            "limits": {"failed_cells": 0, "int_mismatch": 0,
                       "float_rel_gap": check.FLOAT_LIMIT},
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
