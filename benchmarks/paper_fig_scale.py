"""Sweep-engine scaling figure: cells/s and buckets/s vs grid size,
streaming pipeline vs the legacy synchronous runner, plus the
successive-halving work saving on a 1e4-cell grid.

Methodology — every measurement is a **fresh subprocess** timed around
`run_sweep` only (imports and grid construction excluded), because the
quantity that matters for million-cell campaigns is the cold-process
sweep latency a journal resume or a fleet worker actually pays:

* ``sync``        — `SweepSpec(streaming=False)`, persistent compilation
                    cache off: the strict prepare->execute->harvest loop
                    paying full XLA compilation in-process (what every
                    sweep cost before the streaming engine).
* ``stream_cold`` — the async pipeline with an emptied persistent
                    compilation cache (`cache_dir`, a fixed path): pays
                    compilation once and *populates* the cache.
* ``stream_warm`` — the pipeline against the populated cache: what every
                    subsequent process (resume, next fleet worker, next
                    grid chunk) pays.  This is the headline `ratio` row
                    against ``sync``, gated >= 1.3x by
                    `benchmarks/assert_early_exit.py` on the CI smoke
                    grid.

All three modes must produce the identical bandwidth checksum — the
benchmark hard-fails on any numeric divergence, so the perf row can
never come from a wrong answer.  The `prune` section runs a >= 1e4-cell
replicated grid under `PruneSpec(0.125, 0.5, 1)` and records the
fraction of full-horizon device work avoided (gated >= 50% by the
pinned test `tests/test_sweep_streaming.py::
test_prune_halves_work_on_large_grid`).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

from benchmarks._util import emit_json, scaled, smoke_mode

#: grid sizes as workload counts: n_cells = k workloads x 2 layer counts
#: x 5 IO models (one static shape group — the steady-state regime)
SIZES_FULL = (6, 24, 96)
SIZES_SMOKE = (3, 12)

_CHILD = r"""
import json, sys, time
cfg = json.loads(sys.argv[1])
from repro.core.smla import engine, sweep
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WorkloadSpec
from benchmarks._util import progress_printer

STREAM = WorkloadSpec("stream.t", 50.0, 0.85, write_frac=1 / 3)
cells = sweep.paper_grid(
    [(f"w{s}", [STREAM, STREAM], s) for s in range(cfg["k"])],
    layers=(2, 4), n_req=cfg["n_req"])
opts = SimOptions(horizon=cfg["horizon"])
spec = sweep.SweepSpec(tuple(cells), options=opts,
                       streaming=cfg["streaming"],
                       on_bucket=progress_printer(cfg["label"]))
t0 = time.time()
res = sweep.run_sweep(spec)
wall = max(time.time() - t0, 1e-9)
tab = res.scalars(keys=("bandwidth_gbps",))
print("RESULT " + json.dumps({
    "wall_s": round(wall, 3),
    "n_cells": len(res.names),
    "cells_per_s": round(len(res.names) / wall, 3),
    "n_buckets": len(res.buckets),
    "buckets_per_s": round(len(res.buckets) / wall, 3),
    "compiles": engine.compile_count(),
    "checksum_bandwidth": float(tab["bandwidth_gbps"].sum()),
}))
"""

_PRUNE_CHILD = r"""
import json, sys, time
cfg = json.loads(sys.argv[1])
from repro.core.smla import sweep
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WorkloadSpec
from benchmarks._util import progress_printer

STREAM = WorkloadSpec("stream.t", 50.0, 0.85, write_frac=1 / 3)
base = sweep.paper_grid([("s", [STREAM, STREAM], 3)], layers=(2,),
                        n_req=cfg["n_req"])[:4]
reps = -(-cfg["n_cells"] // len(base))
cells = tuple(sweep.SweepCell(f"{c.name}#r{i}", c.stack, c.traces)
              for i in range(reps) for c in base)
spec = sweep.SweepSpec(cells, options=SimOptions(horizon=cfg["horizon"]),
                       prune=sweep.PruneSpec(horizon_frac=0.125,
                                             keep_frac=0.5, rounds=1),
                       on_bucket=progress_printer("fig_scale:prune"))
t0 = time.time()
res = sweep.run_sweep(spec)
wall = max(time.time() - t0, 1e-9)
out = dict(res.prune_work)
out.update(wall_s=round(wall, 3), n_promoted=len(res.names),
           n_pruned=len(res.pruned),
           cells_per_s=round(out["n_cells"] / wall, 3))
print("RESULT " + json.dumps(out))
"""


def cache_dir(k: int) -> str:
    """The persistent compilation cache of grid size `k`'s streaming
    runs: a fixed path inside ``JAX_COMPILATION_CACHE_DIR`` when that is
    set, else inside the checkout's ``.jax_cache``
    (`engine.DEFAULT_COMPILE_CACHE_DIR`)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    return os.path.join(base, "fig_scale", f"k{k}")


def _run_child(code: str, cfg: dict, env: dict | None = None) -> dict:
    # This parent never imports JAX, so the child is the one process
    # holding the accelerator while it runs.
    r = subprocess.run([sys.executable, "-c", code, json.dumps(cfg)],
                       capture_output=True, text=True,
                       env=dict(os.environ, **(env or {})))
    if r.returncode != 0:
        raise RuntimeError(f"fig_scale child failed ({cfg.get('label')}):\n"
                           f"{r.stdout}\n{r.stderr}")
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"fig_scale child printed no RESULT:\n{r.stdout}")


def run_size(k: int, n_req: int, horizon: int) -> dict:
    cache = cache_dir(k)
    base = {"k": k, "n_req": n_req, "horizon": horizon}
    sync = _run_child(_CHILD, dict(base, streaming=False,
                                   label=f"fig_scale:sync:k{k}"),
                      env={"JAX_ENABLE_COMPILATION_CACHE": "false"})
    shutil.rmtree(cache, ignore_errors=True)        # the cold phase
    cache_env = {"JAX_COMPILATION_CACHE_DIR": cache}
    cold = _run_child(_CHILD, dict(base, streaming=True,
                                   label=f"fig_scale:cold:k{k}"),
                      env=cache_env)
    warm = _run_child(_CHILD, dict(base, streaming=True,
                                   label=f"fig_scale:warm:k{k}"),
                      env=cache_env)
    checks = {m["checksum_bandwidth"] for m in (sync, cold, warm)}
    if len(checks) != 1:
        raise RuntimeError(f"fig_scale k={k}: modes disagree on the "
                           f"bandwidth checksum: {checks}")
    return {"n_cells": sync["n_cells"], "n_buckets": sync["n_buckets"],
            "sync": sync, "stream_cold": cold, "stream_warm": warm,
            "ratio": round(warm["cells_per_s"]
                           / max(sync["cells_per_s"], 1e-9), 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (sets SMLA_SMOKE=1)")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["SMLA_SMOKE"] = "1"

    n_req = scaled(120, 24)
    horizon = scaled(6_000, 2_000)
    sizes = SIZES_SMOKE if smoke_mode() else SIZES_FULL
    rows = []
    for k in sizes:
        row = run_size(k, n_req, horizon)
        rows.append(row)
        print(f"n_cells={row['n_cells']:5d}  "
              f"sync={row['sync']['cells_per_s']:8.1f}  "
              f"stream_warm={row['stream_warm']['cells_per_s']:8.1f} "
              f"cells/s  ratio={row['ratio']:.2f}x  "
              f"({row['n_buckets']} buckets)", flush=True)

    prune = _run_child(_PRUNE_CHILD, {
        "n_cells": scaled(20_000, 10_000), "n_req": scaled(10, 6),
        "horizon": scaled(1_024, 512)})
    print(f"prune: {prune['n_cells']} cells -> {prune['n_promoted']} "
          f"promoted, saved {prune['saved_frac']:.0%} of full-horizon "
          f"work in {prune['wall_s']:.1f}s", flush=True)

    path = emit_json("fig_scale", {
        "rows": rows,
        "ratio_best": max(r["ratio"] for r in rows),
        "prune": prune,
        "methodology": ("per-mode fresh subprocess timed around run_sweep; "
                        "sync = streaming=False, compilation cache off, "
                        "stream_warm = pipeline + populated persistent "
                        "compile cache")})
    print(f"fig_scale -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
