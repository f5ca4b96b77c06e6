"""Bring-up check: the SMLA sweep's main path on the TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded sweep only

One chip runs these phases in order:

* device: ``jax.devices()[0]`` must be a TPU.  The script never carries
  on on another platform.
* golden: the 20-cell grid of ``repro.core.smla.golden`` through
  ``run_sweep``, compared with ``tests/golden/smla_small_grid.json``
  (integers exact, floats to ``golden.RTOL``).
* fig12: the paper's Fig. 12 grid at full size (4/8/16 cores x 6 mixes x
  5 configurations = 90 cells, ``n_req=500``, horizon from
  ``analytic.default_horizon``) on the scan backend.  Every core of every
  cell serves its ``n_req``, no refresh debt is left, no bucket fails,
  compiles stay within the figure's bound, and the figure's probe cell
  equals a standalone ``simulate`` in every metric but ``chunks_run``.

``--chips 4`` runs the device phase and then only the sharded sweep: the
same Fig. 12 grid across the four chips, once on the default path (the
per-device while-loop, "local" cond) and once with
``cond_sharding="global"``.  Both are compared with the same buckets run
unsharded on device 0, every metric but ``chunks_run`` equal.

Every sweep runs with ``on_error="raise"`` and no retries, so a compiler
or runtime error stops the script at once.  Lines before the last are
notes (device kind, cells, executables, how many of them XLA compiled
or loaded from the persistent cache and the seconds each took, wall
time with the first bucket, which builds them, apart from the rest).  The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed.  The script writes into no tracked file;
JAX's compile cache goes where ``engine.compile_cache_dir`` says.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import paper_fig12  # noqa: E402
from repro.core.smla import engine, golden, sweep  # noqa: E402
from repro.core.smla.analytic import default_horizon  # noqa: E402
from repro.core.smla.engine import SimOptions  # noqa: E402

GOLDEN_FILE = os.path.join(ROOT, "tests", "golden", "smla_small_grid.json")


class PhaseError(Exception):
    """A phase found the system wrong: the script exits non-zero."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def _note(msg: str) -> None:
    print(msg, flush=True)


def device_phase(n_chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    _check(d.platform == "tpu",
           f"no TPU: jax.devices()[0] is a {d.platform} device")
    _check(len(devs) >= n_chips,
           f"{n_chips} chips asked for, {len(devs)} visible")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    _note(f"device: {device}")
    return device


def run_sweep_timed(label: str, cells, opts: SimOptions,
                    **spec_kw) -> tuple[sweep.SweepResult, int]:
    """`run_sweep` with no retries and no journal; notes its wall time
    with the first bucket apart.  Returns (result, compiles)."""
    marks: list[float] = []
    spec = sweep.SweepSpec(
        tuple(cells), options=opts, on_error="raise", max_retries=0,
        on_bucket=lambda done, total, wall_s, cps: marks.append(wall_s),
        **spec_kw)
    s0, t0 = engine.compile_stats(), time.perf_counter()
    res = sweep.run_sweep(spec)
    wall = time.perf_counter() - t0
    s1 = engine.compile_stats()
    compiles = s1.lru_misses - s0.lru_misses
    first = marks[0] if marks else wall
    # fast cycles the device stepped: each bucket runs until its slowest
    # cell exits, whole chunks at a time
    cycles = sum(b["chunks_run"] * b["chunk"] for b in res.buckets)
    _note(f"[{label}] {len(res.names)} cells, {len(res.buckets)} buckets, "
          f"{compiles} compiles ({s1.xla_compiles - s0.xla_compiles} by "
          f"XLA {s1.compile_s - s0.compile_s:.1f}s, "
          f"{s1.cache_loads - s0.cache_loads} cache loads "
          f"{s1.load_s - s0.load_s:.1f}s, tracing and lowering "
          f"{s1.trace_lower_s - s0.trace_lower_s:.1f}s), horizon "
          f"{opts.horizon}, {cycles} bucket cycles, wall {wall:.1f}s "
          f"(first bucket {first:.1f}s, the rest {wall - first:.1f}s)")
    _check(not res.failed_buckets,
           f"{label}: failed buckets {res.failed_buckets}")
    _check(len(res.names) == len(cells),
           f"{label}: {len(res.names)} of {len(cells)} cells came back")
    return res, compiles


def golden_phase() -> None:
    res, _ = run_sweep_timed("golden", golden.grid_cells(),
                             SimOptions(horizon=golden.HORIZON))
    with open(GOLDEN_FILE) as f:
        want = json.load(f)["cells"]
    errors = golden.mismatches(golden.pinned_metrics(res), want)
    _check(not errors, "golden grid differs on the chip:\n"
           + "\n".join(errors[:40]))


def fig12_phase(n_mixes: int = 6, n_req: int = 500) -> None:
    cells, _ = paper_fig12.grid_cells(n_mixes, n_req)
    horizon = default_horizon(cells)
    res, compiles = run_sweep_timed("fig12", cells,
                                    SimOptions(horizon=horizon))
    short = [n for n in res.names
             if not np.all(np.asarray(res[n]["served"]) == n_req)]
    _check(not short, f"fig12: cells short of n_req={n_req}: {short}")
    debt = res.scalars(keys=("ref_debt_end",))["ref_debt_end"]
    _check(not debt.any(), f"fig12: refresh debt left: {debt.tolist()}")
    bound = paper_fig12.compile_bound(res)
    _check(compiles <= bound, f"fig12: {compiles} compiles > {bound}")
    bad = paper_fig12.probe_mismatches(cells, res, horizon)
    _check(not bad, f"fig12: probe cell differs from simulate() in {bad}")


def _unsharded_buckets(cells, opts: SimOptions, n_dev: int) -> dict:
    """{cell name: metrics} of the buckets `run_sweep` plans for `n_dev`
    devices, each run as one unsharded program on device 0."""
    spec = sweep.SweepSpec(tuple(cells), options=opts)
    out = {}
    for bkt in sweep._plan(spec, opts, list(cells), n_dev):
        params, traces = sweep._build_arrays(bkt)
        m = engine.batched_simulate(params, traces,
                                    opts.with_chunk(bkt.chunk_b),
                                    spec.core, bkt.banks)
        m = {k: np.asarray(v) for k, v in m.items()}
        for row, j in enumerate(bkt.positions):
            out[bkt.group[j].name] = {k: v[row] for k, v in m.items()}
    return out


def sharded_phase(n_mixes: int = 6, n_req: int = 500) -> None:
    n_dev = len(jax.devices())
    _check(n_dev >= sweep.LOCAL_COND_MIN_DEVICES,
           f"the local cond path needs {sweep.LOCAL_COND_MIN_DEVICES} "
           f"devices, {n_dev} visible")
    cells, _ = paper_fig12.grid_cells(n_mixes, n_req)
    opts = SimOptions(horizon=default_horizon(cells))
    t0 = time.perf_counter()
    want = _unsharded_buckets(cells, opts, n_dev)
    _note(f"[fig12 unsharded on device 0] {len(want)} cells, "
          f"wall {time.perf_counter() - t0:.1f}s")
    for mode in ("auto", "global"):
        label = f"fig12 x{n_dev} cond_sharding={mode}"
        res, _ = run_sweep_timed(label, cells, opts, cond_sharding=mode)
        diff = [f"{n}:{k}" for n in res.names for k in want[n]
                if k != "chunks_run"
                and not np.array_equal(np.asarray(res[n][k]), want[n][k])]
        _check(not diff, f"{label}: differs from unsharded in {diff[:40]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep, on four chips")
    args = ap.parse_args(argv)
    try:
        device = device_phase(args.chips)
    except PhaseError as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    phases = ((sharded_phase,) if args.chips == 4
              else (golden_phase, fig12_phase))
    failed = []
    for phase in phases:
        try:
            phase()
        except PhaseError as e:
            # the later phases still run: one report names every failure
            print(f"chip_smoke FAILED {phase.__name__}: {e}",
                  file=sys.stderr, flush=True)
            failed.append(phase.__name__)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
