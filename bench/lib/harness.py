"""One run of one cell: set-up, the measured window, the correctness
checks and the result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds job 0 from the seed and loads (on a checkout's first run,
compiles) every executable its buckets dispatch; every job of a cell
has the same shapes, so no later job needs another.  The window then
runs jobs back to back (``window.py``), building each when it reaches
it.  With ``--trace 1`` the window runs under the profiler, whose trace
stays in ``.bench_trace/<cell>/`` until the cell's next traced run, and
the per-layer metrics are reported; otherwise the end-to-end ones.
After the window the device's peak memory is read and the sampled cells
are compared with the reference (``check.py``).  The last lines on
stderr, and the result's ``checks`` key, give each number compared
beside its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import jax

from bench.lib import check, gen, program, registry, trace, window

class NoChip(Exception):
    """The run found no TPU, or another number of chips than the cell's."""


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/<name>.py``)."""
    config: dict
    window: window.Window
    setup_s: float
    warmup_s: float
    trace: trace.Summary | None


def require_chips(n: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is a {devs[0].platform} "
                     f"device")
    if len(devs) != n:
        raise NoChip(f"the cell runs on {n} chip(s); JAX sees {len(devs)}")


def device_record(n: int) -> dict:
    devs = jax.devices()[:n]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


def execute(bm: dict, wl: dict, seed: int, seconds: float, traced: bool,
            t_start: float, trace_dir: str) -> dict:
    """Everything of a run after the look for a chip; returns the result
    object."""
    cfg = registry.config(bm, wl["config"])
    traffic = registry.traffic(wl["traffic"])
    jobs: dict[int, gen.Job] = {}

    def make_job(k: int) -> gen.Job:
        if k not in jobs:
            jobs[k] = gen.make_job(cfg, traffic, seed, k)
        return jobs[k]

    grid = program.grid(make_job(0), cfg, traffic)
    t = time.perf_counter()
    executables = program.warm(grid)
    warmup_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace.profile_options())
    try:
        win = window.run(make_job, cfg, traffic, seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    summary = None
    if traced:
        summary = trace.reduce(trace.extract(trace.xplane_path(trace_dir),
                                             wl["chips"]))

    device = device_record(wl["chips"])
    if summary is not None:
        device["busy_s"] = sum(summary.busy_s) / len(summary.busy_s)
        device["window_s"] = summary.window_s
    t = time.perf_counter()
    checks, sampled = check.run_checks(win, cfg, traffic, seed)
    reference_s = time.perf_counter() - t
    run = Run(cfg, win, setup_s, warmup_s, summary)
    metrics = {}
    for m in registry.metrics(bm, traced):
        v = registry.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {
        "correct": all(c.ok for c in checks),
        "attempted": sum(len(jr.grid.cells) for jr in win.jobs),
        "failed": int(next(c.value for c in checks
                           if c.name == "failed_cells")),
        "metrics": metrics,
        "device": device,
        "window": {"executables_warmed": executables,
                   "jobs": len(win.jobs), "buckets": len(win.buckets),
                   "window_s": win.elapsed,
                   "after_close_s": win.t_done - win.t_close,
                   "reference_s": reference_s,
                   "sampled": sampled["cells"]},
    }
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                              "bound": c.kind, "ok": c.ok} for c in checks}
    return out


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bm = registry.benchmark()
    wl = registry.workload(bm, args.workload)
    try:
        require_chips(wl["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    trace_dir = os.path.join(registry.ROOT, ".bench_trace", wl["name"])
    out = execute(bm, wl, args.seed, args.seconds, bool(args.trace),
                  t_start, trace_dir)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['bound']} "
              f"{c['limit']!r}) {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
