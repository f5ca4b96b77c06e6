"""Benchmark driver: one module per paper table/figure + framework benches.

Each benchmark runs in a subprocess (several force their own host-device
counts, which must be set before jax initialises).  Output: CSV blocks,
plus machine-readable `BENCH_smla_sweep.json` from the paper figures.

`--smoke` (or SMLA_SMOKE=1) shrinks horizons/trace lengths/problem sizes so
CI can exercise every module in a few minutes; the driver exits non-zero if
any module fails either way.
"""
import argparse
import os
import subprocess
import sys
import time

BENCHES = [
    "benchmarks.paper_table1",        # Table 1 / Fig 10 energy model
    "benchmarks.paper_table2",        # Table 2 configurations
    "benchmarks.paper_fig11",         # single-core perf/energy, 31 workloads
    "benchmarks.paper_fig12",         # multi-core weighted speedup + energy
    "benchmarks.paper_fig13",         # layer-count sensitivity 2/4/8
    "benchmarks.paper_fig14",         # MPKI vs energy
    "benchmarks.paper_fig_policy",    # controller-policy sensitivity
    "benchmarks.paper_fig_ooo",       # OoO window depth x OooSelect
    "benchmarks.paper_fig_refresh",   # refresh-management / deep power states
    "benchmarks.paper_fig_fault",     # fault injection / graceful degradation
    "benchmarks.paper_fig_serve",     # serve<->sim loop: captured LM traffic
    "benchmarks.paper_fig_scale",     # sweep-engine scaling: streaming/prune
    "benchmarks.collective_schedules",# cascaded vs dedicated cross-pod sync
    "benchmarks.smla_pipe_bench",     # SMLA pipeline kernel
    "benchmarks.serve_policies",      # MLR vs SLR serving placement
    "benchmarks.roofline_table",      # §Roofline table from the dry-run
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny horizons/sizes for CI (sets SMLA_SMOKE=1)")
    ap.add_argument("--only", nargs="*", metavar="MOD",
                    help="run only these modules (suffix match)")
    ap.add_argument("--progress", action="store_true",
                    help="per-bucket sweep progress lines (sets "
                         "SMLA_PROGRESS=1; see _util.progress_printer)")
    args = ap.parse_args(argv)

    env = dict(os.environ)
    if args.smoke:
        env["SMLA_SMOKE"] = "1"
    if args.progress:
        env["SMLA_PROGRESS"] = "1"
    # make `-m benchmarks.X` (and repro, via src/) work from any cwd
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    benches = [m for m in BENCHES
               if not args.only or any(m.endswith(o) for o in args.only)]
    if args.only and not benches:
        print(f"no benchmark matches {args.only}; available: "
              + " ".join(m.rsplit('.', 1)[1] for m in BENCHES),
              file=sys.stderr)
        return 2

    failed: list[tuple[str, int]] = []
    for mod in benches:
        print(f"\n===== {mod} =====", flush=True)
        t0 = time.time()
        # This parent imports no JAX, so each child is the one process
        # holding the accelerator while it runs.  --progress streams the
        # child (per-bucket lines land live); otherwise output is
        # captured and replayed on completion
        r = subprocess.run([sys.executable, "-m", mod],
                           capture_output=not args.progress,
                           text=True, env=env)
        dt = time.time() - t0
        sys.stdout.write(r.stdout or "")
        if r.returncode != 0:
            failed.append((mod, r.returncode))
            sys.stdout.write(f"[FAILED rc={r.returncode}]\n")
            sys.stdout.write((r.stderr or "")[-2000:] + "\n")
        print(f"[{mod}: {dt:.1f}s]", flush=True)
    # per-figure failure summary: every module always runs (a broken
    # figure never shadows its siblings), and the tail of the log names
    # exactly which ones need attention
    print(f"\n{len(benches) - len(failed)}/{len(benches)} benchmarks ok")
    if failed:
        print("failed benchmarks:", file=sys.stderr)
        for mod, rc in failed:
            print(f"  {mod} (rc={rc})", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
