"""The program's own host spans in a traced window.

``sweep.run_sweep`` marks its pipeline with ``smla.*`` spans
(``jax.profiler.TraceAnnotation``, on the clock of the device's
execution events): ``smla.plan``; per bucket ``smla.prepare`` (on the
producer thread), ``smla.wait_prepare`` (the dispatching thread blocked
on the producer), ``smla.dispatch``, ``smla.harvest`` and
``smla.finalize``.  ``bench/lib/trace.py`` reduces the window's trace
to the harness's own ``bench.*`` spans; this module reads the same
trace again for the program's.

A span's thread is its line of the host plane.  The dispatching thread
is the one that holds the harness's ``bench.run_sweep`` spans, and the
window runs from the first ``bench.job`` span's start to the last one's
end, as ``trace.reduce`` takes it.
"""
from __future__ import annotations

import glob
import os
import time

from bench.lib import registry

PREFIX = "smla."
WAIT = "smla.wait_prepare"
#: harness spans that locate the window and the dispatching thread
JOB, RUN_SWEEP = "bench.job", "bench.run_sweep"


def window_trace(run, root: str = registry.ROOT) -> str | None:
    """The ``.xplane.pb`` the harness wrote for `run`'s traced window: the
    newest under ``.bench_trace/<cell>/``, provided it was written after
    the window started (an older one is another run's)."""
    paths = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    window_start = time.time() - (time.perf_counter() - run.window.t0)
    return path if os.path.getmtime(path) >= window_start else None


def host_spans(path: str) -> list[list]:
    """``[thread, name, start_ns, dur_ns]`` for every ``smla.*`` and
    ``bench.*`` span on the host plane of an ``.xplane.pb``; `thread` is
    the index of the span's line."""
    import jax
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((PREFIX, "bench.")):
                    out.append([k, e.name, e.start_ns, e.duration_ns])
    return out


def wait_s(spans: list[list]) -> float | None:
    """Seconds the dispatching thread spent in ``smla.wait_prepare``
    inside the window; None where the trace holds no ``smla.*`` span (a
    program without them)."""
    if not any(n.startswith(PREFIX) for _, n, _, _ in spans):
        return None
    jobs = [(s, s + d) for _, n, s, d in spans if n == JOB]
    dispatching = {t for t, n, _, _ in spans if n == RUN_SWEEP}
    if not jobs or len(dispatching) != 1:
        raise ValueError(f"the trace holds {len(jobs)} {JOB} spans and "
                         f"{RUN_SWEEP} spans on {len(dispatching)} threads")
    lo, hi = min(s for s, _ in jobs), max(e for _, e in jobs)
    waits = [(max(s, lo), min(s + d, hi)) for t, n, s, d in spans
             if n == WAIT and t in dispatching]
    return sum(max(e - s, 0.0) for s, e in waits) / 1e9
