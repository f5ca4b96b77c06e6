"""What decides ``correct``: the program's statistics for the cells the
window finished, against the benchmark's own reference.

* ``failed_cells``: cells of any job the window ran that came back in a
  failed bucket, with a core short of its ``n_req``, or with refresh debt
  left (the configuration's guarantees).  Limit 0.
* ``int_mismatch``: integer and boolean statistics of a sample of the
  cells the window finished that differ from the reference.
  Limit 0.
* ``float_rel_gap``: the widest relative gap of a float statistic of the
  same sample from the reference.  Limit ``FLOAT_LIMIT``.
* ``cells_checked``: the sample's size; at least 1.

The sample is drawn from the run's seed and always holds the cell with
the longest makespan.  The configuration's reference
(``registry.reference``; by default ``bench/reference/controller.py``)
simulates each sampled cell alone, request by request, in plain Python
on the host, in worker processes of its own that never touch JAX.
``chunks_run`` (an execution detail that depends on the bucket's chunk
width) and ``degrade_sel`` (an echo of an input) are not compared.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib import gen, registry

#: cells compared with the reference in every run
SAMPLE = 8
#: limit of ``float_rel_gap``: set between the program's readings on
#: the chip and the control's (PERF.md, section 2 gives both)
FLOAT_LIMIT = 1e-6


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    #: "max": the value may not exceed the limit; "min": nor fall below
    kind: str = "max"

    @property
    def ok(self) -> bool:
        if self.kind == "min":
            return self.value >= self.limit
        return self.value <= self.limit


def failed_cells(window, traffic: dict) -> int:
    n_req = traffic["n_req"]
    bad = 0
    for jr in window.jobs:
        res = jr.result
        bad += sum(len(fb["cells"]) for fb in res.failed_buckets)
        for name in res.names:
            m = res[name]
            if not (np.all(np.asarray(m["served"]) == n_req)
                    and int(m["ref_debt_end"]) == 0):
                bad += 1
    return bad


def sample(window, seed: int) -> list[tuple[int, str]]:
    """``(job index, result name)`` of the cells to compare."""
    done = [(b.job, name) for b in window.buckets
            for name in b.meta["cells"]]
    if not done:
        return []
    results = {jr.job.index: jr.result for jr in window.jobs}
    longest = max(done, key=lambda jn: float(
        results[jn[0]][jn[1]]["makespan_ns"]))
    rest = [jn for jn in done if jn != longest]
    rng = gen.job_rng(seed, 2**32)   # a stream no job uses
    picked = rng.choice(len(rest), min(SAMPLE - 1, len(rest)),
                        replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(picked)]


def reference_cell(config: dict, traffic: dict, cell: gen.Cell,
                   policy: dict) -> tuple:
    """The reference's arguments for one cell: ``(channel, traces, core,
    horizon)``."""
    ch = registry.reference(config).channel(
        config["stack"], config["organisations"][cell.org], policy,
        traffic["n_req"])
    return ch, cell.traces, config["core"], traffic["horizon"]


def compare(got: dict, want: dict) -> tuple[int, float]:
    """(integer values that differ, widest relative float gap)."""
    n_int, gap = 0, 0.0
    for k, w in want.items():
        g = np.asarray(got[k])
        w = np.asarray(w)
        if np.issubdtype(w.dtype, np.floating):
            g = g.astype(np.float64)
            w = w.astype(np.float64)
            if not np.all(np.isfinite(g) == np.isfinite(w)):
                gap = float("inf")
                continue
            d = np.abs(g - w) / np.maximum(np.abs(w), 1e-30)
            gap = max(gap, float(np.max(np.where(np.isfinite(w), d, 0.0))))
        else:
            n_int += int(np.sum(g != w))
    return n_int, gap


def run_checks(window, config: dict, traffic: dict, seed: int
               ) -> tuple[list[Check], dict]:
    """The checks of one run, and a note of the cells compared."""
    picked = sample(window, seed)
    by_job = {jr.job.index: jr for jr in window.jobs}
    pairs = [by_job[j].grid.cells[name] for j, name in picked]
    wants = registry.reference(config).simulate_many(
        [reference_cell(config, traffic, c, p) for c, p in pairs])
    gaps = [compare(by_job[j].result[name], want)
            for (j, name), want in zip(picked, wants)]
    checks = [
        Check("failed_cells", failed_cells(window, traffic), 0),
        Check("int_mismatch", sum(g[0] for g in gaps), 0),
        Check("float_rel_gap", max((g[1] for g in gaps), default=0.0),
              FLOAT_LIMIT),
        Check("cells_checked", len(picked), 1, "min"),
    ]
    return checks, {"cells": [f"job{j}:{n}" for j, n in picked]}
