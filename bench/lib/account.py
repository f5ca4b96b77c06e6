"""Work accounting over a window's finished buckets, shared by the metric
readers.

* needed cycles: each cell's own simulated fast cycles to completion,
  ``makespan_ns / unit_ns``, with the fast-clock period taken from the
  configuration file; a property of the model, the same on every commit
  that simulates it correctly;
* stepped lane-cycles: what the device ran for the bucket, every row (pad
  rows included) for the bucket's chunks run times its chunk width;
* stepped cycles: fast cycles the chip stepped, every bucket's while-loop
  chunks times its chunk width.
"""
from __future__ import annotations

from bench.reference.params import unit_ns


def _result(run, job: int):
    return run.window.jobs[job].result


def needed_cycles(run, bucket) -> float:
    u = unit_ns(run.config["stack"])
    res = _result(run, bucket.job)
    return sum(float(res[name]["makespan_ns"]) / u
               for name in bucket.meta["cells"])


def stepped_lane_cycles(bucket) -> float:
    m = bucket.meta
    return float(m["n_rows"] * m["chunks_run"] * m["chunk"])


def stepped_cycles(run) -> float:
    """Fast cycles the chip stepped over every bucket the window's jobs
    ran (the traced span holds all of them)."""
    return float(sum(b.meta["chunks_run"] * b.meta["chunk"]
                     for b in run.window.buckets))
