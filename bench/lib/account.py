"""Work accounting over a window's finished buckets, shared by the metric
readers.

* needed cycles: each cell's own simulated fast cycles to completion,
  ``makespan_ns / unit_ns``, with the fast-clock period worked out from
  the configuration file by its reference (``registry.reference``); a
  property of the model, the same on every commit that simulates it
  correctly;
* loop chunks: a bucket's rows are split in contiguous blocks over its
  devices (``window.Bucket``) and stepped by its while-loops, one over
  every device (global exit) or one a device (local exit).  A loop runs
  until its slowest row exits, so its chunk count is the largest
  ``chunks_run`` of its rows: each row's own count freezes where that
  row exits;
* stepped lane-cycles: what the devices ran for the bucket, every row
  (pad rows included) for the chunks of the loop that holds it, times the
  chunk width;
* stepped cycles: fast cycles the devices stepped, each device the
  chunks of the loop it runs times the chunk width, summed over devices
  and over every bucket.

On one device a bucket is one loop, and its chunk count is the bucket's
``chunks_run``.
"""
from __future__ import annotations

from bench.lib import registry


def _result(run, job: int):
    return run.window.jobs[job].result


def needed_cycles(run, bucket) -> float:
    u = registry.reference(run.config).unit_ns(run.config["stack"])
    res = _result(run, bucket.job)
    return sum(float(res[name]["makespan_ns"]) / u
               for name in bucket.meta["cells"])


def row_cells(bucket) -> list[str]:
    """The cell each of the bucket's rows holds, in row order.  The
    bucket's record lists each cell once, in row order; the planner pads
    a bucket at its end with copies of its first row
    (``sweep._plan_buckets``)."""
    cells = bucket.meta["cells"]
    return cells + [cells[0]] * (bucket.meta["n_rows"] - len(cells))


def loop_chunks(run, bucket) -> list[int]:
    """Chunks each of the bucket's while-loops ran, in device order: the
    rows split into ``bucket.loops`` contiguous blocks, each its slowest
    row's ``chunks_run``."""
    res = _result(run, bucket.job)
    rows = [int(res[name]["chunks_run"]) for name in row_cells(bucket)]
    k = len(rows) // bucket.loops
    return [max(rows[i * k:(i + 1) * k]) for i in range(bucket.loops)]


def stepped_lane_cycles(run, bucket) -> float:
    rows = bucket.meta["n_rows"] // bucket.loops
    return float(sum(rows * c for c in loop_chunks(run, bucket))
                 * bucket.meta["chunk"])


def stepped_cycles(run) -> float:
    """Fast cycles the devices stepped over every bucket the window's
    jobs ran (the traced span holds all of them), summed over devices."""
    return float(sum(b.devices // b.loops * sum(loop_chunks(run, b))
                     * b.meta["chunk"] for b in run.window.buckets))


def synchronous_cycles(run) -> float:
    """What `stepped_cycles` would be if every device stepped each
    bucket until the bucket's slowest row exits."""
    return float(sum(b.devices * max(loop_chunks(run, b)) * b.meta["chunk"]
                     for b in run.window.buckets))
