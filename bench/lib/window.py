"""The measured window: grid jobs back to back through ``run_sweep``, in a
closed loop with one caller.

A job is the unit the window dispatches: one ``run_sweep`` call, which
returns only once all its buckets are done.  When the window's time is
up no job is started; the job in flight runs to its end, and the window
closes when it returns.  Every bucket of every job the window started
counts, over the time from the window's start to that close, so no
unfinished work is counted and a stall that runs to the end still counts
as time.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax

from bench.lib import gen, program


@dataclasses.dataclass
class Bucket:
    """One finished bucket of a job, and how the job laid it out:
    its rows split in contiguous blocks over `devices` chips and stepped
    by `loops` while-loops, each until its own rows are done
    (``program.loop_layout``)."""
    job: int
    meta: dict               # the program's record of the bucket
    devices: int = 1
    loops: int = 1


@dataclasses.dataclass
class JobRun:
    job: gen.Job
    grid: program.Grid
    result: object           # sweep.SweepResult


@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    jobs: list[JobRun]
    buckets: list[Bucket]
    compiles: int            # executables built while the window ran
    t_done: float            # host clock when the last job returned

    @property
    def t_close(self) -> float:
        """When the window stopped starting jobs."""
        return self.t0 + self.seconds

    @property
    def elapsed(self) -> float:
        """The window's length: its start to the last job's return."""
        return self.t_done - self.t0


def _bucket_done(*_):
    with jax.profiler.TraceAnnotation("bench.bucket_done"):
        pass


def run(make_job: Callable[[int], gen.Job], config: dict, traffic: dict,
        seconds: float) -> Window:
    """Run jobs 0, 1, ... until the window's time is up; the job in flight
    then runs to its end."""
    jobs, buckets = [], []
    c0 = program.compile_count()
    t0 = time.perf_counter()
    t_close = t0 + seconds
    k = 0
    while time.perf_counter() < t_close:
        job = make_job(k)
        g = program.grid(job, config, traffic, on_bucket=_bucket_done)
        with jax.profiler.TraceAnnotation("bench.job", job=k):
            with jax.profiler.TraceAnnotation("bench.run_sweep"):
                res = program.run(g)
        jobs.append(JobRun(job, g, res))
        layout = program.loop_layout(g)
        buckets += [Bucket(k, meta, *layout) for meta in res.buckets]
        k += 1
    compiles = program.compile_count() - c0
    return Window(t0, seconds, jobs, buckets, compiles, time.perf_counter())
