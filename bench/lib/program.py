"""The benchmark's one bridge to the system under test.

Everything the harness takes from the program passes through here:
``sweep.run_sweep`` and the public types it is called with, the result's
per-cell metrics and bucket records, and ``engine.compile_count``.  The
warm-up also reaches the sweep planner (``sweep._plan``,
``sweep._build_arrays``) and ``engine.batched_simulate``, as
``chip_smoke.py`` does, so that it loads exactly the executables the
window dispatches without running a bucket's real work; and the layout
of a bucket's rows over the devices comes from the planner's
``sweep._resolve_cond_sharding``.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.smla import config as smla_config
from repro.core.smla import engine, sweep

from bench.lib.gen import Job

#: program enum of each policy axis the traffic files name
_POLICY_ENUMS = {
    "scheduler": smla_config.SchedPolicy,
    "row": smla_config.RowPolicy,
    "refresh_gran": smla_config.RefreshGranularity,
    "write_drain": smla_config.WriteDrainPolicy,
    "self_refresh": smla_config.SelfRefreshPolicy,
    "ref_postpone": smla_config.RefreshPostpone,
    "layer_clock": smla_config.LayerClockPolicy,
    "ooo": smla_config.OooSelect,
}


def controller_policy(entry: dict) -> smla_config.ControllerPolicy:
    return smla_config.ControllerPolicy(**{
        axis: _POLICY_ENUMS[axis][value]
        for axis, value in entry.items() if axis != "name"})


def compile_count() -> int:
    return engine.compile_count()


@dataclasses.dataclass
class Grid:
    """One job as the program runs it, and how its result names map back
    to the job's ``(cell, policy)`` pairs."""
    spec: sweep.SweepSpec
    #: result name -> (generator cell, policy entry)
    cells: dict


def grid(job: Job, config: dict, traffic: dict, on_bucket=None) -> Grid:
    """The job as one ``run_sweep`` call with default settings."""
    stack = config["stack"]
    cells = []
    for c in job.cells:
        org = config["organisations"][c.org]
        sc = smla_config.StackConfig(
            io_model=smla_config.IOModel[org["io_model"]],
            rank_org=smla_config.RankOrg[org["rank_org"]], **stack)
        cells.append(sweep.SweepCell(c.name, sc, c.traces))
    pols = [controller_policy(p) for p in job.policies]
    spec = sweep.SweepSpec(
        tuple(cells),
        options=engine.SimOptions(horizon=int(traffic["horizon"])),
        core=engine.CoreParams(**config["core"]),
        policies=(None if traffic.get("policies") is None
                  else tuple(pols)),
        on_error="record", on_bucket=on_bucket)
    names = {}
    for c, p in job.expanded():
        name = c.name if spec.policies is None \
            else f"{c.name}|{controller_policy(p).tag}"
        names[name] = (c, p)
    return Grid(spec, names)


def run(g: Grid) -> sweep.SweepResult:
    return sweep.run_sweep(g.spec)


def loop_layout(g: Grid) -> tuple[int, int]:
    """``(devices, loops)`` of every bucket the job runs: its rows split
    in contiguous blocks over `devices` chips, and stepped by `loops`
    while-loops that each exit when their own rows are done, one over
    every chip (global exit) or one a chip (local exit)."""
    n_dev = len(jax.devices())
    _, local = sweep._resolve_cond_sharding(
        g.spec, g.spec.resolved_options(), n_dev)
    return n_dev, (local if local > 1 else 1)


def _plan(spec: sweep.SweepSpec) -> list:
    cells = list(spec.cells) if spec.policies is None \
        else sweep.policy_cells(spec.cells, spec.policies)
    return sweep._plan(spec, spec.resolved_options(), cells,
                       len(jax.devices()))


def warm(g: Grid) -> int:
    """Load (or compile) every executable the job's buckets dispatch,
    each on the bucket's own shapes and sharding, with one request per
    core so that each call ends after its first chunk.  Returns how many
    executables it ran."""
    seen = set()
    spec = g.spec
    opts = spec.resolved_options()
    for b in _plan(spec):
        key = (b.banks, b.chunk_b, len(b.positions), b.r_max, b.n_req_max,
               b.local_cond, b.sharding is not None)
        if key in seen:
            continue
        seen.add(key)
        params, traces = sweep._build_arrays(b)
        params["n_req"] = np.ones_like(params["n_req"])
        if b.sharding is not None:
            params = jax.device_put(params, b.sharding)
            traces = jax.device_put(traces, b.sharding)
        jax.block_until_ready(engine.batched_simulate(
            params, traces, opts.with_chunk(b.chunk_b), spec.core,
            b.banks, local_cond_devices=b.local_cond))
    return len(seen)
