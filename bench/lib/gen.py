"""The benchmark's one traffic generator: grid jobs from a traffic file,
a configuration file, the run's seed and the job's index.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

* ``n_req``    requests per core, the fixed work of every cell;
* ``horizon``  the fast-cycle horizon of every job (a static argument of
  the compiled program, so one value for the whole cell);
* ``mixes``    the mixes, each a list of workload names from the
  configuration's workload table, one per core;
* ``policies`` optional controller-policy axis: a list of
  ``{"name": ..., <axis>: <value name>}``, axes left out keep the paper's
  controller.

Job ``k`` is a pure function of ``(seed, k)``, and its work is the same
for every seed: each mix's request streams come from ``k`` alone, and
the seed relabels them (``relabel``), each cell with a permutation of
the banks and a mask on the row numbers of its own, drawn from
``numpy.random.default_rng([seed, k])``.  Arrivals, reads and writes,
and which requests share a bank and a row are kept, so a seed changes
the addresses a run simulates and not how long it simulates them.  Each
mix runs on every organisation of the configuration, so a job is
``mixes x organisations`` cells, each swept once per policy.

The trace synthesis is a copy of the program's ``traces.synthetic_trace``
and ``traces.core_traces`` as they stood when the benchmark was defined,
so that no change to the program moves the benchmark's inputs.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib import registry


@dataclasses.dataclass(frozen=True)
class Cell:
    """One grid cell before the policy axis: a mix on an organisation."""
    name: str
    org: str
    workloads: tuple[str, ...]
    traces: dict


@dataclasses.dataclass(frozen=True)
class Job:
    index: int
    cells: tuple[Cell, ...]
    #: the policy axis, ``({"name": ..., axis: value}, ...)``; one entry
    #: ``{"name": "default"}`` when the traffic sweeps no policies
    policies: tuple[dict, ...]

    def expanded(self):
        """``(cell, policy)`` for every simulation the job runs."""
        return [(c, p) for p in self.policies for c in self.cells]


def synthetic_trace(seed: int, spec: dict, n_req: int, n_ranks: int,
                    n_banks: int, n_rows: int = 4096) -> dict:
    """One core's request stream (copy of ``traces.synthetic_trace``)."""
    rng = np.random.default_rng(seed)
    mean_gap = 1000.0 / spec["mpki"]
    gaps = rng.exponential(mean_gap, size=n_req) + 1.0
    inst = np.cumsum(gaps).astype(np.float32)

    rank = rng.integers(0, n_ranks, size=n_req)
    if spec["bank_spread"] >= 1.0:
        bank = rng.integers(0, n_banks, size=n_req)
    else:
        p = np.exp(-np.arange(n_banks)
                   / max(spec["bank_spread"] * n_banks, .5))
        bank = rng.choice(n_banks, size=n_req, p=p / p.sum())
    row = np.empty(n_req, np.int64)
    cur = rng.integers(0, n_rows, size=(n_ranks, n_banks))
    stay = rng.random(n_req) < spec["row_hit"]
    fresh = rng.integers(0, n_rows, size=n_req)
    # request i's row is the latest non-stay draw for its bank, or the
    # bank's initial row when none precedes it
    key = rank * n_banks + bank
    for k in np.unique(key):
        m = key == k
        seen = np.where(~stay[m], np.arange(m.sum()), -1)
        last = np.maximum.accumulate(seen)
        start = cur[k // n_banks, k % n_banks]
        row[m] = np.where(last >= 0, fresh[m][np.maximum(last, 0)], start)
    wr = (rng.random(n_req) < spec["write_frac"]).astype(np.int32)
    return {"inst": inst, "rank": rank.astype(np.int32),
            "bank": bank.astype(np.int32), "row": row.astype(np.int32),
            "wr": wr}


def core_traces(seed: int, specs: list[dict], n_req: int, n_ranks: int,
                n_banks: int) -> dict:
    """Per-core traces stacked to ``(cores, n_req)`` arrays (copy of
    ``traces.core_traces``)."""
    ts = [synthetic_trace(seed + 97 * i, s, n_req, n_ranks, n_banks)
          for i, s in enumerate(specs)]
    return {k: np.stack([t[k] for t in ts]) for k in ts[0]}


def job_rng(seed: int, k: int) -> np.random.Generator:
    """The generator of job `k` of a run seeded `seed` (any integer)."""
    return np.random.default_rng([seed % 2**64, k])


def relabel(traces: dict, rng: np.random.Generator, n_banks: int,
            n_rows: int = 4096) -> dict:
    """A cell's streams with its banks permuted and its row numbers XORed
    with one mask, the same for every core: which requests meet in a bank
    and in a row is kept.  ``n_rows`` is a power of two."""
    perm = rng.permutation(n_banks).astype(np.int32)
    mask = np.int32(rng.integers(n_rows))
    return dict(traces, bank=perm[traces["bank"]], row=traces["row"] ^ mask)


def make_job(config: dict, traffic: dict, seed: int, k: int) -> Job:
    work = np.random.default_rng(k)     # the job's streams: seed-free
    rng = job_rng(seed, k)              # the seed's relabelling of them
    table = {w["name"]: w for w in config["assumed"]["workload_table"]}
    stack = config["stack"]
    n_ranks = registry.reference(config).n_ranks
    cells = []
    for m, mix in enumerate(traffic["mixes"]):
        trace_seed = int(work.integers(2**31))
        specs = [table[n] for n in mix]
        for org_name, org in config["organisations"].items():
            tr = relabel(core_traces(trace_seed, specs, traffic["n_req"],
                                     n_ranks(stack, org),
                                     stack["banks_per_rank"]),
                         rng, stack["banks_per_rank"])
            cells.append(Cell(f"m{m}/{org_name}", org_name, tuple(mix), tr))
    policies = tuple(traffic.get("policies") or ({"name": "default"},))
    return Job(k, tuple(cells), policies)
