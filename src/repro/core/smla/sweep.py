"""Batched SMLA sweep engine: the whole paper evaluation grid in one
(or a handful of) jitted programs, executed as a streaming pipeline.

The paper's headline figures sweep the cycle simulator over ~31 workloads
x 5 IO models x 2/4/8 layers — and, beyond the paper, over the controller
policy cross-product (`SweepSpec.policies`).  Run cell-by-cell that is
O(grid) compiles and serial scans; here every grid cell becomes one row
of a stacked batch and `engine.batched_simulate` vmaps a single compiled
scan over it.

Heterogeneous configs are padded to a common shape:
* rank axis   -> max rank count in the batch (`StackConfig.to_params`);
  padded ranks/groups are provably never referenced,
* request axis-> max trace length (`traces.pad_traces`); the engine stops
  consuming at the cell's traced `n_req`.
Cells are grouped by the remaining *static* quantities (core count,
banks-per-rank) — one compile per (group, chunk width), cached across
calls by `engine._compiled`.  Controller policies are **traced** integer
selectors (`core/smla/policies.py`), so the policy axis NEVER adds a
compile: the whole scheduler x row-policy x refresh x write-drain
cross-product reuses the shape group's executable.

Within a group, execution is *makespan-aware*: the chunked engine exits a
stacked batch only when its slowest cell finishes, so one slow baseline
cell would otherwise hold a batch of fast cascaded cells at the barrier.
`run_sweep` therefore orders cells by a cheap analytic service-time
estimate (`analytic.estimate_service_cycles`) and splits the group into
equal-size buckets of similar expected makespan — every bucket shares the
same padded static shapes (short buckets are padded with duplicates of
their own fastest cell).  With the default ``chunk="auto"`` each bucket
additionally derives its own scan-chunk width from its estimated
makespan (`CHUNK_LADDER`, clamped to `engine.DEFAULT_CHUNK`), so fast
buckets exit at finer granularity; chunk width never changes any metric
except the `chunks_run` diagnostic, and the few ladder widths are each
compiled once and cached across calls.

Execution is a **streaming pipeline** (``SweepSpec.streaming``, default
on): a producer thread probes the journal and pads/stacks the next
buckets' arrays while the device executes the current one, the dispatch
of bucket k+1 is issued before bucket k's device->host metric copies, and
results are accumulated *incrementally* — `SweepResult.cells` is a lazy
view over per-bucket storage (the journal's per-bucket ``.npz`` files
when journaling, in-memory stacked arrays otherwise), so host memory for
a journal-backed sweep is O(bucket), not O(grid).  ``streaming=False``
runs the identical plan strictly synchronously (prepare -> execute ->
harvest per bucket); both modes are bit-identical — pipelining only moves
wall-clock, never numerics.  The persistent JAX compilation cache
(`engine.compile_cache_dir`) adds on top, so the compiled shape-group
executables survive the *process* and a journal resume skips both
re-execution and recompilation.

Under ``jax.profiler`` the pipeline marks its work with host spans on
the clock of the device's execution events: ``smla.plan`` once, and per
bucket (each span carrying the bucket's ``bucket`` ordinal)
``smla.prepare`` (padding and stacking, on the producer thread),
``smla.wait_prepare`` (the dispatching thread blocked on the producer),
``smla.dispatch`` (``device_put`` and the call; trace, lower and compile
on a cache miss), ``smla.harvest`` (the device-to-host copy, which waits
for the execution) and ``smla.finalize`` (bookkeeping and ``on_bucket``).
README's "Profiling a sweep" reads a stalled bucket off them.

When more than one JAX device is visible, the stacked cell axis of each
bucket is sharded across devices (bucket sizes are rounded up to a
device multiple).  At ``LOCAL_COND_MIN_DEVICES`` or more devices the
sweep switches from the global-cond `NamedSharding` path to the
*reduce-tree cond* path (``SweepSpec.cond_sharding``): a fully-manual
``shard_map`` gives each device its own chunked while-loop whose early
exit reduces only over its local cell shard — no per-chunk cross-device
all-reduce, and a device whose shard finishes early goes idle instead of
spinning until the globally slowest cell exits.

Grids too large to run exhaustively can be **pruned** with successive
halving (``SweepSpec.prune`` / `PruneSpec`): a free seed round ranks
every cell by the analytic estimate, measurement rounds run the
survivors at geometrically growing short horizons promoting the top
``keep_frac`` by the target metric, and only the final survivors pay the
full horizon.  A pruned sweep is NOT bit-identical to an exhaustive one
— cut cells are never fully simulated (they are listed with their cut
round and score in `SweepResult.pruned`, and the work saved is accounted
in `SweepResult.prune_work`).

Metric results come back as lazy per-cell dicts plus stacked scalar
arrays (`SweepResult.scalars`) for machine-readable benchmark output,
and per-bucket calibration metadata (`SweepResult.buckets`: analytic
estimate vs measured makespan per cell) the figure benchmarks emit so
estimate drift is visible in the perf trajectory.

Long grids run crash-resiliently: transient device errors are retried
with bounded exponential backoff, a bucket that still fails can be
isolated into `SweepResult.failed_buckets` instead of aborting its
siblings (``on_error="record"``), and ``journal=`` checkpoints each
completed bucket to disk so a killed sweep resumes bit-identically
(see `SweepSpec`).  The fault axis (`fault_cells`) crosses cells with
`FaultConfig` scenarios exactly like the policy axis — traced data,
zero extra compiles.
"""
from __future__ import annotations

import collections
import collections.abc
import dataclasses
import functools
import hashlib
import json
import os
import queue
import threading
import time
from typing import Callable, Sequence

import jax
import numpy as np

from repro.core.smla import engine
from repro.core.smla.config import ControllerPolicy, StackConfig, paper_configs
from repro.core.smla.engine import CoreParams, SimOptions
from repro.core.smla.faults import FaultConfig
from repro.core.smla.traces import (WorkloadSpec, core_traces, pad_traces,
                                    stack_traces)

#: metrics that are scalars per cell (the rest are per-core arrays)
SCALAR_METRICS = ("bandwidth_gbps", "n_act", "n_row_conflicts", "bus_util",
                  "horizon_ns", "makespan_ns", "n_wr", "bus_cycles",
                  "wr_bus_cycles", "refresh_cycles", "ref_rank_blocked_cycles",
                  "ref_postponed", "ref_pulled_in", "ref_debt_max",
                  "ref_debt_end", "pd_cycles", "pd_frac", "sr_cycles",
                  "sr_frac", "n_sr_exit", "n_drain_bursts", "n_grants",
                  "n_slot_grants", "n_enqueued", "n_outstanding",
                  "chunks_run", "n_ecc_reread", "degrade_sel",
                  "n_row_hit", "wtr_stall_cycles", "n_ooo_retire")

#: substrings (matched against ``f"{type(e).__name__}: {e}"``) that mark a
#: device/runtime error as *transient* — worth a bounded exponential-backoff
#: retry before the bucket is declared failed.  The names follow the XLA /
#: gRPC status vocabulary surfaced in jaxlib exception text.
_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "OOM",
                      "UNAVAILABLE", "DEADLINE_EXCEEDED", "INTERNAL",
                      "DATA_LOSS", "ABORTED")


def _is_transient(exc: BaseException) -> bool:
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in _TRANSIENT_MARKERS)

#: scan-chunk widths ``chunk="auto"`` picks from, per bucket: the smallest
#: width >= est/AUTO_CHUNK_TARGET so a bucket runs ~AUTO_CHUNK_TARGET
#: chunks to its estimated makespan.  A short ladder (not arbitrary ints)
#: bounds the number of distinct compiled executables at len(CHUNK_LADDER)
#: per shape group, each cached across calls.  The target is calibrated
#: against the estimate being an intentionally conservative upper bound
#: (measured makespans run ~0.6-0.7x of it on the default grid): 32
#: estimated chunks ~= 20 real ones, still well above while-loop
#: dispatch overhead.
CHUNK_LADDER = (128, 256, 512, 1024)
AUTO_CHUNK_TARGET = 32

#: chunk sentinel: derive per-bucket widths from the analytic estimate
#: instead of one global constant (re-exported from `engine` — the same
#: value is valid in `SimOptions.chunk`).
AUTO = engine.AUTO

#: device count at which ``cond_sharding="auto"`` switches from the
#: global-cond NamedSharding path to the shard-local (reduce-tree) cond
#: path: below this the per-chunk all-reduce over a handful of devices is
#: cheap; at/beyond it the all-reduce tree and the globally-synchronised
#: exit start to dominate, so each device runs its own while-loop.
LOCAL_COND_MIN_DEVICES = 4

#: journal .npz files a `_CellStore` keeps decompressed at once: bounds
#: rehydration memory at O(bucket) while keeping bucket-sequential access
#: (scalars(), zip over cells) at one file read per bucket.
_NPZ_LRU_BUCKETS = 2


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One grid point: a stack configuration driving a set of core traces."""
    name: str
    stack: StackConfig
    traces: dict                       # {inst,rank,bank,row}: (C, n_req)


@dataclasses.dataclass(frozen=True)
class PruneSpec:
    """Successive-halving early pruning for grids too large to run
    exhaustively.

    Round 0 (``seed_from_estimate``, free): every cell is ranked by the
    analytic service-time estimate (`analytic.estimate_service_cycles`
    scaled to wall time by the cell's fast-clock period, so mixed layer
    counts compare fairly; a tested upper bound on the makespan — *lower
    is better* for throughput metrics) and only the top ``keep_frac``
    survive, without simulating anything.  Rounds 1..``rounds`` then run the survivors at
    geometrically growing short horizons (round r uses
    ``horizon * horizon_frac ** (rounds - r + 1)`` fast cycles), rank
    them by the *measured* ``metric`` and again promote the top
    ``keep_frac``.  The final survivors run at the full horizon and form
    the returned `SweepResult`; every cut cell is listed in
    `SweepResult.pruned` with its cut round and score.

    A pruned sweep is NOT bit-identical to an exhaustive one: cut cells
    are never fully simulated, and survivors' short-horizon rounds are
    extra (bit-identical-at-their-horizon) runs.  The *final* metrics of
    the surviving cells ARE bit-identical to the same cells in an
    exhaustive sweep — pruning decides *what* runs, never changes what a
    run computes.

    The analytic seed round ranks by estimated service time, which is a
    proxy for throughput-style metrics (shorter makespan = higher
    bandwidth over fixed work); disable ``seed_from_estimate`` when
    optimising a metric the estimate does not track (e.g. energy).
    """
    horizon_frac: float = 0.125
    keep_frac: float = 0.5
    rounds: int = 1
    metric: str = "bandwidth_gbps"
    maximize: bool = True
    seed_from_estimate: bool = True

    def __post_init__(self):
        if not 0.0 < self.horizon_frac < 1.0:
            raise ValueError(f"PruneSpec.horizon_frac must be in (0, 1), "
                             f"got {self.horizon_frac}")
        if not 0.0 < self.keep_frac < 1.0:
            raise ValueError(f"PruneSpec.keep_frac must be in (0, 1), "
                             f"got {self.keep_frac}")
        if self.rounds < 0:
            raise ValueError(f"PruneSpec.rounds must be >= 0, got "
                             f"{self.rounds}")
        if self.metric not in SCALAR_METRICS:
            raise ValueError(f"PruneSpec.metric {self.metric!r} is not a "
                             f"scalar metric (see SCALAR_METRICS)")


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A batch of grid cells sharing one execution surface and core model.

    The execution surface — horizon, early-exit chunk policy, backend,
    interpret mode, compile cache — is one `engine.SimOptions` value
    (`options`).  The legacy fields `horizon`/`chunk` remain as a
    one-release shim: ``SweepSpec(cells, horizon, chunk=...)`` builds the
    equivalent options; passing both `horizon` and `options` is an
    error.  With ``chunk=AUTO`` (the default) each makespan bucket
    derives its own width from the analytic estimate (`CHUNK_LADDER`);
    an int pins one width, None disables early exit (one full-horizon
    chunk).  `makespan_batching` orders compatible cells by their
    analytic service-time estimate and buckets them so fast cells are
    not barriered behind slow ones; `max_buckets` caps how many buckets
    one shape group may use.  `policies` is the controller-policy grid
    axis: when set, every cell is swept once per policy (cell names gain
    a ``|tag`` suffix); the selectors are traced, so the axis multiplies
    the grid without multiplying compiles.

    Streaming execution:

    * `streaming` (default True) — run the bucket pipeline: a producer
      thread prepares (journal-probes, pads, stacks) upcoming buckets
      while the device executes the current one, and bucket k's
      device->host metric copies overlap bucket k+1's execution.
      Bit-identical to `streaming=False` (strict prepare/execute/harvest
      per bucket) — the pipeline moves wall-clock, not numerics.
    * `prefetch` — how many prepared buckets the producer may hold ahead
      of the device (bounds host memory at O(prefetch * bucket)).
    * `on_bucket` — progress callback ``on_bucket(done, total, wall_s,
      cells_per_s)`` invoked after every finalized bucket (including
      journal-loaded and failed ones), so long grids are observable.
    * `prune` — successive-halving early pruning (`PruneSpec`); the
      returned result covers only the promoted survivors.
    * `cond_sharding` — multi-device early-exit strategy: ``"global"``
      shards cells via NamedSharding under one program (the while-loop
      cond all-reduces across devices every chunk), ``"local"`` wraps
      the pipeline in a fully-manual shard_map so each device's loop
      exits on its own shard (scan backend only), ``"auto"`` (default)
      picks "local" at >= `LOCAL_COND_MIN_DEVICES` devices.

    Resilience (for long overnight grids):

    * `max_retries` / `retry_base_s` — a bucket whose execution dies with
      a *transient* device error (`_TRANSIENT_MARKERS`: OOM, UNAVAILABLE,
      DEADLINE_EXCEEDED, ...) is retried up to `max_retries` times with
      exponential backoff (`retry_base_s * 2**attempt` seconds).
      Non-transient errors are never retried.
    * `on_error="record"` — a bucket that still fails is *isolated*: its
      cells land in `SweepResult.failed_buckets` (tags + error text) and
      the sweep continues with the remaining buckets instead of aborting
      hours of siblings.  The default `"raise"` keeps the historical
      fail-fast behaviour.
    * `journal` — a directory path enabling checkpoint/resume: each
      completed bucket's metrics are written atomically to
      ``{journal}/{sha1(key)}.npz`` keyed by the bucket's full execution
      signature (cells, chunk, horizon, backend, banks, validate, jax
      version, device platform).  A re-run with the same spec and
      journal loads finished buckets from disk (bit-identical — npz
      round-trips the exact arrays) and only executes the missing ones,
      so a killed sweep resumes where it died.  Journal-backed results
      stay on disk: `SweepResult.cells` rehydrates lazily from the
      per-bucket files."""
    cells: tuple[SweepCell, ...]
    horizon: int | None = None
    core: CoreParams = CoreParams()
    chunk: int | None | str = AUTO
    makespan_batching: bool = True
    max_buckets: int = 8
    policies: tuple[ControllerPolicy, ...] | None = None
    options: SimOptions | None = None
    journal: str | None = None
    max_retries: int = 2
    retry_base_s: float = 0.05
    on_error: str = "raise"
    streaming: bool = True
    prefetch: int = 2
    prune: PruneSpec | None = None
    on_bucket: Callable[[int, int, float, float], None] | None = None
    cond_sharding: str = "auto"

    def __post_init__(self):
        if not self.cells:
            raise ValueError("SweepSpec.cells is empty — a sweep needs at "
                             "least one grid cell")
        if self.max_buckets < 1:
            raise ValueError(f"SweepSpec.max_buckets must be >= 1, got "
                             f"{self.max_buckets}")
        if self.on_error not in ("raise", "record"):
            raise ValueError(f"SweepSpec.on_error must be 'raise' or "
                             f"'record', got {self.on_error!r}")
        if self.max_retries < 0:
            raise ValueError(f"SweepSpec.max_retries must be >= 0, got "
                             f"{self.max_retries}")
        if self.retry_base_s < 0:
            raise ValueError(f"SweepSpec.retry_base_s must be >= 0, got "
                             f"{self.retry_base_s}")
        if self.prefetch < 1:
            raise ValueError(f"SweepSpec.prefetch must be >= 1, got "
                             f"{self.prefetch}")
        if self.cond_sharding not in ("auto", "global", "local"):
            raise ValueError(f"SweepSpec.cond_sharding must be 'auto', "
                             f"'global' or 'local', got "
                             f"{self.cond_sharding!r}")
        if self.prune is not None and not isinstance(self.prune, PruneSpec):
            raise ValueError(f"SweepSpec.prune must be a PruneSpec, got "
                             f"{type(self.prune).__name__}")
        if self.on_bucket is not None and not callable(self.on_bucket):
            raise ValueError("SweepSpec.on_bucket must be callable")

    def resolved_options(self) -> SimOptions:
        """The one SimOptions this sweep runs under."""
        if self.options is not None:
            if self.horizon is not None:
                raise ValueError("pass horizon inside SimOptions, not "
                                 "alongside it")
            return self.options
        if self.horizon is None:
            raise ValueError("SweepSpec needs options=SimOptions(...) "
                             "(or the legacy positional horizon)")
        return SimOptions(horizon=self.horizon, chunk=self.chunk)


class _BucketData:
    """One finalized bucket's stacked metric arrays: held in memory for
    journal-less sweeps, re-read lazily from the journal's per-bucket
    ``.npz`` for journal-backed ones — the file is the unit of truth and
    host memory stays O(bucket), not O(grid)."""
    __slots__ = ("arrays", "path")

    def __init__(self, arrays: dict | None = None, path: str | None = None):
        self.arrays = arrays
        self.path = path

    def load(self, store: "_CellStore") -> dict:
        if self.arrays is not None:
            return self.arrays
        return store._load_npz(self.path)


class _CellStore(collections.abc.Sequence):
    """Lazy per-cell metric dicts over per-bucket storage.

    ``store[i]`` materializes (and memoizes) cell i's dict, so explicit
    access returns a stable, mutable dict exactly like the former eager
    list of dicts.  `peek` reads a single metric through the bucket
    arrays *without* memoizing the cell — `SweepResult.scalars` uses it,
    so a full-grid scalar table over a journal-backed sweep never holds
    more than `_NPZ_LRU_BUCKETS` buckets in memory."""

    def __init__(self):
        self._refs: list[tuple[_BucketData, int]] = []
        self._cache: dict[int, dict] = {}
        self._npz: collections.OrderedDict[str, dict] = \
            collections.OrderedDict()

    def _append(self, ref: tuple[_BucketData, int]) -> None:
        self._refs.append(ref)

    def _load_npz(self, path: str) -> dict:
        got = self._npz.get(path)
        if got is None:
            with np.load(path) as z:
                got = {k: z[k] for k in z.files}
            self._npz[path] = got
            while len(self._npz) > _NPZ_LRU_BUCKETS:
                self._npz.popitem(last=False)
        else:
            self._npz.move_to_end(path)
        return got

    def __len__(self) -> int:
        return len(self._refs)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        if i < 0:
            i += len(self._refs)
        got = self._cache.get(i)
        if got is None:
            data, row = self._refs[i]
            arrays = data.load(self)
            got = {k: np.asarray(v)[row] for k, v in arrays.items()}
            self._cache[i] = got
        return got

    def peek(self, i: int, key: str):
        """Cell i's metric `key` without materializing the cell dict."""
        got = self._cache.get(i)
        if got is not None:
            return got[key]
        data, row = self._refs[i]
        return np.asarray(data.load(self)[key])[row]


@dataclasses.dataclass
class SweepResult:
    names: list[str]
    #: per-cell metric dicts — a lazy `_CellStore` view over per-bucket
    #: storage (indexing/iterating materializes plain numpy dicts; the
    #: journal's .npz files back it when journaling is on)
    cells: Sequence
    #: per-cell effective scan-chunk width actually used
    chunks: list[int] = dataclasses.field(default_factory=list)
    #: per-bucket calibration metadata: {"cells", "chunk", "est_cycles",
    #: "measured_cycles", "est_max", "measured_max", "n_rows",
    #: "chunks_run"} — analytic estimate vs measured makespan, emitted
    #: into the figure perf blocks
    buckets: list[dict] = dataclasses.field(default_factory=list)
    #: execution backend that produced these metrics ("scan" | "pallas"),
    #: carried so benchmark records are self-describing
    backend: str = "scan"
    #: buckets that failed after retries under ``on_error="record"``:
    #: {"cells": [tags], "error": "Type: text", "attempts": n}.  Failed
    #: cells are excluded from `names`/`cells`, so `scalars()` stays
    #: well-formed over the survivors.
    failed_buckets: list[dict] = dataclasses.field(default_factory=list)
    #: cells cut by successive halving (`SweepSpec.prune`): {"name",
    #: "round", "score", "metric"} — round 0 is the free analytic seed
    #: cut, rounds >= 1 are measured short-horizon cuts.
    pruned: list[dict] = dataclasses.field(default_factory=list)
    #: work accounting for a pruned sweep: executed cell-cycles (device
    #: lanes x fast cycles actually issued, short rounds included) vs the
    #: full-horizon bound `n_cells * horizon`, and the saved fraction.
    prune_work: dict = dataclasses.field(default_factory=dict)

    def __getitem__(self, name: str) -> dict:
        return self.cells[self.names.index(name)]

    def scalars(self, keys: Sequence[str] = SCALAR_METRICS) -> dict:
        """Stacked (n_cells,) arrays of the scalar metrics + cell names.

        Only scalar-per-cell metrics can be stacked this way; asking for a
        per-core metric (e.g. ``ipc``) raises a ValueError instead of the
        former cryptic ``float()``-on-array crash."""
        out = {"name": np.array(self.names)}
        peek = getattr(self.cells, "peek", None)
        for k in keys:
            vals = []
            for i, name in enumerate(self.names):
                v = peek(i, k) if peek is not None else self.cells[i][k]
                a = np.asarray(v).ravel()
                if a.size != 1:
                    raise ValueError(
                        f"scalars(): metric {k!r} is per-core (shape "
                        f"{np.asarray(v).shape} in cell {name!r}); use "
                        f"result[name][{k!r}] for per-core arrays")
                vals.append(float(a[0]))
            out[k] = np.array(vals)
        return out


def make_cell(name: str, stack: StackConfig, specs: Sequence[WorkloadSpec],
              n_req: int, seed: int = 0) -> SweepCell:
    """Synthesise this cell's traces exactly as `analytic.run_config` does."""
    traces = core_traces(seed, list(specs), n_req, stack.n_ranks,
                         stack.banks_per_rank)
    return SweepCell(name, stack, traces)


def policy_cells(cells: Sequence[SweepCell],
                 policies: Sequence[ControllerPolicy]) -> list[SweepCell]:
    """Cross `cells` with controller policies: each cell is replicated
    once per policy (same traces — the workload does not change, only the
    controller does) and renamed ``{name}|{policy.tag}``."""
    out = []
    for pol in policies:
        for c in cells:
            out.append(SweepCell(f"{c.name}|{pol.tag}",
                                 dataclasses.replace(c.stack, policy=pol),
                                 c.traces))
    return out


def fault_cells(cells: Sequence[SweepCell],
                faults: Sequence[FaultConfig]) -> list[SweepCell]:
    """Cross `cells` with fault scenarios: each cell is replicated once
    per FaultConfig (same traces — the workload does not change, only the
    hardware's health does) and renamed ``{name}%{fault.tag}``.  Like the
    policy axis, the fault axis is lowered to traced data in
    `StackConfig.to_params`, so it never adds a compile."""
    out = []
    for fc in faults:
        for c in cells:
            out.append(SweepCell(f"{c.name}%{fc.tag}",
                                 dataclasses.replace(c.stack, faults=fc),
                                 c.traces))
    return out


def paper_grid(workloads: Sequence[tuple[str, Sequence[WorkloadSpec], int]],
               layers: Sequence[int] = (4,), n_req: int = 500,
               config_names: Sequence[str] | None = None) -> list[SweepCell]:
    """The paper's evaluation grid: workloads x 5 IO models x layer counts.

    workloads: (name, specs, seed) triples.  Cell names are
    'L{layers}/{config}/{workload}'.
    """
    cells = []
    for L in layers:
        for cname, sc in paper_configs(L).items():
            if config_names is not None and cname not in config_names:
                continue
            for wname, specs, seed in workloads:
                cells.append(make_cell(f"L{L}/{cname}/{wname}", sc,
                                       specs, n_req, seed))
    return cells


def _auto_chunk(est_max: float) -> int:
    """The ladder width for a bucket whose slowest member is estimated at
    `est_max` fast cycles: smallest width giving ~AUTO_CHUNK_TARGET
    chunks, clamped to engine.DEFAULT_CHUNK."""
    target = est_max / AUTO_CHUNK_TARGET
    for w in CHUNK_LADDER:
        if w >= target:
            return min(w, engine.DEFAULT_CHUNK)
    return min(CHUNK_LADDER[-1], engine.DEFAULT_CHUNK)


def _plan_buckets(spec: SweepSpec, opts: SimOptions, group: list[SweepCell],
                  n_dev: int) -> tuple[list[list[int]], list[float]]:
    """Split one static-shape group into equal-size makespan buckets.

    Returns (buckets, est): each bucket is a list of positions into
    `group`, padded to a common size (a multiple of `n_dev`) by repeating
    the bucket's own fastest member — a duplicate of a resident cell
    never extends the bucket's early-exit point.  One bucket size per
    group keeps every bucket on the same padded shapes.  `est` is the
    per-position analytic service-time estimate (always computed: it
    also drives the auto chunk width and the calibration metadata)."""
    from repro.core.smla import analytic        # lazy: analytic imports us
    n = len(group)
    est = [float(e) for e in analytic.estimates_for_cells(group, spec.core)]
    single = (not spec.makespan_batching or opts.chunk is None or n <= 1)
    k = 1 if single else min(spec.max_buckets, n)
    size = -(-n // k)
    size = -(-size // n_dev) * n_dev            # device multiple
    k = -(-n // size)
    if k > 1:
        order = sorted(range(n), key=lambda j: (est[j], j))
    else:
        order = list(range(n))
    buckets = []
    for b in range(k):
        sl = order[b * size:(b + 1) * size]
        sl = sl + [sl[0]] * (size - len(sl))
        buckets.append(sl)
    return buckets, est


def _bucket_chunk(opts: SimOptions,
                  bucket_est: Sequence[float]) -> int | None:
    """The scan-chunk width one bucket runs with."""
    if opts.chunk == AUTO:
        return _auto_chunk(max(bucket_est))
    return opts.chunk


def _bucket_key(ordinal: int, names: Sequence[str], chunk_b, opts: SimOptions,
                banks: int) -> str:
    """Stable journal key for one bucket: sha1 of its full execution
    signature.  Two runs of the same spec enumerate buckets identically,
    so the key round-trips; any change to the grid, chunking, horizon,
    backend, validation mode, jax version or device platform changes the
    key and invalidates the journal entry rather than silently reusing
    stale metrics (npz arrays are exact, but a jax/device upgrade may
    legitimately move float metrics — a journal written under one build
    must not masquerade as the other's output)."""
    payload = json.dumps({"ordinal": ordinal, "cells": list(names),
                          "chunk": chunk_b, "horizon": opts.horizon,
                          "backend": opts.backend, "banks": banks,
                          "validate": opts.validate,
                          "jax": jax.__version__,
                          "platform": jax.default_backend()}, sort_keys=True)
    return hashlib.sha1(payload.encode()).hexdigest()


def _journal_load(journal: str, key: str) -> dict | None:
    path = os.path.join(journal, key + ".npz")
    if not os.path.exists(path):
        return None
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def _journal_save(journal: str, key: str, out: dict) -> None:
    """Atomic per-bucket checkpoint: write to a unique tmp file with an
    explicit ``.npz`` suffix (so np.savez never renames it underneath
    us), then ``os.replace`` into place — a sweep killed mid-write never
    leaves a truncated entry behind, and concurrent writers of the same
    key (two resumed sweeps racing on one journal) each land a complete
    file, last one wins."""
    os.makedirs(journal, exist_ok=True)
    path = os.path.join(journal, key + ".npz")
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}.npz"
    try:
        np.savez(tmp, **{k: np.asarray(v) for k, v in out.items()})
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _run_with_retry(fn, max_retries: int, base_s: float) -> tuple[dict, int]:
    """Call `fn` with bounded exponential-backoff retries on *transient*
    errors only.  Returns (result, attempts); re-raises the last error
    once retries are exhausted or immediately for non-transient ones."""
    attempt = 0
    while True:
        try:
            return fn(), attempt + 1
        except Exception as exc:
            attempt += 1
            if attempt > max_retries or not _is_transient(exc):
                raise
            time.sleep(base_s * (2 ** (attempt - 1)))


def _cell_sharding(n_dev: int):
    """NamedSharding that splits a stacked batch's leading cell axis
    across all visible devices."""
    mesh = engine.cell_mesh(n_dev)
    return jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("cells"))


def _resolve_cond_sharding(spec: SweepSpec, opts: SimOptions,
                           n_dev: int) -> tuple[object | None, int]:
    """-> (cell sharding | None, local_cond device count).  local_cond >
    1 selects the engine's reduce-tree cond path (per-device while-loop
    exit); 0 keeps the global-cond path under one sharded program."""
    if n_dev <= 1:
        return None, 0
    mode = spec.cond_sharding
    if mode == "auto":
        mode = ("local" if n_dev >= LOCAL_COND_MIN_DEVICES
                and opts.backend == "scan" else "global")
    if mode == "local" and opts.backend != "scan":
        raise ValueError(
            f"cond_sharding='local' needs the scan backend (each device "
            f"runs its own while_loop); backend={opts.backend!r} only "
            f"supports 'global'")
    return _cell_sharding(n_dev), (n_dev if mode == "local" else 0)


@dataclasses.dataclass
class _Bucket:
    """One planned unit of execution: a padded slice of a shape group."""
    ordinal: int                 # global dispatch order (journal keying)
    banks: int
    r_max: int
    n_req_max: int
    group: list                  # the shape group's SweepCells (shared)
    idxs: list                   # original cell index per group position
    positions: list              # group positions resident here (padded)
    est: list                    # per-group-position analytic estimate
    chunk_b: object              # int | None
    jkey: str | None
    sharding: object             # NamedSharding | None
    local_cond: int              # >1: reduce-tree cond device count


def _plan(spec: SweepSpec, opts: SimOptions, cells: list[SweepCell],
          n_dev: int) -> list[_Bucket]:
    """The full bucket schedule, computed up front: shape groups ->
    makespan buckets -> chunk widths -> journal keys.  Enumeration order
    is deterministic, so journal keys round-trip across runs."""
    order: dict[tuple, list[int]] = {}
    for i, cell in enumerate(cells):
        key = (cell.traces["inst"].shape[0], cell.stack.banks_per_rank)
        order.setdefault(key, []).append(i)
    sharding, local_cond = _resolve_cond_sharding(spec, opts, n_dev)
    plan: list[_Bucket] = []
    b_ord = 0
    for (_, banks), idxs in order.items():
        group = [cells[i] for i in idxs]
        r_max = max(c.stack.n_ranks for c in group)
        n_req_max = max(c.traces["inst"].shape[1] for c in group)
        buckets, est = _plan_buckets(spec, opts, group, n_dev)
        for bucket in buckets:
            chunk_b = _bucket_chunk(opts, [est[j] for j in bucket])
            jkey = (_bucket_key(b_ord, [group[j].name for j in bucket],
                                chunk_b, opts, banks)
                    if spec.journal is not None else None)
            plan.append(_Bucket(ordinal=b_ord, banks=banks, r_max=r_max,
                                n_req_max=n_req_max, group=group, idxs=idxs,
                                positions=list(bucket), est=est,
                                chunk_b=chunk_b, jkey=jkey,
                                sharding=sharding, local_cond=local_cond))
            b_ord += 1
    return plan


def _build_arrays(bkt: _Bucket) -> tuple[dict, dict]:
    """Pad and stack one bucket's params/traces (pure numpy, host-side —
    this is the work the producer thread overlaps with device compute)."""
    batch = [bkt.group[j] for j in bkt.positions]
    plist = []
    for c in batch:
        p = c.stack.to_params(bkt.r_max)
        p["n_req"] = np.int32(c.traces["inst"].shape[1])
        plist.append(p)
    params = {k: np.stack([p[k] for p in plist]) for k in plist[0]}
    traces = stack_traces([pad_traces(c.traces, bkt.n_req_max)
                           for c in batch])
    return params, traces


def _span(name: str, **args):
    """A host span ``smla.<name>`` in the profiler's trace, on the clock
    of the device's execution events; a no-op when no profiler runs.
    Spans of one bucket carry its ``bucket`` ordinal."""
    return jax.profiler.TraceAnnotation("smla." + name, **args)


def _prepare(bkt: _Bucket, journal: str | None):
    """One pipeline item: (bucket, journal-loaded metrics | None, params,
    traces) — either the bucket is already journaled (no arrays needed)
    or its padded arrays are built here."""
    with _span("prepare", bucket=bkt.ordinal):
        cached = (_journal_load(journal, bkt.jkey)
                  if bkt.jkey is not None else None)
        if cached is not None:
            return (bkt, cached, None, None)
        params, traces = _build_arrays(bkt)
        return (bkt, None, params, traces)


def _inline_items(plan: list[_Bucket], spec: SweepSpec):
    """Synchronous prepare: each bucket is padded on the main thread
    right before dispatch (the `streaming=False` path)."""
    for bkt in plan:
        yield _prepare(bkt, spec.journal)


class _Producer:
    """Background prepare thread for the streaming pipeline: journal
    probes and array padding for upcoming buckets run while the device
    executes the current one.  Errors cross back to the consumer; `stop`
    unblocks and joins the thread (used on normal exit and on kill)."""

    def __init__(self, plan: list[_Bucket], spec: SweepSpec):
        self._ordinals = [bkt.ordinal for bkt in plan]
        self._q: queue.Queue = queue.Queue(maxsize=max(1, spec.prefetch))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._work, args=(list(plan), spec),
            name="smla-sweep-producer", daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _work(self, plan: list[_Bucket], spec: SweepSpec) -> None:
        try:
            for bkt in plan:
                if self._stop.is_set():
                    return
                if not self._put(("item", _prepare(bkt, spec.journal))):
                    return
            self._put(("done", None))
        except BaseException as exc:      # surface in the consumer thread
            self._put(("error", exc))

    def _get(self):
        while True:
            try:
                return self._q.get(timeout=0.5)
            except queue.Empty:
                if not self._thread.is_alive():
                    raise RuntimeError(
                        "sweep producer thread died without reporting")

    def __iter__(self):
        # items arrive in plan order: the k-th wait is for the k-th
        # bucket, the last for the end of the plan
        for k in range(len(self._ordinals) + 1):
            args = ({"bucket": self._ordinals[k]}
                    if k < len(self._ordinals) else {})
            with _span("wait_prepare", **args):
                tag, payload = self._get()
            if tag == "done":
                return
            if tag == "error":
                raise payload
            yield payload

    def stop(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)


def _run_grid(spec: SweepSpec, opts: SimOptions,
              cells: list[SweepCell]) -> SweepResult:
    """Execute an (already policy-expanded) cell list as the streaming
    bucket pipeline.  See `run_sweep` for semantics."""
    n_dev = max(len(jax.devices()), 1)
    with _span("plan"):
        plan = _plan(spec, opts, cells, n_dev)
    n = len(cells)
    refs: list = [None] * n
    chunks: list[int] = [0] * n
    store = _CellStore()
    bucket_meta: list[dict] = []
    failed_buckets: list[dict] = []
    failed_pos: set[int] = set()
    t0 = time.time()
    progress = [0, 0]                       # buckets done, unique cells done
    #: FIFO of finalization work: ("dev", bkt, device handles, attempts,
    #: params, traces) awaiting device->host copy, or ("cached", bkt,
    #: arrays) journal loads queued behind in-flight device work so
    #: bucket metadata keeps plan order.
    pending: collections.deque = collections.deque()

    def _mark_done(n_new_cells: int) -> None:
        progress[0] += 1
        progress[1] += n_new_cells
        if spec.on_bucket is not None:
            wall = max(time.time() - t0, 1e-9)
            spec.on_bucket(progress[0], len(plan), wall, progress[1] / wall)

    def _dispatch(bkt: _Bucket, params: dict, traces: dict) -> dict:
        with _span("dispatch", bucket=bkt.ordinal):
            if bkt.sharding is not None:
                params = jax.device_put(params, bkt.sharding)
                traces = jax.device_put(traces, bkt.sharding)
            # resolved at call time through the module so tests can inject
            # failures by monkeypatching engine.batched_simulate
            return engine.batched_simulate(
                params, traces, opts.with_chunk(bkt.chunk_b), spec.core,
                bkt.banks, local_cond_devices=bkt.local_cond)

    def _record_failure(bkt: _Bucket, exc: Exception) -> None:
        tags = list(dict.fromkeys(bkt.group[j].name for j in bkt.positions))
        failed_buckets.append({
            "cells": tags,
            "error": f"{type(exc).__name__}: {exc}",
            "attempts": (spec.max_retries + 1
                         if _is_transient(exc) else 1)})
        failed_pos.update(bkt.idxs[j] for j in bkt.positions)
        _mark_done(0)

    def _finalize(bkt: _Bucket, out_np: dict, save: bool) -> None:
        with _span("finalize", bucket=bkt.ordinal):
            if save and bkt.jkey is not None:
                _journal_save(spec.journal, bkt.jkey, out_np)
            if bkt.jkey is not None:
                data = _BucketData(path=os.path.join(spec.journal,
                                                     bkt.jkey + ".npz"))
            else:
                data = _BucketData(arrays=out_np)
            eff = engine.effective_chunk(opts.horizon, bkt.chunk_b)
            # duplicate pad entries land on the same original index with
            # bit-identical values — assigning them again is harmless.
            meta = {"cells": [], "chunk": eff, "est_cycles": [],
                    "measured_cycles": [], "n_rows": len(bkt.positions),
                    "chunks_run": int(np.max(
                        np.asarray(out_np["chunks_run"])))}
            mk = np.asarray(out_np["makespan_ns"])
            seen: set[int] = set()
            for j_pos, j in enumerate(bkt.positions):
                refs[bkt.idxs[j]] = (data, j_pos)
                chunks[bkt.idxs[j]] = eff
                if j in seen:
                    continue                     # pad duplicate
                seen.add(j)
                meta["cells"].append(bkt.group[j].name)
                meta["est_cycles"].append(float(bkt.est[j]))
                meta["measured_cycles"].append(
                    float(mk[j_pos]) / float(bkt.group[j].stack.unit_ns))
            meta["est_max"] = max(meta["est_cycles"])
            meta["measured_max"] = max(meta["measured_cycles"])
            bucket_meta.append(meta)
            _mark_done(len(seen))

    def _harvest_head() -> None:
        entry = pending.popleft()
        if entry[0] == "cached":
            _finalize(entry[1], entry[2], save=False)
            return
        _, bkt, out, attempts, params, traces = entry
        try:
            with _span("harvest", bucket=bkt.ordinal):
                out_np = {k: np.asarray(v) for k, v in out.items()}
        except Exception as exc:
            # an asynchronously-dispatched device error surfaces at copy
            # time: re-run the bucket synchronously under whatever retry
            # budget the dispatch left unused
            left = spec.max_retries - (attempts - 1)
            if not _is_transient(exc) or left <= 0:
                if spec.on_error != "record":
                    raise
                _record_failure(bkt, exc)
                return

            def redo():
                o = _dispatch(bkt, params, traces)
                return {k: np.asarray(v) for k, v in o.items()}
            try:
                out_np, _ = _run_with_retry(redo, left - 1,
                                            spec.retry_base_s)
            except Exception as exc2:
                if spec.on_error != "record":
                    raise
                _record_failure(bkt, exc2)
                return
        _finalize(bkt, out_np, save=True)

    def _n_dev_pending() -> int:
        return sum(1 for e in pending if e[0] == "dev")

    # streaming keeps one bucket executing while the previous one's
    # metrics copy back (depth 2); sync mode harvests before dispatching
    # the next bucket (depth 1) — the historical strict loop.
    max_inflight = 2 if spec.streaming else 1
    src = _Producer(plan, spec) if spec.streaming \
        else _inline_items(plan, spec)
    try:
        for bkt, cached, params, traces in src:
            if cached is not None:
                if pending:
                    pending.append(("cached", bkt, cached))
                else:
                    _finalize(bkt, cached, save=False)
                continue
            while _n_dev_pending() >= max_inflight:
                _harvest_head()
            try:
                out, attempts = _run_with_retry(
                    functools.partial(_dispatch, bkt, params, traces),
                    spec.max_retries, spec.retry_base_s)
            except Exception as exc:
                if spec.on_error != "record":
                    raise
                _record_failure(bkt, exc)
                continue
            pending.append(("dev", bkt, out, attempts, params, traces))
        while pending:
            _harvest_head()
    except BaseException:
        if isinstance(src, _Producer):
            src.stop()
        # drain already-dispatched buckets so a killed sweep's journal
        # keeps every finished bucket (best effort — the original error
        # is what propagates)
        try:
            while pending:
                _harvest_head()
        except BaseException:
            pass
        raise
    finally:
        if isinstance(src, _Producer):
            src.stop()

    keep = [i for i in range(n) if i not in failed_pos]
    for i in keep:
        store._append(refs[i])
    return SweepResult(names=[cells[i].name for i in keep],
                       cells=store, chunks=[chunks[i] for i in keep],
                       buckets=bucket_meta, backend=opts.backend,
                       failed_buckets=failed_buckets)


def _measured_work(res: SweepResult) -> float:
    """Device work one sweep actually issued, in cell-cycles: padded
    lanes x chunks executed x chunk width, summed over buckets."""
    return float(sum(b["n_rows"] * b["chunks_run"] * b["chunk"]
                     for b in res.buckets))


def _run_pruned(spec: SweepSpec, opts: SimOptions) -> SweepResult:
    """Successive halving (see `PruneSpec`): free analytic seed cut,
    short-horizon measurement rounds, full horizon only for the final
    survivors."""
    from repro.core.smla import analytic        # lazy: analytic imports us
    pr = spec.prune
    cells = (list(spec.cells) if spec.policies is None
             else policy_cells(spec.cells, spec.policies))
    n = len(cells)
    survivors = list(range(n))
    pruned: list[dict] = []
    executed = 0.0

    def _keep_n(n_alive: int) -> int:
        return max(1, int(np.ceil(pr.keep_frac * n_alive)))

    if pr.seed_from_estimate and len(survivors) > 1:
        # rank by estimated service *time*, not raw fast cycles: cells
        # with different layer counts run different fast-clock periods,
        # so cross-config cycle counts are incomparable while ns are
        est = analytic.estimates_for_cells(cells, spec.core) \
            * np.array([c.stack.unit_ns for c in cells])
        ranked = sorted(survivors, key=lambda i: (est[i], i))
        kn = _keep_n(len(survivors))
        for i in ranked[kn:]:
            pruned.append({"name": cells[i].name, "round": 0,
                           "score": float(est[i]),
                           "metric": "estimate_service_ns"})
        survivors = sorted(ranked[:kn])

    def _subrun(idx_list: list[int], sub_opts: SimOptions) -> SweepResult:
        sub = dataclasses.replace(
            spec, cells=tuple(cells[i] for i in idx_list), horizon=None,
            options=sub_opts, policies=None, prune=None)
        return _run_grid(sub, sub_opts, [cells[i] for i in idx_list])

    for r in range(1, pr.rounds + 1):
        if len(survivors) <= 1:
            break
        frac = pr.horizon_frac ** (pr.rounds - r + 1)
        h_r = max(1, int(round(opts.horizon * frac)))
        res_r = _subrun(survivors, dataclasses.replace(opts, horizon=h_r))
        executed += _measured_work(res_r)
        rows = res_r.scalars(keys=(pr.metric,))[pr.metric]
        # res_r preserves input order minus failed buckets: align by a
        # single forward walk (names may repeat; order disambiguates)
        scores: dict[int, float] = {}
        p = 0
        for i in survivors:
            if p < len(res_r.names) and res_r.names[p] == cells[i].name:
                scores[i] = float(rows[p])
                p += 1
        alive = [i for i in survivors if i in scores]
        for i in survivors:
            if i not in scores:   # failed bucket under on_error="record"
                pruned.append({"name": cells[i].name, "round": r,
                               "score": float("nan"), "metric": pr.metric})
        sgn = -1.0 if pr.maximize else 1.0
        ranked = sorted(alive, key=lambda i: (sgn * scores[i], i))
        kn = _keep_n(len(alive))
        for i in ranked[kn:]:
            pruned.append({"name": cells[i].name, "round": r,
                           "score": scores[i], "metric": pr.metric})
        survivors = sorted(ranked[:kn])

    res = _subrun(survivors, opts)
    executed += _measured_work(res)
    full = float(n) * float(opts.horizon)
    res.pruned = pruned
    res.prune_work = {
        "executed_cell_cycles": executed,
        "full_horizon_cell_cycles": full,
        "saved_frac": 1.0 - executed / full if full > 0 else 0.0,
        "n_cells": n, "n_survivors": len(survivors),
        "rounds_run": pr.rounds, "keep_frac": pr.keep_frac,
        "horizon_frac": pr.horizon_frac}
    return res


def run_sweep(spec: SweepSpec) -> SweepResult:
    """Execute every cell (times every policy, when `spec.policies` is
    set), batching compatible cells into vmapped jit calls — bucketed by
    estimated makespan so the chunked engine's early exit is not
    barriered on a slow outlier, sharded over the cell axis when
    multiple devices are visible, and executed as a streaming pipeline
    (producer-thread prepare, overlapped dispatch/harvest) unless
    ``spec.streaming=False``.  Metrics are bit-identical to per-cell
    `engine.simulate` with the same effective chunk width; chunk width
    and streaming only move wall-clock and the `chunks_run` diagnostic.

    Resilience: transient device errors are retried with exponential
    backoff; under ``spec.on_error="record"`` a bucket that still fails
    is recorded in `SweepResult.failed_buckets` and its siblings keep
    running; with ``spec.journal`` set, each completed bucket checkpoints
    to disk and a re-run resumes bit-identically from the journal.

    With ``spec.prune`` set, successive halving runs instead (`PruneSpec`
    — the result covers the promoted survivors only and is NOT
    bit-identical to an exhaustive sweep)."""
    opts = spec.resolved_options()
    if spec.prune is not None:
        return _run_pruned(spec, opts)
    cells = (list(spec.cells) if spec.policies is None
             else policy_cells(spec.cells, spec.policies))
    return _run_grid(spec, opts, cells)
