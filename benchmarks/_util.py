"""Shared benchmark helpers: smoke-mode scaling and machine-readable output.

Smoke mode (`SMLA_SMOKE=1`, set by `benchmarks/run.py --smoke`) shrinks
horizons/trace lengths so CI can exercise every benchmark module in
minutes; numbers are then structural, not paper-comparable.

Every paper-figure benchmark appends its grid metrics to one JSON file
(default `BENCH_smla_sweep.json`, override with `BENCH_JSON`) keyed by
figure name, so the perf trajectory can be tracked across commits without
parsing CSV text.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import numpy as np

BENCH_JSON_ENV = "BENCH_JSON"
BENCH_JSON_DEFAULT = "BENCH_smla_sweep.json"


def smoke_mode() -> bool:
    return os.environ.get("SMLA_SMOKE", "") not in ("", "0")


def scaled(full: int, smoke: int) -> int:
    """`full` normally, `smoke` under SMLA_SMOKE=1."""
    return smoke if smoke_mode() else full


PROGRESS_ENV = "SMLA_PROGRESS"


def progress_printer(label: str, every: int = 1, force: bool = False):
    """An `on_bucket` callback for `SweepSpec` that prints per-bucket
    progress (`[label] bucket done/total wall cells/s`), so long sweeps
    launched through `benchmarks/run.py` are observable instead of
    silent for hours.  Enabled by `run.py --progress` (sets
    SMLA_PROGRESS=1) or `force=True`; returns None when disabled —
    `SweepSpec(on_bucket=None)` is the no-op default, so callers can
    pass the result through unconditionally."""
    if not force and os.environ.get(PROGRESS_ENV, "") in ("", "0"):
        return None

    def on_bucket(done: int, total: int, wall_s: float,
                  cells_per_s: float) -> None:
        if done % every and done != total:
            return
        print(f"[{label}] bucket {done}/{total}  {wall_s:7.1f}s  "
              f"{cells_per_s:8.1f} cells/s", flush=True)
    return on_bucket


def _jsonable(x: Any) -> Any:
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if hasattr(x, "tolist"):                      # numpy scalar / array
        return x.tolist()
    return x


def perf_block(wall_s: float, res, horizon: int) -> dict:
    """Machine-readable perf summary for one figure's sweep, so early-exit
    gains are comparable across commits.

    res: a `SweepResult`.  Reports wall time, cells/s, how much of
    the horizon the early exit saved (`chunks_run_total` vs
    `chunks_possible`, both respecting per-bucket adaptive widths —
    `cell_n_chunks_max` is per cell), and the estimate calibration: per
    bucket, the analytic `estimate_service_cycles` upper bound next to
    the measured makespan (`measured_over_est` drifting toward/past 1.0
    flags an engine change outrunning the estimate)."""
    from repro.core.smla import engine
    chunks = np.array([int(np.asarray(c["chunks_run"])) for c in res.cells])
    widths = np.array([int(w) for w in res.chunks] if res.chunks
                      else [engine.effective_chunk(horizon, None)]
                      * len(chunks))
    n_max = np.array([engine.n_chunks(horizon, int(w)) for w in widths])
    possible = int(n_max.sum())
    wall = max(wall_s, 1e-9)
    calibration = [
        {"chunk": m["chunk"], "n_cells": len(m["cells"]),
         "est_max": round(m["est_max"], 1),
         "measured_max": round(m["measured_max"], 1),
         "measured_over_est": round(
             m["measured_max"] / max(m["est_max"], 1e-9), 4)}
        for m in res.buckets]
    return {
        "wall_s": round(wall_s, 3),
        "cells_per_s": round(len(chunks) / wall, 3),
        "n_buckets": len(res.buckets),
        "horizon": horizon,
        "chunk_widths": sorted({int(w) for w in widths}),
        "cell_n_chunks_max": [int(x) for x in n_max],
        "chunks_run_total": int(chunks.sum()),
        "chunks_possible": possible,
        "early_exit_frac": round(1.0 - chunks.sum() / max(possible, 1), 4),
        "calibration": calibration,
    }


@dataclasses.dataclass
class FigureRecord:
    """One figure's benchmark emission as a typed record.

    Collapses the three result-plumbing paths every paper_fig module used
    to hand-roll — `SweepResult.scalars()` coercion, the `perf_block`
    summary, and the early-exit CI gate's field spelunking — onto one
    object that also *carries its provenance*: `backend` and
    `chunk_widths` ride along, so a BENCH JSON row is self-describing
    across execution backends (scan vs pallas) instead of relying on the
    section name.  `from_sweep` builds it from a live `SweepResult`;
    `from_json` rehydrates an emitted section so
    `benchmarks/assert_early_exit.py` gates through the same accessors
    the emitters used.
    """
    figure: str
    backend: str
    horizon: int
    n_cells: int
    compiles: int
    wall_s: float
    perf: dict
    chunk_widths: list
    cell_names: list | None = None
    scalars: dict | None = None
    #: figure-specific payload (rows, geomeans, workload mixes, ...)
    extra: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_sweep(cls, figure: str, res, wall_s: float, *, horizon: int,
                   compiles: int, extra: dict | None = None,
                   include_scalars: bool = True) -> "FigureRecord":
        """res: a `sweep.SweepResult` (its `backend` field is recorded)."""
        perf = perf_block(wall_s, res, horizon)
        scal = None
        if include_scalars:
            scal = {k: v for k, v in res.scalars().items() if k != "name"}
        return cls(figure=figure, backend=res.backend, horizon=horizon,
                   n_cells=len(res.names), compiles=compiles,
                   wall_s=round(wall_s, 3), perf=perf,
                   chunk_widths=perf["chunk_widths"],
                   cell_names=list(res.names), scalars=scal,
                   extra=dict(extra or {}))

    @classmethod
    def from_json(cls, figure: str, fig: dict | None) -> "FigureRecord":
        """Rehydrate an emitted section (raises ValueError when the
        section is missing its perf block — the gate's failure mode)."""
        if not fig or "perf" not in fig:
            raise ValueError(f"no {figure} perf section")
        return cls(figure=figure, backend=fig.get("backend", "scan"),
                   horizon=int(fig.get("horizon", 0)),
                   n_cells=int(fig.get("n_cells", 0)),
                   compiles=int(fig.get("compiles", 0)),
                   wall_s=float(fig.get("wall_s", 0.0)), perf=fig["perf"],
                   chunk_widths=fig.get("chunk_widths",
                                        fig["perf"].get("chunk_widths", [])),
                   cell_names=fig.get("cell_names"),
                   scalars=fig.get("scalars"))

    def payload(self) -> dict:
        out = dict(self.extra)
        out.update(backend=self.backend, horizon=self.horizon,
                   n_cells=self.n_cells, compiles=self.compiles,
                   wall_s=self.wall_s, perf=self.perf,
                   chunk_widths=self.chunk_widths)
        if self.cell_names is not None:
            out["cell_names"] = self.cell_names
        if self.scalars is not None:
            out["scalars"] = self.scalars
        return out

    def emit(self, path: str | None = None,
             section: str | None = None) -> str:
        return emit_json(section or self.figure, self.payload(), path)

    def early_exit_cells(self) -> list[tuple[str, int, int]]:
        """Non-baseline cells that exited before the horizon:
        (name, chunks_run, chunks_max) triples.  Raises ValueError when
        the record lacks the needed fields (scalars/cell_names)."""
        if self.scalars is None or self.cell_names is None:
            raise ValueError(f"{self.figure}: record carries no "
                             f"scalars/cell_names")
        chunks = self.scalars["chunks_run"]
        n_max = self.perf["cell_n_chunks_max"]
        return [(n, int(c), int(m)) for n, c, m
                in zip(self.cell_names, chunks, n_max)
                if "/baseline/" not in n and int(c) < int(m)]


def emit_json(section: str, payload: dict, path: str | None = None) -> str:
    """Merge `payload` under `section` into the benchmark JSON file."""
    path = path or os.environ.get(BENCH_JSON_ENV, BENCH_JSON_DEFAULT)
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    data[section] = _jsonable(dict(payload, smoke=smoke_mode()))
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path
