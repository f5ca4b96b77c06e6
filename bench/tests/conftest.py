"""Shared pieces of the benchmark's CPU tests: tiny traffic in place of
the cells' own, and a window that closes once its first job returns.

Run with ``JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest bench/tests``.
"""
from __future__ import annotations

import sys
import time
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: a size a test run holds: few requests, two mixes (so that buckets
#: hold more than one row)
TINY = {"n_req": 40, "horizon": 32768}


def tiny_traffic(t: dict) -> dict:
    return dict(t, mixes=t["mixes"][:2], **TINY)


@pytest.fixture(scope="session", autouse=True)
def own_compile_cache(tmp_path_factory):
    """Executables these tests compile go to a cache of their own."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()


def patch_tiny(monkeypatch) -> None:
    """Every traffic file the harness reads comes back at TINY size."""
    from bench.lib import registry
    real = registry.traffic
    monkeypatch.setattr(registry, "traffic",
                        lambda name, bench=registry.BENCH:
                        tiny_traffic(real(name, bench)))


def patch_one_job(monkeypatch) -> None:
    """The window closes as soon as its first job has returned, so that
    every bucket of that job counts and no second job starts."""
    from bench.lib import program, window
    offset = [0.0]
    clock = types.SimpleNamespace(
        perf_counter=lambda: time.perf_counter() + offset[0])
    real_run = program.run

    def run_then_close(g):
        res = real_run(g)
        offset[0] += 1e9
        return res
    monkeypatch.setattr(window, "time", clock)
    monkeypatch.setattr(window.program, "run", run_then_close)


@pytest.fixture
def tiny(monkeypatch):
    patch_tiny(monkeypatch)


@pytest.fixture
def one_job(monkeypatch):
    patch_one_job(monkeypatch)


def run_cell(name: str, seed: int = 2**31 + 7) -> dict:
    """A whole run of cell `name` after the look for a chip."""
    from bench.lib import harness, registry
    bm = registry.benchmark()
    return harness.execute(bm, registry.workload(bm, name), seed, 1e6,
                           False, time.perf_counter(), "unused")
