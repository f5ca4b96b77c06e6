"""Streaming sweep pipeline (PR 9): bit-identity of the async
producer/dispatch pipeline vs the strict synchronous path, lazy
journal-backed results (`_CellStore`), the persistent compilation cache,
successive-halving pruning (`PruneSpec`), the jax-build journal keying,
the `on_bucket` progress hook, and multi-device cell sharding at 4 and 8
forced host devices including the reduce-tree cond path."""
import os

import jax
import numpy as np
import pytest
from conftest import run_subprocess_jax

from repro.core.smla import analytic, engine, sweep
from repro.core.smla.config import ControllerPolicy
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WorkloadSpec

HORIZON = 3_000
N_REQ = 30
STREAM = WorkloadSpec("stream.t", 50.0, 0.85, write_frac=1 / 3)


def _cells(n_layers=(2, 4)):
    """10 cells (5 IO models x len(n_layers)), one shape group."""
    return tuple(sweep.paper_grid([("s", [STREAM, STREAM], 3)],
                                  layers=n_layers, n_req=N_REQ))


def _spec(cells, **kw):
    return sweep.SweepSpec(tuple(cells),
                           options=SimOptions(horizon=HORIZON), **kw)


def _assert_same_cells(got: sweep.SweepResult, want: sweep.SweepResult,
                       include_chunks_run=True):
    assert got.names == want.names
    for name, g, w in zip(got.names, got.cells, want.cells):
        assert set(g) == set(w), name
        for k in g:
            if k == "chunks_run" and not include_chunks_run:
                continue
            assert np.array_equal(np.asarray(g[k]), np.asarray(w[k])), \
                f"{name}:{k}"


# ----------------------------------------------------------------------------
# streaming vs synchronous bit-identity
# ----------------------------------------------------------------------------

def test_streaming_bit_identical_to_sync():
    """The pipeline (producer thread, overlapped dispatch/harvest) must
    reproduce the strict synchronous runner bit-for-bit — including the
    chunks_run diagnostic (same plan, same widths) and the per-bucket
    calibration metadata."""
    cells = _cells()
    res_s = sweep.run_sweep(_spec(cells, streaming=True))
    res_y = sweep.run_sweep(_spec(cells, streaming=False))
    _assert_same_cells(res_s, res_y)
    assert res_s.chunks == res_y.chunks
    assert len(res_s.buckets) == len(res_y.buckets)
    for bs, by in zip(res_s.buckets, res_y.buckets):
        assert bs["cells"] == by["cells"]
        assert bs["est_cycles"] == by["est_cycles"]
        assert bs["measured_cycles"] == by["measured_cycles"]
        assert bs["chunks_run"] == by["chunks_run"]
        assert bs["n_rows"] == by["n_rows"]


def test_streaming_compile_count_unchanged():
    """Pipelining must not add compiles: one shape group still costs at
    most one compile per distinct bucket chunk width."""
    cells = _cells()
    spec = _spec(cells, streaming=True)
    sweep.run_sweep(spec)                        # warm (may compile)
    engine.reset_compile_count()
    res = sweep.run_sweep(spec)
    assert engine.compile_count() == 0
    engine.reset_compile_count()
    sweep.run_sweep(_spec(cells, streaming=False))
    assert engine.compile_count() == 0
    assert len(set(res.chunks)) >= 1


def test_streaming_journal_matches_memory_and_resume(tmp_path, monkeypatch):
    """Journal-backed streaming results (lazily rehydrated from the
    per-bucket .npz files) match the in-memory path bit-for-bit, and a
    resume off the journal re-executes nothing."""
    cells = _cells()
    jd = str(tmp_path / "journal")
    ref = sweep.run_sweep(_spec(cells))
    res1 = sweep.run_sweep(_spec(cells, journal=jd))
    _assert_same_cells(res1, ref)

    def forbidden(*a, **kw):
        raise AssertionError("engine must not run on a full journal")
    monkeypatch.setattr(engine, "batched_simulate", forbidden)
    res2 = sweep.run_sweep(_spec(cells, journal=jd))
    _assert_same_cells(res2, res1)


def test_on_bucket_progress_callback(tmp_path):
    """on_bucket(done, total, wall_s, cells_per_s) fires once per
    finalized bucket — executed AND journal-loaded — with a monotone
    done counter and positive throughput."""
    cells = _cells()
    calls = []

    def hook(done, total, wall_s, cells_per_s):
        calls.append((done, total, wall_s, cells_per_s))

    jd = str(tmp_path / "journal")
    res = sweep.run_sweep(_spec(cells, journal=jd, on_bucket=hook))
    assert len(calls) == len(res.buckets)
    total = calls[0][1]
    assert [c[0] for c in calls] == list(range(1, total + 1))
    assert all(c[1] == total for c in calls)
    assert all(c[2] >= 0 and c[3] > 0 for c in calls)
    calls.clear()
    sweep.run_sweep(_spec(cells, journal=jd, on_bucket=hook))
    assert len(calls) == len(res.buckets)        # cached buckets report too


# ----------------------------------------------------------------------------
# lazy _CellStore
# ----------------------------------------------------------------------------

def test_cellstore_lazy_journal_backed(tmp_path):
    """Journal-backed cells rehydrate from the per-bucket files: a full
    scalars() pass never holds more than the npz LRU's worth of buckets,
    and explicit indexing memoizes a stable, mutable dict."""
    cells = _cells()
    jd = str(tmp_path / "journal")
    res = sweep.run_sweep(_spec(cells, journal=jd))
    store = res.cells
    assert isinstance(store, sweep._CellStore)
    tab = res.scalars()                          # peek path: no memoizing
    assert not store._cache
    assert len(store._npz) <= sweep._NPZ_LRU_BUCKETS
    assert tab["bandwidth_gbps"].shape == (len(cells),)
    # explicit access materializes (and caches) a plain mutable dict
    d = store[0]
    assert store[0] is d
    d["wrapped"] = np.array([1.5])
    assert store.peek(0, "wrapped") == 1.5       # cache-first read-through
    # negative indexing and slicing behave like the former list
    assert store[-1] is store[len(cells) - 1]
    assert [id(x) for x in store[:2]] == [id(store[0]), id(store[1])]


def test_cellstore_survives_bucket_file_round_trip(tmp_path):
    """Values read back through the journal equal the in-memory run
    exactly (npz round-trips the arrays bit-for-bit)."""
    cells = _cells()[:4]
    jd = str(tmp_path / "journal")
    ref = sweep.run_sweep(_spec(cells))
    res = sweep.run_sweep(_spec(cells, journal=jd))
    for name in res.names:
        for k, v in ref[name].items():
            assert np.array_equal(np.asarray(res[name][k]),
                                  np.asarray(v)), (name, k)


# ----------------------------------------------------------------------------
# journal keying across jax builds
# ----------------------------------------------------------------------------

def test_bucket_key_includes_jax_build(monkeypatch):
    opts = SimOptions(horizon=HORIZON)
    base = sweep._bucket_key(0, ["a", "b"], 256, opts, 8)
    assert base == sweep._bucket_key(0, ["a", "b"], 256, opts, 8)
    monkeypatch.setattr(jax, "__version__", "999.99.9")
    assert sweep._bucket_key(0, ["a", "b"], 256, opts, 8) != base


# ----------------------------------------------------------------------------
# persistent compilation cache
# ----------------------------------------------------------------------------

def test_compile_cache_dir_validation():
    with pytest.raises(ValueError, match="compile_cache_dir"):
        SimOptions(horizon=HORIZON, compile_cache_dir=123)


def test_persistent_compile_cache_across_processes(tmp_path):
    """SimOptions.compile_cache_dir survives the process: the first
    subprocess populates the cache directory, the second runs the same
    sweep against it without adding entries (every executable was found)
    and reproduces the metrics bit-for-bit."""
    cache = str(tmp_path / "xla-cache")
    out_a = str(tmp_path / "a.npz")
    out_b = str(tmp_path / "b.npz")
    code = f"""
import numpy as np
from repro.core.smla import sweep
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WorkloadSpec

STREAM = WorkloadSpec("stream.t", 50.0, 0.85, write_frac=1/3)
cells = tuple(sweep.paper_grid([("s", [STREAM, STREAM], 3)], layers=(2, 4),
                               n_req=30))
res = sweep.run_sweep(sweep.SweepSpec(
    cells, options=SimOptions(horizon=3000,
                              compile_cache_dir={cache!r})))
tab = res.scalars()
np.savez({{}}, **{{k: v for k, v in tab.items() if k != "name"}})
print("CACHE-RUN-OK")
"""
    run_a = code.replace("np.savez({}", f"np.savez({out_a!r}")
    run_b = code.replace("np.savez({}", f"np.savez({out_b!r}")
    no_env = {"JAX_COMPILATION_CACHE_DIR": None}
    out = run_subprocess_jax(run_a, n_devices=1, env_overrides=no_env)
    assert "CACHE-RUN-OK" in out
    entries = set(os.listdir(cache))
    assert entries, "first run must populate the compilation cache"
    out = run_subprocess_jax(run_b, n_devices=1, env_overrides=no_env)
    assert "CACHE-RUN-OK" in out
    assert set(os.listdir(cache)) == entries     # all hits, no new compiles
    with np.load(out_a) as za, np.load(out_b) as zb:
        assert set(za.files) == set(zb.files)
        for k in za.files:
            assert np.array_equal(za[k], zb[k]), k


def test_compile_cache_dir_precedence(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins over SimOptions.compile_cache_dir,
    which wins over the fixed in-checkout default."""
    own = str(tmp_path / "own")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert engine.compile_cache_dir(SimOptions(horizon=HORIZON)) \
        == engine.DEFAULT_COMPILE_CACHE_DIR
    assert engine.compile_cache_dir(
        SimOptions(horizon=HORIZON, compile_cache_dir=own)) == own
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    assert engine.compile_cache_dir(
        SimOptions(horizon=HORIZON, compile_cache_dir=own)) == outside
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert engine.DEFAULT_COMPILE_CACHE_DIR == os.path.join(root,
                                                            ".jax_cache")


def test_env_compile_cache_dir_is_the_only_one_written(tmp_path):
    """A run under JAX_COMPILATION_CACHE_DIR writes cache entries there
    and creates no other cache directory, even when SimOptions names
    one."""
    outside, own = tmp_path / "outside", tmp_path / "own"
    code = f"""
from repro.core.smla import sweep
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WorkloadSpec

w = WorkloadSpec("stream.t", 50.0, 0.85)
cells = tuple(sweep.paper_grid([("s", [w], 3)], n_req=20))
sweep.run_sweep(sweep.SweepSpec(cells, options=SimOptions(
    horizon=2000, compile_cache_dir={str(own)!r})))
print("ENV-CACHE-OK")
"""
    out = run_subprocess_jax(code, n_devices=1, env_overrides={
        "JAX_COMPILATION_CACHE_DIR": str(outside)})
    assert "ENV-CACHE-OK" in out
    assert os.listdir(outside), "the named cache must hold the executables"
    assert not own.exists()


# ----------------------------------------------------------------------------
# successive-halving pruning
# ----------------------------------------------------------------------------

def test_prune_spec_validation():
    for bad in (dict(horizon_frac=0.0), dict(horizon_frac=1.0),
                dict(keep_frac=0.0), dict(keep_frac=1.0),
                dict(rounds=-1), dict(metric="ipc"),
                dict(metric="nonsense")):
        with pytest.raises(ValueError):
            sweep.PruneSpec(**bad)
    sweep.PruneSpec()                            # defaults are valid


def test_prune_promotes_true_top_cells():
    """On a small grid the promoted survivors must contain the true best
    cells of an exhaustive sweep, their metrics must be bit-identical to
    the exhaustive run (pruning picks what runs, never changes a run),
    and every cut cell must be accounted in res.pruned."""
    cells = _cells()
    ref = sweep.run_sweep(_spec(cells))
    rtab = ref.scalars(keys=("bandwidth_gbps",))
    order = np.argsort(-rtab["bandwidth_gbps"], kind="stable")
    true_best = rtab["name"][order[0]]

    res = sweep.run_sweep(_spec(
        cells, prune=sweep.PruneSpec(horizon_frac=0.25, keep_frac=0.5,
                                     rounds=1)))
    # 10 cells -> seed keeps 5 -> round 1 keeps 3 survivors
    assert len(res.names) == 3
    assert true_best in res.names
    assert {p["name"] for p in res.pruned} \
        == set(rtab["name"]) - set(res.names)
    assert {p["round"] for p in res.pruned} == {0, 1}
    for p in res.pruned:
        assert np.isfinite(p["score"])
        assert p["metric"] in ("estimate_service_ns", "bandwidth_gbps")
    for name in res.names:                       # survivors bit-identical
        for k, v in ref[name].items():
            assert np.array_equal(np.asarray(res[name][k]),
                                  np.asarray(v)), (name, k)
    w = res.prune_work
    assert w["n_cells"] == len(cells) and w["n_survivors"] == 3
    assert 0.0 < w["executed_cell_cycles"] < w["full_horizon_cell_cycles"]


def test_prune_minimize_metric():
    """maximize=False promotes the smallest values instead."""
    cells = _cells()
    ref = sweep.run_sweep(_spec(cells)).scalars(keys=("makespan_ns",))
    res = sweep.run_sweep(_spec(
        cells, prune=sweep.PruneSpec(horizon_frac=0.25, keep_frac=0.5,
                                     rounds=1, metric="makespan_ns",
                                     maximize=False)))
    best = ref["name"][np.argsort(ref["makespan_ns"], kind="stable")[0]]
    assert best in res.names


def test_prune_zero_rounds_is_seed_cut_only():
    cells = _cells()
    res = sweep.run_sweep(_spec(
        cells, prune=sweep.PruneSpec(keep_frac=0.5, rounds=0)))
    assert len(res.names) == 5                   # ceil(0.5 * 10)
    assert all(p["round"] == 0 for p in res.pruned)
    est = analytic.estimates_for_cells(list(cells)) \
        * np.array([c.stack.unit_ns for c in cells])
    keep = sorted(np.argsort(est, kind="stable")[:5])
    assert res.names == [cells[i].name for i in keep]


def test_prune_halves_work_on_large_grid():
    """Acceptance: on a >= 1e4-cell grid, successive halving executes
    less than half the full-horizon device work.  The grid replicates a
    few base cells (shared trace arrays — building 1e4 distinct traces
    is host-side noise this test doesn't need)."""
    base = _cells((2,))[:4]
    horizon = 512
    reps = 2_500                                 # 4 * 2500 = 10_000 cells
    cells = tuple(sweep.SweepCell(f"{c.name}#r{i}", c.stack, c.traces)
                  for i in range(reps) for c in base)
    assert len(cells) >= 10_000
    res = sweep.run_sweep(sweep.SweepSpec(
        cells, options=SimOptions(horizon=horizon),
        prune=sweep.PruneSpec(horizon_frac=0.125, keep_frac=0.5, rounds=1)))
    w = res.prune_work
    assert w["full_horizon_cell_cycles"] == len(cells) * horizon
    assert w["saved_frac"] >= 0.5, w
    assert len(res.names) == int(np.ceil(0.5 * np.ceil(0.5 * len(cells))))


def test_prune_with_policy_axis():
    """The policy axis expands before pruning, so cuts apply to the
    expanded cross-product."""
    cells = _cells()[:2]
    pols = (ControllerPolicy.grid(scheduler=ControllerPolicy().scheduler,
                                  row=ControllerPolicy().row,
                                  refresh_gran=ControllerPolicy()
                                  .refresh_gran)[:4])
    res = sweep.run_sweep(_spec(
        cells, policies=tuple(pols),
        prune=sweep.PruneSpec(horizon_frac=0.25, keep_frac=0.5, rounds=1)))
    n = len(cells) * len(pols)
    assert res.prune_work["n_cells"] == n
    assert len(res.names) + len(res.pruned) == n
    assert all("|" in name for name in res.names)


def test_policy_grid_enumeration():
    full = ControllerPolicy.grid()
    assert len(full) == 768 and len(set(full)) == 768
    assert ControllerPolicy() in full
    pinned = ControllerPolicy.grid(row=ControllerPolicy().row)
    assert len(pinned) == 384
    with pytest.raises(ValueError, match="unknown policy axes"):
        ControllerPolicy.grid(rows=ControllerPolicy().row)


# ----------------------------------------------------------------------------
# multi-device: 4 and 8 forced host devices, reduce-tree cond path
# ----------------------------------------------------------------------------

_MULTI_DEV_CODE = r"""
import numpy as np
import jax
from repro.core.smla import engine, sweep
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WorkloadSpec

N_DEV = %(n_dev)d
assert len(jax.devices()) == N_DEV, jax.devices()
STREAM = WorkloadSpec("stream.t", 50.0, 0.85, write_frac=1/3)
cells = tuple(sweep.paper_grid([("s", [STREAM, STREAM], 3)], layers=(2, 4),
                               n_req=30))
opts = SimOptions(horizon=3000, chunk=256)

# auto resolves to the reduce-tree (shard-local cond) path at >= 4 devices
spec = sweep.SweepSpec(cells, options=opts)
sharding, local = sweep._resolve_cond_sharding(spec, opts, N_DEV)
assert local == N_DEV and sharding is not None, (local, sharding)

res_local = sweep.run_sweep(spec)
res_global = sweep.run_sweep(sweep.SweepSpec(cells, options=opts,
                                             cond_sharding="global"))
assert res_local.names == res_global.names
for name, g, w in zip(res_local.names, res_local.cells, res_global.cells):
    for k in g:
        if k == "chunks_run":
            continue   # local cond exits per device shard by design
        assert np.array_equal(np.asarray(g[k]), np.asarray(w[k])), (name, k)
for cell in cells:
    ref = engine.simulate(cell.stack, cell.traces, opts)
    for k in ref:
        if k == "chunks_run":
            continue
        a = np.asarray(res_local[cell.name][k])
        b = np.asarray(ref[k])
        assert np.array_equal(a, b), (cell.name, k, a, b)
print("REDUCE-TREE-OK", N_DEV)
"""


@pytest.mark.parametrize("n_dev", [4, 8])
def test_multi_device_reduce_tree_cond(n_dev):
    """At 4 and 8 forced host devices the auto cond-sharding engages the
    reduce-tree (per-device while-loop) path; metrics stay bit-identical
    to both the global-cond sharded path and single-device simulate()."""
    out = run_subprocess_jax(_MULTI_DEV_CODE % {"n_dev": n_dev},
                             n_devices=n_dev)
    assert f"REDUCE-TREE-OK {n_dev}" in out


def test_local_cond_rejected_off_scan_backend():
    cells = _cells()[:2]
    opts = SimOptions(horizon=HORIZON, backend="pallas", interpret=True)
    spec = sweep.SweepSpec(cells, options=opts, cond_sharding="local")
    with pytest.raises(ValueError, match="cond_sharding='local'"):
        sweep._resolve_cond_sharding(spec, opts, 4)


def test_local_cond_engine_requires_scan():
    opts = SimOptions(horizon=HORIZON, backend="pallas",
                      interpret=True).resolved()
    with pytest.raises(ValueError, match="local-cond"):
        engine._compiled(opts, engine.CoreParams(), 8, (2, 2, 30, 8),
                         True, 4)
