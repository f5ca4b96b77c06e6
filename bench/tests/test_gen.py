"""The traffic generator: jobs are a pure function of (seed, job index)."""
import numpy as np
import pytest

from bench.lib import gen, registry

SEEDS = (0, 2**31 + 11, 2**40 + 3)


def _load(cell):
    bm = registry.benchmark()
    wl = registry.workload(bm, cell)
    return registry.config(bm, wl["config"]), registry.traffic(wl["traffic"])


def _same(a: gen.Job, b: gen.Job) -> bool:
    return (a.policies == b.policies and len(a.cells) == len(b.cells)
            and all(x.name == y.name and x.workloads == y.workloads
                    and all(np.array_equal(x.traces[k], y.traces[k])
                            for k in x.traces)
                    for x, y in zip(a.cells, b.cells)))


@pytest.mark.parametrize("cell", ["smla4-mp16", "smla8-policy"])
@pytest.mark.parametrize("seed", SEEDS)
def test_job_is_a_function_of_seed_and_index(cell, seed):
    cfg, traffic = _load(cell)
    a = gen.make_job(cfg, traffic, seed, 0)
    assert _same(a, gen.make_job(cfg, traffic, seed, 0))
    assert not _same(a, gen.make_job(cfg, traffic, seed, 1))
    assert not _same(a, gen.make_job(cfg, traffic, seed + 1, 0))


@pytest.mark.parametrize("cell,cells,policies", [
    ("smla4-mp16", 30, 1), ("smla8-policy", 10, 11)])
def test_job_shape(cell, cells, policies):
    cfg, traffic = _load(cell)
    job = gen.make_job(cfg, traffic, 5, 0)
    assert len(job.cells) == cells and len(job.policies) == policies
    assert len(job.expanded()) == cells * policies
    for c in job.cells:
        assert c.traces["inst"].shape == (len(c.workloads), traffic["n_req"])


def test_every_job_runs_the_traffic_files_mixes():
    cfg, traffic = _load("smla4-mp16")
    for k in (0, 3):
        job = gen.make_job(cfg, traffic, 9, k)
        assert [c.workloads for c in job.cells[::5]] == [
            tuple(m) for m in traffic["mixes"]]


def test_trace_copy_matches_the_program_generator():
    """The benchmark's copy of the trace synthesis gives the streams the
    program's own generator gives (as of the benchmark's definition)."""
    from repro.core.smla import traces
    cfg, _ = _load("smla4-mp16")
    table = {w["name"]: w for w in cfg["assumed"]["workload_table"]}
    for w in traces.WORKLOADS:
        mine = gen.synthetic_trace(77, table[w.name], 64, 4, 2)
        theirs = traces.synthetic_trace(77, w, 64, 4, 2)
        for k in mine:
            assert np.array_equal(mine[k], theirs[k]), (w.name, k)


@pytest.mark.parametrize("cell", ["smla4-mp16", "smla8-policy"])
def test_seed_relabels_the_same_streams(cell):
    """Two seeds give a job the same arrivals and directions, and the same
    requests meeting in a bank and in a row, under other addresses."""
    cfg, traffic = _load(cell)
    a, b = (gen.make_job(cfg, traffic, s, 0) for s in SEEDS[1:])
    for x, y in zip(a.cells, b.cells):
        for k in ("inst", "rank", "wr"):
            assert np.array_equal(x.traces[k], y.traces[k])
        for k in ("bank", "row"):
            pairs = set(zip(x.traces[k].ravel(), y.traces[k].ravel()))
            assert len(pairs) == len(np.unique(x.traces[k])) \
                == len(np.unique(y.traces[k]))
    assert any(not np.array_equal(x.traces["row"], y.traces["row"])
               for x, y in zip(a.cells, b.cells))


def test_every_seed_simulates_the_same_work():
    """Under the paper's controller the reference reads the same
    statistics for a tiny job's cells on two seeds: a seed changes
    addresses, not the work."""
    from bench.lib import check
    from bench.reference import controller
    from bench.tests.conftest import tiny_traffic
    cfg, traffic = _load("smla4-mp16")
    traffic = tiny_traffic(traffic)
    outs = []
    for seed in SEEDS[1:]:
        job = gen.make_job(cfg, traffic, seed, 0)
        outs.append(controller.simulate_many(
            [check.reference_cell(cfg, traffic, c, p)
             for c, p in job.expanded()]))
    for x, y in zip(*outs):
        assert x.keys() == y.keys()
        for k in x:
            assert np.array_equal(np.asarray(x[k]), np.asarray(y[k])), k
