"""Metric arithmetic on hand-made bucket records."""
import types

import pytest

from bench.lib import account, registry, window

UNIT_NS = 1.25      # the 4-layer stack's fast cycle


class FakeResult:
    def __init__(self, cells):
        self.cells = cells

    def __getitem__(self, name):
        return self.cells[name]


def _run(buckets, cells, t0=100.0, trace=None):
    res = FakeResult(cells)
    jr = window.JobRun(job=None, grid=None, result=res)
    win = window.Window(t0=t0, seconds=10.0, jobs=[jr], buckets=buckets,
                        compiles=0, t_done=t0 + 12)
    cfg = {"stack": {"base_freq_mhz": 200.0, "layers": 4}}
    return types.SimpleNamespace(config=cfg, window=win, trace=trace,
                                 setup_s=3.0, warmup_s=1.0)


def _cell(cycles, chunks):
    return {"makespan_ns": cycles * UNIT_NS, "chunks_run": chunks}


def _bucket(names, rows, chunks_run, chunk, devices=1, loops=1):
    return window.Bucket(job=0, meta={
        "cells": names, "n_rows": rows, "chunks_run": chunks_run,
        "chunk": chunk}, devices=devices, loops=loops)


def _cells():
    return {"a": _cell(1000, 1), "b": _cell(3000, 3), "c": _cell(2000, 2),
            "d": _cell(500, 1)}


def test_sim_cycles_per_s_counts_cells_finished_in_the_window():
    # the window closes when its last job returns (t_done, 12 s after t0):
    # every cell of that job counts, the bucket past t_close too
    buckets = [_bucket(["a", "b"], 2, 3, 1024),
               _bucket(["c"], 2, 2, 1024),
               _bucket(["d"], 2, 1, 1024)]
    run = _run(buckets, _cells())
    got = registry.reader("sim_cycles_per_s")(run)
    assert got == pytest.approx((1000 + 3000 + 2000 + 500) / 12.0)


def test_lane_waste_share_counts_pad_rows():
    buckets = [_bucket(["a", "b"], 2, 3, 1024),
               _bucket(["c"], 2, 2, 1024)]
    run = _run(buckets, _cells())
    stepped = 2 * 3 * 1024 + 2 * 2 * 1024
    got = registry.reader("lane_waste_share")(run)
    assert got == pytest.approx(1 - 6000 / stepped)


def test_no_finished_bucket_reads_nothing():
    run = _run([], _cells())
    assert registry.reader("sim_cycles_per_s")(run) is None
    assert registry.reader("lane_waste_share")(run) is None


def test_exec_us_per_step_counts_every_bucket_run():
    # the traced span holds every bucket the jobs ran
    buckets = [_bucket(["a", "b", "c"], 4, 3, 1000),
               _bucket(["d"], 2, 1, 512)]
    trace = types.SimpleNamespace(busy_s=[0.4])
    run = _run(buckets, _cells(), trace=trace)
    got = registry.reader("exec_us_per_step")(run)
    assert got == pytest.approx(0.4 / (3 * 1000 + 1 * 512) * 1e6)


def test_one_loop_runs_the_buckets_chunks():
    # one device: a bucket is one loop, which runs the bucket's
    # chunks_run; its pad rows copy the first row
    buckets = [_bucket(["a", "b", "c"], 4, 3, 1000),
               _bucket(["d", "c"], 4, 2, 512)]
    run = _run(buckets, _cells())
    assert account.row_cells(buckets[1]) == ["d", "c", "d", "d"]
    for b in buckets:
        assert account.loop_chunks(run, b) == [b.meta["chunks_run"]]
    assert account.stepped_cycles(run) == 3 * 1000 + 2 * 512
    assert account.stepped_cycles(run) == account.synchronous_cycles(run)
    assert registry.reader("device_step_share")(run) is None


def test_four_devices_each_step_their_own_rows():
    # a row a device, each device's loop exiting on its own row: the
    # devices step 1 + 3 + 2 + 1 chunks, where waiting for the slowest
    # row would step 4 x 3; the second bucket's pad rows (copies of d)
    # sit on devices 2 and 3
    buckets = [_bucket(["a", "b", "c", "d"], 4, 3, 1000, devices=4,
                       loops=4),
               _bucket(["d", "c"], 4, 2, 100, devices=4, loops=4)]
    trace = types.SimpleNamespace(busy_s=[0.1, 0.4, 0.3, 0.2])
    run = _run(buckets, _cells(), trace=trace)
    assert account.loop_chunks(run, buckets[0]) == [1, 3, 2, 1]
    assert account.loop_chunks(run, buckets[1]) == [1, 2, 1, 1]
    stepped = 7 * 1000 + 5 * 100
    assert account.stepped_cycles(run) == stepped
    assert account.synchronous_cycles(run) == 4 * 3 * 1000 + 4 * 2 * 100
    got = registry.reader("lane_waste_share")(run)
    assert got == pytest.approx(1 - (6500 + 2500) / stepped)
    got = registry.reader("exec_us_per_step")(run)
    assert got == pytest.approx(1.0 / stepped * 1e6)
    got = registry.reader("device_step_share")(run)
    assert got == pytest.approx(stepped / (12000 + 800))


def test_one_loop_over_four_devices_steps_the_slowest_row_on_each():
    # global exit: every device steps the bucket until its slowest row
    # exits, so nothing is saved
    buckets = [_bucket(["a", "b", "c", "d"], 4, 3, 1000, devices=4)]
    trace = types.SimpleNamespace(busy_s=[0.3] * 4)
    run = _run(buckets, _cells(), trace=trace)
    assert account.loop_chunks(run, buckets[0]) == [3]
    assert account.stepped_cycles(run) == 4 * 3 * 1000
    got = registry.reader("lane_waste_share")(run)
    assert got == pytest.approx(1 - 6500 / (4 * 3 * 1000))
    got = registry.reader("exec_us_per_step")(run)
    assert got == pytest.approx(1.2 / (4 * 3 * 1000) * 1e6)
    assert registry.reader("device_step_share")(run) == 1.0


def test_setup_and_compile_readers():
    run = _run([], _cells())
    assert registry.reader("setup_s")(run) == 3.0
    assert registry.reader("warmup_s")(run) == 1.0
    assert registry.reader("window_compiles")(run) == 0
    assert registry.reader("exec_us_per_step")(run) is None
