"""The trace-to-metric reduction, on hand-made events and on a trace
recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

from bench.lib import trace

#: a traced run of ``smla4-mp16`` at the tests' tiny size (``conftest.TINY``,
#: seed 2**31 + 7) on a TPU v5e: the profiler's ``.xplane.pb``, gzipped,
#: and the run's result line
RECORDED = Path(__file__).parent / "data" / "smla4-mp16-tiny"
#: the same of ``smla4-mp16-x4`` on a TPU v5e-4
RECORDED_X4 = Path(__file__).parent / "data" / "smla4-mp16-x4-tiny"


def _events():
    # one device; times in ns.  Window: the two jobs, 0..1000.
    return {
        "devices": [[["jit__sim_core(1)", 100, 300],
                     ["jit__sim_core(1)", 350, 150],
                     ["jit__sim_core(2)", 700, 200],
                     ["jit__sim_core(2)", 1500, 100]]],   # after the window
        "spans": [["bench.job", 0, 600], ["bench.run_sweep", 10, 580],
                  ["bench.job", 600, 400], ["bench.run_sweep", 650, 340],
                  ["bench.bucket_done", 520, 1]],
    }


def test_busy_window_and_ops():
    s = trace.reduce(_events())
    assert s.window_s == pytest.approx(1000e-9)
    # union of [100, 400], [350, 500], [700, 900]
    assert s.busy_s == [pytest.approx((400 + 200) * 1e-9)]
    assert s.device_ops[0] == ["jit__sim_core(1)", pytest.approx(450e-9)]
    assert len(s.device_ops) == 2


def test_idle_gaps_are_named_by_the_innermost_span():
    s = trace.reduce(_events())
    # busy [100, 500] and [700, 900]: gaps 0-100 and 900-1000 inside a
    # run_sweep span, 500-700 between the jobs' run_sweep calls
    got = sorted((round(d * 1e9), n) for n, d in s.idle_gaps)
    assert got == [(100, "bench.run_sweep"), (100, "bench.run_sweep"),
                   (200, "bench.job")]


def test_overlapping_executions_count_once():
    ev = _events()
    ev["devices"][0].append(["jit__sim_core(1)", 120, 100])
    assert trace.reduce(ev).busy_s == trace.reduce(_events()).busy_s


def test_executions_from_runtime_events():
    # two executions issued back to back, a third after an idle gap;
    # completions are read in order
    got = trace.executions([0, 10, 500], [300, 450, 700])
    assert [(s, d) for _, s, d in got] == [(0, 300), (300, 150),
                                           (500, 200)]


def test_four_devices_reduce_each_on_its_own():
    # four chips of one bucket: 0 and 1 execute over the same interval,
    # 2 exits its loop early, 3 late; none is merged with another
    ev = {"devices": [[["program execution", 100, 300]],
                      [["program execution", 100, 300]],
                      [["program execution", 100, 100]],
                      [["program execution", 100, 500],
                       ["program execution", 700, 200]]],
          "spans": _events()["spans"]}
    s = trace.reduce(ev)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == [pytest.approx(x * 1e-9) for x in (300, 300, 100,
                                                          700)]
    assert s.device_ops == [["program execution", pytest.approx(1400e-9)]]
    # each device's own gaps: device 2 idles from 200 to the window's
    # end while the others execute, devices 0 and 1 from 400, device 3
    # only between its two executions and at the window's edges
    got = sorted((round(d * 1e9), n) for n, d in s.idle_gaps)
    assert got == [(100, "bench.run_sweep")] * 6 + [
        (600, "bench.run_sweep")] * 2 + [(800, "bench.job")]


def test_one_chip_record_names_device_zero(tmp_path):
    """The one-chip record's executions take their device from the
    runtime's ordinal, not from the run's chip count: read as a run of
    four chips, every execution still falls on device 0."""
    pb = tmp_path / "run.xplane.pb"
    pb.write_bytes(gzip.decompress(
        RECORDED.with_suffix(".xplane.pb.gz").read_bytes()))
    one, four = trace.extract(str(pb), 1), trace.extract(str(pb), 4)
    assert four["devices"] == one["devices"] + [[], [], []]
    assert four["spans"] == one["spans"]


def test_no_job_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": [[]], "spans": []})


def test_recorded_trace_reduces_to_what_its_run_reported(tmp_path):
    pb = tmp_path / "run.xplane.pb"
    pb.write_bytes(gzip.decompress(
        RECORDED.with_suffix(".xplane.pb.gz").read_bytes()))
    run = json.loads(RECORDED.with_suffix(".json").read_text())
    ex = trace.extract(str(pb), 1)
    # one program execution per bucket the window ran
    assert len(ex["devices"][0]) == run["window"]["buckets"]
    s = trace.reduce(ex)
    assert 0 < s.busy_s[0] < s.window_s
    assert s.window_s == run["device"]["window_s"]
    assert s.busy_s == [run["device"]["busy_s"]]
    assert s.device_ops == run["breakdown"]["device_ops"]
    assert s.idle_gaps == run["breakdown"]["idle_gaps"]


def test_recorded_four_chip_trace_reduces_to_what_its_run_reported(
        tmp_path):
    pb = tmp_path / "run.xplane.pb"
    pb.write_bytes(gzip.decompress(
        RECORDED_X4.with_suffix(".xplane.pb.gz").read_bytes()))
    run = json.loads(RECORDED_X4.with_suffix(".json").read_text())
    assert run["device"]["count"] == 4
    ex = trace.extract(str(pb), 4)
    # each device executed every bucket the window ran, once
    assert [len(d) for d in ex["devices"]] == [run["window"]["buckets"]] * 4
    s = trace.reduce(ex)
    assert s.window_s == run["device"]["window_s"]
    assert sum(s.busy_s) / len(s.busy_s) == run["device"]["busy_s"]
    assert max(s.busy_s) < s.window_s
    assert s.device_ops == run["breakdown"]["device_ops"]
    assert s.idle_gaps == run["breakdown"]["idle_gaps"]
    # the devices' buckets end apart: none is another's copy
    ends = {tuple(s + d for _, s, d in dev) for dev in ex["devices"]}
    assert len(ends) == 4


def test_an_execution_that_names_no_device_of_the_run_is_an_error(
        tmp_path):
    pb = tmp_path / "run.xplane.pb"
    pb.write_bytes(gzip.decompress(
        RECORDED_X4.with_suffix(".xplane.pb.gz").read_bytes()))
    with pytest.raises(ValueError):
        trace.extract(str(pb), 2)
