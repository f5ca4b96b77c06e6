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


def test_a_trace_of_several_chips_is_refused():
    with pytest.raises(ValueError):
        trace.extract("unused.xplane.pb", 4)


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
