"""The stage probe's reduction and the program spans' reader, on
hand-made events and on a probe recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

from bench.lib import probe, spans
from repro.core.smla import engine

STAGES = engine.STAGE_SCOPES
LOOP = engine.LOOP_SCOPE
#: the framework name of an op of the scan body, in stage `s`
BODY = "jit(_sim_core)/vmap()/while/body/while/body/closed_call/smla.{}/{}"


def _ops():
    """One op in each stage, then the ops only a compiled program has."""
    ops = [[BODY.format(s, "add"), 10.0] for s in STAGES]
    return ops + [
        # a fusion of ops from two stages carries its root's name
        [BODY.format("transfer", "select_n"), 30.0],
        # a copy XLA inserted, with no framework name
        ["", 5.0],
        # the loop's control
        ["jit(_sim_core)/vmap()/while/cond/smla.loop/lt", 2.0],
    ]


def test_ops_go_to_their_innermost_stage_scope():
    r = probe.reduce_ops(_ops(), STAGES, LOOP)
    assert list(r) == list(STAGES) + [probe.UNSCOPED]
    assert r["transfer"] == [40.0, 2]
    assert r[probe.UNSCOPED] == [7.0, 2]
    assert all(r[s] == [10.0, 1] for s in STAGES if s != "transfer")
    nested = "jit(f)/smla.loop/while/body/smla.power/mul"
    assert probe.scope_of(nested, STAGES, LOOP) == "power"


def test_a_scope_the_program_does_not_declare_fails():
    ops = _ops() + [[BODY.format("xfer", "add"), 1.0]]
    with pytest.raises(ValueError, match="smla.xfer"):
        probe.reduce_ops(ops, STAGES, LOOP)


def test_a_declared_scope_that_no_op_carries_fails():
    ops = [op for op in _ops() if "smla.gate/" not in op[0]]
    with pytest.raises(ValueError, match="gate"):
        probe.reduce_ops(ops, STAGES, LOOP)


def test_executables_weighted_by_the_window_steps():
    a = probe.reduce_ops(_ops(), STAGES, LOOP)
    b = {s: [2 * ns, 3 * n] for s, (ns, n) in a.items()}
    # a probed 100 steps, b 200; the window stepped 3x as much with b.
    # transfer: 40 ns and 2 ops in a, 80 ns and 6 ops in b
    rows = {r[0]: r[1:] for r in
            probe.weighted([(a, 100, 1000), (b, 200, 3000)])}
    assert rows["transfer"] == pytest.approx(
        [0.4e-3, 0.25 * 2 / 100 + 0.75 * 6 / 200])


def test_probe_rows_and_totals():
    p = probe.Probe([["refresh", 1.5, 3.0], [probe.UNSCOPED, 0.5, 2.0]],
                    probe_s=1.0, trace_bytes=10)
    assert p.stage("refresh") == ["refresh", 1.5, 3.0]
    assert p.ops_per_step == 5.0
    with pytest.raises(KeyError):
        p.stage("transfer")


def _spans():
    # thread 0 dispatches (it holds bench.run_sweep), thread 1 is the
    # producer; the window is the job, 100..1100 ns
    return [[0, "bench.job", 100, 1000], [0, "bench.run_sweep", 110, 980],
            [0, "smla.plan", 120, 10],
            [0, "smla.wait_prepare", 50, 100],     # half before the window
            [0, "smla.wait_prepare", 400, 30],
            [1, "smla.wait_prepare", 500, 300],    # not the dispatcher's
            [1, "smla.prepare", 130, 200]]


def test_wait_is_the_dispatching_threads_inside_the_window():
    assert spans.wait_s(_spans()) == pytest.approx((50 + 30) * 1e-9)


def test_no_program_span_reads_nothing():
    assert spans.wait_s([s for s in _spans()
                         if not s[1].startswith("smla.")]) is None


def test_spans_without_one_dispatching_thread_fail():
    two = _spans() + [[1, "bench.run_sweep", 200, 10]]
    with pytest.raises(ValueError):
        spans.wait_s(two)


def test_probe_calls_the_windows_executables_for_one_chunk(tiny, one_job):
    """Every bucket the window ran maps to one probed executable, the
    weights are the window's stepped cycles, and each probe call runs
    one chunk of the executable the window ran, with nothing built,
    whose compiled text names every stage scope (CPU, tiny)."""
    from bench.lib import gen, registry, window
    bm = registry.benchmark()
    wl = registry.workload(bm, "smla4-mp16")
    cfg, traffic = registry.config(bm, wl["config"]), \
        registry.traffic(wl["traffic"])
    win = window.run(lambda k: gen.make_job(cfg, traffic, 2**31 + 7, k),
                     cfg, traffic, 1e6)
    buckets, stepped = probe.window_executables(win)
    assert set(stepped) == set(buckets)
    assert sum(stepped.values()) == sum(
        b.meta["chunks_run"] * b.meta["chunk"] for b in win.buckets)
    spec = win.jobs[0].grid.spec
    before = engine.compile_stats()
    for b in buckets.values():
        width = engine.effective_chunk(spec.resolved_options().horizon,
                                       b.chunk_b)
        compiled, args = probe.executable(spec, b)
        assert probe.steps_run(compiled(*args), spec, b) == width
        names = probe.op_names(compiled.as_text())
        scopes = {probe.scope_of(n, STAGES, LOOP) for n in names.values()}
        assert scopes == set(STAGES) | {probe.UNSCOPED}
    # the executables the window ran, which JAX still holds: none built
    assert probe.builds(engine.compile_stats()) == probe.builds(before)


def test_nested_op_events_count_their_own_time():
    # a loop's event (0..100 ps) holds two body ops, one holding a third
    events = [(0, 100, "%while.1 = (s32[]) while(...)"),
              (10, 30, "%fusion.2 = s32[4] fusion(...)"),
              (50, 40, "%while.3 = s32[4] while(...)"),
              (60, 10, "%copy.4 = s32[4] copy(...)"),
              (100, 5, "%add.5 = s32[] add(...)")]   # after the loop
    got = probe._self_times(events)
    assert {i: t for i, t, _ in got} == {
        "while.1": 0.03, "fusion.2": 0.03, "while.3": 0.03,
        "copy.4": 0.01, "add.5": 0.005}
    assert [p for _, _, p in got] == [-1, 0, 0, 2, -1]


def test_an_op_with_no_name_takes_its_enclosing_ops_scope():
    # the enqueue's gather is a loop XLA built: its body's ops carry no
    # op name, so they count under the loop's own, innermost named event
    events = [(0, 100, "%while.1 = s32[] while(...)"),
              (10, 60, "%while.2 = s32[4] while(...)"),
              (20, 20, "%fusion.3 = s32[4] fusion(...)"),
              (45, 10, "%copy.4 = s32[4] copy(...)"),
              (80, 10, "%copy.5 = s32[4] copy(...)"),
              (100, 5, "%copy.6 = s32[4] copy(...)")]
    names = {"while.1": "jit(_sim_core)/while",
             "while.2": BODY.format("enqueue", "gather"),
             "fusion.3": "", "copy.4": "", "copy.5": "", "copy.6": ""}
    ops = probe.named_ops(probe._self_times(events), names)
    assert [n for n, _ in ops] == [
        names["while.1"], names["while.2"], names["while.2"],
        names["while.2"], names["while.1"], ""]
    scopes = [probe.scope_of(n, STAGES, LOOP) for n, _ in ops]
    assert scopes == [probe.UNSCOPED, "enqueue", "enqueue", "enqueue",
                      probe.UNSCOPED, probe.UNSCOPED]


def test_an_instruction_the_executable_lacks_fails():
    events = [(0, 10, "%fusion.9 = s32[4] fusion(...)")]
    with pytest.raises(ValueError, match="fusion.9"):
        probe.named_ops(probe._self_times(events), {"fusion.8": ""})


def test_op_names_of_a_compiled_module():
    hlo = """HloModule jit__sim_core
%body (p: s32[]) -> s32[] {
  %p = s32[] parameter(0)
  ROOT %fusion.7 = s32[] fusion(%p), kind=kLoop, metadata={op_name="jit(_sim_core)/smla.power/add" stack_frame_id=3}
}
"""
    assert probe.op_names(hlo) == {"p": "",
                                   "fusion.7": "jit(_sim_core)/smla.power/add"}


#: the stage probe of a traced run of ``smla4-mp16`` at the tests' tiny
#: size (``conftest.TINY``, one job, seed 2**31 + 7) on a TPU v5e: its one
#: executable's ``.xplane.pb`` and ``op_names``, gzipped, and the probe's
#: record with the run's metrics
RECORDED = Path(__file__).parent / "data" / "smla4-mp16-tiny-probe"


def test_recorded_probe_reduces_to_what_its_run_reported(tmp_path):
    pb = tmp_path / "probe.xplane.pb"
    pb.write_bytes(gzip.decompress(
        RECORDED.with_suffix(".xplane.pb.gz").read_bytes()))
    names = json.loads(gzip.decompress(
        RECORDED.with_suffix(".op_names.json.gz").read_bytes()))
    run = json.loads(RECORDED.with_suffix(".json").read_text())
    exe, = run["executables"]
    reduced = probe.reduce_trace(str(pb), names, STAGES, LOOP)
    assert sum(v[1] for v in reduced.values()) == exe["ops"]
    stages = probe.weighted([(reduced, exe["steps"], exe["window_steps"])])
    assert stages == run["device_stages"]
    p = probe.Probe(stages, run["probe_s"], run["trace_bytes"])
    assert p.ops_per_step == run["metrics"]["device_ops_per_step"]["value"]
    for scope, us, _ in stages:
        assert us == run["metrics"][f"stage_{scope}_us_per_step"]["value"]
    # one chunk stepped per step the sum of the stages, within the host's
    # bound on an execution
    assert 0.9 < sum(us for _, us, _ in stages) \
        / run["metrics"]["exec_us_per_step"]["value"] < 1.1
