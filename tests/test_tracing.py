"""The program's own instrumentation: the sweep pipeline's host spans in
the profiler's trace, and the engine's compile counters."""
import collections
import glob
import json

import jax
from conftest import run_subprocess_jax

from repro.core.smla import golden, sweep
from repro.core.smla.engine import SimOptions

#: spans the dispatching thread or the producer opens once per bucket
PER_BUCKET = ("smla.prepare", "smla.dispatch", "smla.harvest",
              "smla.finalize")


def _spans(log_dir: str) -> list[tuple[str, dict]]:
    """``(name, args)`` of every ``smla.*`` span on the host plane."""
    path, = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(e.name, dict(e.stats)) for line in plane.lines
                    for e in line.events if e.name.startswith("smla.")]
    return out


def test_sweep_spans_name_each_bucket(tmp_path):
    # the golden grid's cells and buckets, over a horizon short enough
    # that the trace of the CPU's own ops (one event per op and fast
    # cycle) stops in seconds
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    spec = sweep.SweepSpec(tuple(golden.grid_cells()),
                           options=SimOptions(horizon=128))
    with jax.profiler.trace(str(tmp_path), profiler_options=po):
        res = sweep.run_sweep(spec)
    spans = _spans(str(tmp_path))
    names = collections.Counter(n for n, _ in spans)
    assert names["smla.plan"] == 1
    n = len(res.buckets)
    assert n > 1
    for name in PER_BUCKET:
        got = sorted(a["bucket"] for s, a in spans if s == name)
        assert got == list(range(n)), name
    # the dispatching thread waits once per bucket and once for the end
    waits = [a.get("bucket") for s, a in spans if s == "smla.wait_prepare"]
    assert sorted(waits, key=lambda b: (b is None, b)) \
        == list(range(n)) + [None]


#: one single-cell simulation at a shape no other test uses, twice; prints
#: the compile counters after each call
_COMPILE_ONCE = """
import json
from repro.core.smla import engine
from repro.core.smla.config import StackConfig
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WORKLOADS, core_traces
stack = StackConfig()
traces = core_traces(0, [WORKLOADS[0]] * 3, 37, stack.n_ranks,
                     stack.banks_per_rank)
for _ in range(2):
    engine.simulate(stack, traces, SimOptions(horizon=2048, chunk=256))
    s = engine.compile_stats()
    print("STATS", json.dumps([s.lru_misses, s.xla_compiles,
                               s.cache_loads, s.compile_s, s.load_s,
                               s.trace_lower_s]))
"""


def _stats(out: str) -> list[list]:
    return [json.loads(line.split(" ", 1)[1])
            for line in out.splitlines() if line.startswith("STATS ")]


def test_compile_stats_tell_a_compile_from_a_cache_load(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    cold = _stats(run_subprocess_jax(_COMPILE_ONCE, n_devices=1,
                                     env_overrides=env))
    # a new shape: one executable, compiled; its second call builds none
    assert [s[:3] for s in cold] == [[1, 1, 0], [1, 1, 0]]
    assert cold[0][3] > 0 and cold[0][4] == 0 and cold[0][5] > 0
    assert cold[1] == cold[0]
    # a fresh process meets the warm persistent cache: a load, no compile
    warm = _stats(run_subprocess_jax(_COMPILE_ONCE, n_devices=1,
                                     env_overrides=env))
    assert [s[:3] for s in warm] == [[1, 0, 1], [1, 0, 1]]
    assert warm[0][3] == 0 and warm[0][4] > 0
