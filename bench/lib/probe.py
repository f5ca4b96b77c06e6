"""The stage probe: per-stage device time of one fast cycle, from an
op-level trace of one chunk per executable.

The window's trace holds the host only: an op-level trace of the device
cannot hold a window (``bench/lib/trace.py``).  The probe runs the first
time a reader asks for it (`of`: the first ``stage_*`` or
``device_ops_per_step`` reader, after the window's trace is reduced and
the reference check has run), and every reader gets the same probe,
whatever their order.  For every distinct executable the window ran it
builds the bucket's arguments as ``program.warm`` does (one request per
core, so a call ends after its first chunk) and takes the program from
``engine.batched_executable``: the compiled object the window ran, which
JAX still holds, so that the call traced with the device's ops and the
text that names them are of one executable.  A probe that builds an
executable (`builds` of ``engine.compile_stats()`` grows) is an error,
so the ``warmup_xla_*`` readers read the same whatever their order.  A
step of ``engine._sim_core`` has no data-dependent branch, so a chunk
costs per step what the window's chunks cost.

A device op event names its HLO instruction; the compiled text gives
the instruction's framework op name, whose innermost ``smla.<scope>``
name scope places the event among ``engine.STAGE_SCOPES`` (the seven
stages and the live-step gate; a fusion carries its root's name).
Events nest (a loop's event holds its body's), so each op counts its own
time, and an op with no op name (the body of a loop XLA built, such as
the enqueue's gather) takes the name of the innermost event that holds
it and has one.  Ops in ``engine.LOOP_SCOPE`` or in no scope (copies XLA
inserts, loop control) count as ``unscoped``.  A ``smla.`` scope the
program does not declare, a declared one that no op carries, or an
event naming an instruction the text lacks is an error, so that a
renamed scope fails the run instead of reading zero.  Per scope the
probe gives device microseconds and ops per fast cycle stepped (chunks
run x chunk width), each executable weighted by the fast cycles the
window's buckets stepped with it.

The probe's traces, each executable's ``op_names.json`` and
``probe.json`` (the breakdown, the probe's wall time and trace size)
stay in ``.bench_trace/<cell>/probe/``; ``probe.json`` is also
printed to stderr as a ``probe: {...}`` line.  A program that declares
no ``STAGE_SCOPES``, or a cell on more than one chip, gets no probe, and
its readers report nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import shutil
import sys
import time

import jax
import numpy as np

from repro.core.smla import engine, sweep

from bench.lib import program, spans

UNSCOPED = "unscoped"
#: the device op events of a trace: planes of the chips, their ops line
DEVICE_PLANE, OPS_LINE = "/device:TPU:", "XLA Ops"
#: an op event's name is its HLO instruction, ``%<name> = ...``
_INSTRUCTION = re.compile(r"%([^\s=]+) = ")


@dataclasses.dataclass
class Probe:
    #: ``[scope, us_per_step, ops_per_step]``, the stage scopes in the
    #: program's order, then ``unscoped``
    device_stages: list[list]
    probe_s: float
    trace_bytes: int

    def stage(self, scope: str) -> list:
        for row in self.device_stages:
            if row[0] == scope:
                return row
        raise KeyError(f"the probe found no scope {scope!r}: the program "
                       f"declares {[r[0] for r in self.device_stages]}")

    @property
    def ops_per_step(self) -> float:
        return sum(r[2] for r in self.device_stages)


def of(run) -> Probe | None:
    """The probe of a traced run, measured the first time a reader asks."""
    if "_probe" not in vars(run):
        run._probe = measure(run)
    return run._probe


def scope_of(op_name: str, stages: tuple, loop: str) -> str:
    """The stage scope an op's framework name falls in (its innermost
    ``smla.`` component), or ``UNSCOPED``."""
    found = [c[len(engine.SCOPE_PREFIX):] for c in op_name.split("/")
             if c.startswith(engine.SCOPE_PREFIX)]
    if not found:
        return UNSCOPED
    if found[-1] not in stages + (loop,):
        raise ValueError(f"op {op_name!r} is in a scope the program does "
                         f"not declare (stages {stages}, loop {loop!r})")
    return found[-1] if found[-1] in stages else UNSCOPED


def reduce_ops(ops: list[list], stages: tuple, loop: str) -> dict:
    """``{scope: [device_ns, n_ops]}`` over ``[op_name, self_ns]`` events,
    for every stage scope and ``UNSCOPED``."""
    out = {s: [0.0, 0] for s in stages + (UNSCOPED,)}
    for name, dur in ops:
        acc = out[scope_of(name, stages, loop)]
        acc[0] += dur
        acc[1] += 1
    empty = [s for s in stages if not out[s][1]]
    if empty:
        raise ValueError(f"no device op carries the scopes {empty}: the "
                         f"trace names scopes otherwise than the program")
    return out


def op_names(hlo: str) -> dict:
    """``{instruction: framework op name}`` of a compiled module's text
    (an instruction without metadata has the name ``""``)."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s+(?:ROOT )?%([^\s=]+) = ", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = name.group(1) if name else ""
    return out


def device_ops(path: str) -> list[list]:
    """``[instruction, self_ns, parent]`` for every op event on the chips'
    planes of an ``.xplane.pb``, as `_self_times` gives them; `parent`
    indexes this list."""
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                base = len(out)
                out += [[i, t, p if p < 0 else base + p]
                        for i, t, p in _self_times(
                            [(round(e.start_ns * 1e3),
                              round(e.duration_ns * 1e3), e.name)
                             for e in line.events])]
    return out


def _self_times(events: list[tuple]) -> list[list]:
    """``[instruction, self_ns, parent]`` from ``(start_ps, dur_ps, name)``
    events of one line, which nest and never partly overlap, in order of
    start: an op's events nest (a loop holds its body's ops), so each
    counts its own time, its span less its children's; `parent` is the
    index of the innermost event that holds it, or -1."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [d for _, d, _ in events]
    parent = [-1] * len(events)
    open_: list[tuple[int, int]] = []           # (end_ps, index)
    for i, (s, d, _) in enumerate(events):
        while open_ and open_[-1][0] <= s:
            open_.pop()
        if open_:
            own[open_[-1][1]] -= d
            parent[i] = open_[-1][1]
        open_.append((s + d, i))
    out = []
    for (_, _, name), t, p in zip(events, own, parent):
        m = _INSTRUCTION.match(name)
        if m is None:
            raise ValueError(f"op event {name[:80]!r} names no instruction")
        out.append([m.group(1), t / 1e3, p])
    return out


def named_ops(ops: list[list], names: dict) -> list[list]:
    """``[op_name, self_ns]`` of `device_ops`, each named by its
    instruction's framework op name (`op_names`); an instruction with
    none takes the name of the innermost event that holds it and has
    one.  An instruction `names` lacks is an error: the events and the
    text are of different programs."""
    out = []
    for instruction, t, parent in ops:
        if instruction not in names:
            raise ValueError(f"the trace names an instruction, "
                             f"{instruction!r}, the executable lacks")
        name = names[instruction]
        if not name and parent >= 0:
            name = out[parent][0]
        out.append([name, t])
    return out


def weighted(per_exe: list[tuple[dict, float, float]]) -> list[list]:
    """``device_stages`` from ``(reduced, steps probed, weight)`` per
    executable: per scope, the weighted mean over executables of device
    us and ops per step."""
    total = sum(w for _, _, w in per_exe)
    scopes = list(per_exe[0][0])
    return [[s,
             sum(w * r[s][0] / n for r, n, w in per_exe) / total / 1e3,
             sum(w * r[s][1] / n for r, n, w in per_exe) / total]
            for s in scopes]


def executable_key(b) -> tuple:
    """What makes a bucket's executable distinct, as ``program.warm``
    keys it."""
    return (b.banks, b.chunk_b, len(b.positions), b.r_max, b.n_req_max,
            b.local_cond, b.sharding is not None)


def window_executables(win) -> tuple[dict, dict]:
    """``({key: a bucket of it}, {key: fast cycles stepped})`` over every
    bucket of the window's jobs."""
    bucket_of, stepped = {}, {}
    for jr in win.jobs:
        key_of = {}
        for b in program._plan(jr.grid.spec):
            bucket_of.setdefault(executable_key(b), b)
            for j in b.positions:
                key_of[b.group[j].name] = executable_key(b)
        for meta in jr.result.buckets:
            k = key_of[meta["cells"][0]]
            stepped[k] = stepped.get(k, 0) + meta["chunks_run"] * \
                meta["chunk"]
    return bucket_of, stepped


def reduce_trace(path: str, names: dict, stages: tuple,
                 loop: str) -> dict:
    """`reduce_ops` over the op events of a probe's ``.xplane.pb``, named
    by `named_ops` from `op_names` of the executable's text."""
    return reduce_ops(named_ops(device_ops(path), names), stages, loop)


def executable(spec, b) -> tuple:
    """Bucket `b`'s program as ``engine.batched_executable`` gives it,
    ``(compiled, args)``, on the arguments ``program.warm`` builds: one
    request per core, so that a call ends after its first chunk."""
    params, traces = sweep._build_arrays(b)
    params["n_req"] = np.ones_like(params["n_req"])
    if b.sharding is not None:
        params = jax.device_put(params, b.sharding)
        traces = jax.device_put(traces, b.sharding)
    return engine.batched_executable(
        params, traces, spec.resolved_options().with_chunk(b.chunk_b),
        spec.core, b.banks, local_cond_devices=b.local_cond)


def builds(stats) -> tuple:
    """The counts of ``engine.compile_stats()`` that grow when an
    executable is built (its lowering time grows with any lowering)."""
    return stats.lru_misses, stats.xla_compiles, stats.cache_loads


def steps_run(out: dict, spec, b) -> int:
    """The fast cycles a call of bucket `b`'s program stepped."""
    chunks = int(np.max(np.asarray(out["chunks_run"])))
    return chunks * engine.effective_chunk(spec.resolved_options().horizon,
                                           b.chunk_b)


def _profile_options():
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    return po


def measure(run) -> Probe | None:
    stages = getattr(engine, "STAGE_SCOPES", None)
    if run.trace is None or stages is None or len(jax.devices()) != 1:
        return None
    window = spans.window_trace(run)
    if window is None:
        raise FileNotFoundError("the traced window left no trace")
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.dirname(window)))), "probe")
    shutil.rmtree(out_dir, ignore_errors=True)
    t0 = time.perf_counter()
    bucket_of, stepped = window_executables(run.window)
    spec = run.window.jobs[0].grid.spec
    before = engine.compile_stats()
    per_exe, record, nbytes = [], [], 0
    for i, (key, b) in enumerate(sorted(bucket_of.items(), key=str)):
        compiled, args = executable(spec, b)
        names = op_names(compiled.as_text())
        d = os.path.join(out_dir, str(i))
        os.makedirs(d)
        with open(os.path.join(d, "op_names.json"), "w") as f:
            json.dump(names, f)
        jax.profiler.start_trace(d, profiler_options=_profile_options())
        try:
            result = jax.block_until_ready(compiled(*args))
        finally:
            jax.profiler.stop_trace()
        steps = steps_run(result, spec, b)
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        nbytes += os.path.getsize(path)
        reduced = reduce_trace(path, names, stages, engine.LOOP_SCOPE)
        per_exe.append((reduced, steps, stepped.get(key, 0)))
        record.append({"steps": steps, "window_steps": stepped.get(key, 0),
                       "device_ns": sum(v[0] for v in reduced.values()),
                       "ops": sum(v[1] for v in reduced.values())})
    if builds(engine.compile_stats()) != builds(before):
        raise RuntimeError(f"the probe built an executable: "
                           f"{engine.compile_stats()} after {before}")
    probe = Probe(weighted(per_exe), time.perf_counter() - t0, nbytes)
    out = dataclasses.asdict(probe) | {"executables": record}
    with open(os.path.join(out_dir, "probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(f"probe: {json.dumps(out)}", file=sys.stderr, flush=True)
    return probe
