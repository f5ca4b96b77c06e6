"""Reduction of a profiler trace of the window to execution times.

``extract`` keeps what the reduction needs from the ``.xplane.pb`` the
profiler writes: the intervals in which the chip held a program
execution, and the harness's own host spans (``bench.*``
``TraceAnnotation``s around each job, its ``run_sweep`` call and each
bucket's completion; set-up precedes the traced window).

The TPU's op-level trace cannot hold a window: every HLO op of every
fast cycle is an event (6.3 M events, 305 MB and 154 s to stop the
profiler for one 3.5 s bucket on a TPU v5e), its buffers overflow within
two seconds, and past the overflow even the program-level "XLA Modules"
events end early.  The traced run therefore records the host only
(``PROFILE_MODE``), and an execution is bounded by the TPU runtime's own
host events: from its issue (``EXEC_START``) to the runtime's read of
its completion flag (``EXEC_END``), and no earlier than the previous
execution's end, since the chip runs one program at a time.  These are
host timestamps: an execution's interval holds the device's run and the
runtime's latency to notice its end, so the busy time reads high, never
low.

**Which device.**  The issue and the completion-flag read carry no
device themselves; the events beside them do, by their
``device_ordinal`` stat.  An issue holds the runtime's
``DoEnqueueProgram`` on its host line, and a read is followed on its
line by the ``CompleteCallbacks`` of the execution it saw end.  Both
name the same ordinal as the ``core_id`` of ``tpu::System::Execute``:
in a host-only trace of a tiny ``smla4-mp16-x4`` run on a TPU v5e-4,
each device's completions sit on a thread of their own while one
issuing thread serves several devices, every ``DoEnqueueProgram`` and
``CompleteCallbacks`` event carries its device's ordinal, and each
execution's enqueue and callbacks carry one flow id, so that issue and
completion pair up on one device
(``bench/tests/data/smla4-mp16-x4-tiny.*``).  On one chip the same
events carry ordinal 0 (``smla4-mp16-tiny.*``, a TPU v5e), so one
chip is reduced the same way.  ``extract`` builds each device's
executions from its own events alone; an execution event that names no
device of the run is an error.  ``reduce`` gives:

* ``window_s``: from the start of the first ``bench.job`` span to the end
  of the last;
* ``busy_s``: per device, the length of the union of its execution
  intervals inside the window;
* ``device_ops``: the programs that took most execution time;
* ``idle_gaps``: the longest stretches inside the window in which a
  device held no execution, over every device, each named by the
  innermost harness span that was open at its middle (what the host was
  doing).
"""
from __future__ import annotations

import dataclasses
import glob
import os

#: TPU trace mode of the traced run: host events only
PROFILE_MODE = "TRACE_ONLY_HOST"
#: the TPU runtime's host events that open and close an execution
EXEC_START = "tpu::System::Execute=>IssueSequencedEvent"
EXEC_END = "ReadSyncFlag"
EXEC_NAME = "program execution"
#: the runtime's events that name the device of an issue (the enqueue
#: inside it) and of a completion (the callbacks after it), by this stat
ENQUEUE_DEVICE = "DoEnqueueProgram"
END_DEVICE = "CompleteCallbacks"
ORDINAL = "device_ordinal"
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: list[float]
    device_ops: list[list]
    idle_gaps: list[list]


def profile_options():
    import jax
    po = jax.profiler.ProfileOptions()
    po.advanced_configuration = {"tpu_trace_mode": PROFILE_MODE}
    return po


def xplane_path(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def executions(starts: list[float], ends: list[float]) -> list[list]:
    """Execution intervals from the runtime's issue and completion times:
    execution k ends at the first completion after its issue that no
    earlier execution took, and starts no earlier than the previous
    execution's end."""
    out, ends = [], sorted(ends)
    j, prev_end = 0, float("-inf")
    for s in sorted(starts):
        while j < len(ends) and ends[j] < s:
            j += 1
        if j == len(ends):
            break
        begin = max(s, prev_end)
        out.append([EXEC_NAME, begin, ends[j] - begin])
        prev_end = ends[j]
        j += 1
    return out


def _ordinal(event) -> int | None:
    for k, v in event.stats:
        if k == ORDINAL:
            return int(v)
    return None


def _device_of(events: list, i: int) -> int | None:
    """The device of execution event ``events[i]`` on a host line sorted
    by start, parents first: an issue is its enqueue's device, a
    completion-flag read that of the callbacks that follow it on the
    line, before the next read."""
    e = events[i]
    if e.name == EXEC_START:
        end = e.start_ns + e.duration_ns
        for f in events[i + 1:]:
            if f.start_ns > end:
                break
            if f.name == ENQUEUE_DEVICE:
                return _ordinal(f)
    else:
        for f in events[i + 1:]:
            if f.name == EXEC_END:
                break
            if f.name == END_DEVICE:
                return _ordinal(f)
    return None


def extract(path: str, n_devices: int) -> dict:
    """``{"devices": [[[name, start_ns, dur_ns], ...], ...], "spans":
    [[name, start_ns, dur_ns], ...]}`` from one ``.xplane.pb`` of a run
    on `n_devices` chips: one execution list per device, each built by
    `executions` from that device's events alone."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    starts = [[] for _ in range(n_devices)]
    ends = [[] for _ in range(n_devices)]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            events = sorted(ln.events,
                            key=lambda e: (e.start_ns, -e.duration_ns))
            for i, e in enumerate(events):
                if e.name.startswith(SPAN_PREFIX):
                    spans.append([e.name, e.start_ns, e.duration_ns])
                if e.name not in (EXEC_START, EXEC_END):
                    continue
                dev = _device_of(events, i)
                if dev is None or not 0 <= dev < n_devices:
                    raise ValueError(f"{e.name} at {e.start_ns} ns names no "
                                     f"device of {n_devices} (got {dev})")
                if e.name == EXEC_START:
                    starts[dev].append(e.start_ns)
                else:
                    ends[dev].append(e.start_ns + e.duration_ns)
    return {"devices": [executions(s, e) for s, e in zip(starts, ends)],
            "spans": spans}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _span_at(spans, t: float) -> str:
    """The innermost (shortest) harness span open at `t`."""
    open_ = [(d, n) for n, s, d in spans if s <= t <= s + d]
    return min(open_)[1] if open_ else "outside any span"


def reduce(ex: dict) -> Summary:
    jobs = [(s, s + d) for n, s, d in ex["spans"] if n == "bench.job"]
    if not jobs or not ex["devices"]:
        raise ValueError("trace holds no bench.job span or no device")
    lo, hi = min(s for s, _ in jobs), max(e for _, e in jobs)
    busy, per_op, gaps = [], {}, []
    for events in ex["devices"]:
        iv = _union(_clip([(s, s + d) for _, s, d in events], lo, hi))
        busy.append(sum(e - s for s, e in iv) / 1e9)
        for name, s, d in events:
            c = _clip([(s, s + d)], lo, hi)
            if c:
                per_op[name] = per_op.get(name, 0.0) + (c[0][1] - c[0][0])
        edges = [lo] + [x for se in iv for x in se] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append([_span_at(ex["spans"], (a + b) / 2),
                             (b - a) / 1e9])
    ops = sorted(([n, t / 1e9] for n, t in per_op.items()),
                 key=lambda x: -x[1])[:TOP]
    gaps = sorted(gaps, key=lambda x: -x[1])[:TOP]
    return Summary((hi - lo) / 1e9, busy, ops, gaps)
