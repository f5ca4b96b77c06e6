"""Device microseconds per fast cycle stepped in stage `_stage_schedule`
(`smla.schedule`), from the stage probe's op-level trace of one chunk
per executable (``bench/lib/probe.py``)."""
from bench.lib import probe


def read(run):
    p = probe.of(run)
    return None if p is None else p.stage("schedule")[1]
