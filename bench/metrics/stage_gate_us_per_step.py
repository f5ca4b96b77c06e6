"""Device microseconds per fast cycle stepped in the live-step gate
(`smla.gate`), which keeps a step past the horizon from changing the
state, from the stage probe's op-level trace of one chunk per executable
(``bench/lib/probe.py``)."""
from bench.lib import probe


def read(run):
    p = probe.of(run)
    return None if p is None else p.stage("gate")[1]
