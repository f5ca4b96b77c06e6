"""Device op events per fast cycle stepped, over every scope, from the
stage probe's op-level trace of one chunk per executable
(``bench/lib/probe.py``): the compiled scan body's kernels plus the chunk
loop's, spread over the chunk."""
from bench.lib import probe


def read(run):
    p = probe.of(run)
    return None if p is None else p.ops_per_step
