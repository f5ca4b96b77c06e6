"""Device microseconds per fast cycle stepped in ops in no stage scope: the
chunk loop's control (`smla.loop`) and the copies XLA inserts outside
any scoped op, from the stage probe's op-level trace of one chunk per
executable (``bench/lib/probe.py``)."""
from bench.lib import probe


def read(run):
    p = probe.of(run)
    return None if p is None else p.stage("unscoped")[1]
