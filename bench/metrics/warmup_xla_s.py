"""Seconds XLA spent compiling the engine's executables or loading them
from the persistent cache, as ``engine.compile_stats()`` totals them
when the reader runs: the warm-up's, as the window builds none
(``window_compiles``), the reference check calls no engine and the
stage probe builds none (an error, ``bench/lib/probe.py``)."""
from repro.core.smla import engine


def read(run):
    stats = getattr(engine, "compile_stats", None)
    if stats is None:
        return None
    s = stats()
    return s.compile_s + s.load_s
