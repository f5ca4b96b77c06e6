"""Executables of the engine XLA compiled (persistent-cache misses), as
``engine.compile_stats()`` totals them when the reader runs: the
warm-up's, as the window builds none (``window_compiles``), the
reference check calls no engine and the stage probe builds none (an
error, ``bench/lib/probe.py``).  0 on a warm ``.jax_cache/``."""
from repro.core.smla import engine


def read(run):
    stats = getattr(engine, "compile_stats", None)
    return None if stats is None else stats().xla_compiles
