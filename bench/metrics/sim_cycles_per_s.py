"""Simulated fast cycles per second of the window: every cell of every
job the window ran contributes its own cycles to completion, over the
host seconds from the window's start to the last job's return."""
from bench.lib.account import needed_cycles


def read(run):
    if not run.window.buckets:
        return None
    cycles = sum(needed_cycles(run, b) for b in run.window.buckets)
    return cycles / run.window.elapsed
