"""Microseconds of program execution per fast cycle the chip stepped:
the traced window's execution time (bounded by the TPU runtime's host
events, ``bench/lib/trace.py``) over the fast cycles of every bucket the
window's jobs ran.  Nothing is read from a trace that holds no
execution."""
from bench.lib.account import stepped_cycles


def read(run):
    if run.trace is None or not run.trace.busy_s[0]:
        return None
    return run.trace.busy_s[0] / stepped_cycles(run) * 1e6
