"""Microseconds of program execution per fast cycle a device stepped:
the devices' execution time over the traced window (bounded by the TPU
runtime's host events, ``bench/lib/trace.py``), summed over devices,
over the fast cycles each device stepped for every bucket the window's
jobs ran, summed likewise (``account.stepped_cycles``).  Nothing is
read from a trace that holds no execution."""
from bench.lib.account import stepped_cycles


def read(run):
    if run.trace is None or not sum(run.trace.busy_s):
        return None
    return sum(run.trace.busy_s) / stepped_cycles(run) * 1e6
