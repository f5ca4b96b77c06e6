"""Share of the lane-cycles the device stepped for the window's buckets
that no cell needed: 1 - (cells' own cycles to completion) / (rows x
chunks run x chunk width, pad rows included).  Buckets wait for their
slowest cell and exit on chunk boundaries; both show here."""
from bench.lib.account import needed_cycles, stepped_lane_cycles


def read(run):
    buckets = run.window.buckets
    if not buckets:
        return None
    stepped = sum(stepped_lane_cycles(b) for b in buckets)
    return 1.0 - sum(needed_cycles(run, b) for b in buckets) / stepped
