"""Share of the lane-cycles the devices stepped for the window's buckets
that no cell needed: 1 - (cells' own cycles to completion) / (each
loop's rows x its chunks run x chunk width, pad rows included).  Loops
wait for their slowest row and exit on chunk boundaries; both show
here."""
from bench.lib.account import needed_cycles, stepped_lane_cycles


def read(run):
    buckets = run.window.buckets
    if not buckets:
        return None
    stepped = sum(stepped_lane_cycles(run, b) for b in buckets)
    return 1.0 - sum(needed_cycles(run, b) for b in buckets) / stepped
