"""Seconds the dispatching thread waited on the sweep's producer thread
for a bucket's padded arrays (``smla.wait_prepare`` spans in the traced
window, clipped to it; ``bench/lib/spans.py``)."""
from bench.lib import spans


def read(run):
    path = spans.window_trace(run)
    return None if path is None else spans.wait_s(spans.host_spans(path))
