"""Finds every piece of the benchmark by the name ``BENCHMARK.json`` gives
it, so that a new configuration, traffic mix or metric is a new file and
a new entry, never an edit:

* a configuration: the ``file`` of its ``configs`` entry;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric (end-to-end or per-layer): ``bench/metrics/<name>.py``, whose
  ``read(run)`` returns the number, or None where the run holds nothing
  to read it from.
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    return _by_name(bm["workloads"], name, "workload")


def config(bm: dict, name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(
        root, _by_name(bm["configs"], name, "configuration")["file"]))


def traffic(name: str, bench: str = BENCH) -> dict:
    return _load_json(os.path.join(bench, "traffic", f"{name}.json"))


def metrics(bm: dict, trace: bool) -> list[dict]:
    """The metrics a run reports: the end-to-end metrics untraced, the
    per-layer metrics traced."""
    return bm["per_layer"] if trace else bm["end_to_end"]


def reader(name: str, bench: str = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
