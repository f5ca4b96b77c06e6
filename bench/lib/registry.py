"""Finds every piece of the benchmark by the name ``BENCHMARK.json`` gives
it, so that a new configuration, traffic mix or metric is a new file and
a new entry, never an edit:

* a configuration: the ``file`` of its ``configs`` entry;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric (end-to-end or per-layer): ``bench/metrics/<name>.py``, whose
  ``read(run)`` returns the number, or None where the run holds nothing
  to read it from;
* a configuration's reference: the module ``bench/reference/<module>.py``
  that its file names under ``"reference"``; a file without the key is
  checked by ``bench/reference/params.py`` and ``controller.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
from typing import Callable

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


@dataclasses.dataclass(frozen=True)
class Reference:
    """What the harness takes from a configuration's plain reference:

    * ``channel(stack, org, policy, n_req)``: one cell's channel, from the
      configuration's ``stack`` block, one of its ``organisations`` and a
      map from policy axis to value name;
    * ``simulate_many(cells, fdtype=numpy.float32)``: each ``(channel,
      traces, core, horizon)`` cell's statistics under the program's
      names, with its instruction counts in `fdtype` (the control runs
      the precision below the configuration's);
    * ``n_ranks(stack, org)``: the ranks an organisation has, which the
      traffic generator draws addresses over;
    * ``unit_ns(stack)``: one fast cycle in ns, the unit of the work
      accounting.
    """
    channel: Callable
    simulate_many: Callable
    n_ranks: Callable
    unit_ns: Callable


def reference(config: dict) -> Reference:
    """The reference of a configuration: the module under
    ``bench/reference/`` its ``"reference"`` key names, which provides
    the four functions of `Reference`; without the key, ``params.py``'s
    ``channel``, ``n_ranks`` and ``unit_ns`` and ``controller.py``'s
    ``simulate_many``.  A reference imports nothing of the program."""
    name = config.get("reference")
    if name is None:
        from bench.reference import controller, params
        return Reference(params.channel, controller.simulate_many,
                         params.n_ranks, params.unit_ns)
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise ValueError(f"reference {name!r} is not a module name under "
                         f"bench/reference/")
    mod = importlib.import_module(f"bench.reference.{name}")
    return Reference(mod.channel, mod.simulate_many, mod.n_ranks,
                     mod.unit_ns)
