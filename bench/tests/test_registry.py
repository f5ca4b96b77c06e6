"""The harness finds each configuration, traffic mix and metric by the
name BENCHMARK.json gives it, so that new ones are new files."""
import json
import shutil
import sys
import types

import numpy as np
import pytest

from bench.lib import registry


def test_every_named_piece_has_its_file():
    bm = registry.benchmark()
    for wl in bm["workloads"]:
        cfg = registry.config(bm, wl["config"])
        assert cfg["name"] == wl["config"]
        assert registry.traffic(wl["traffic"])["n_req"] > 0
        for trace in (False, True):
            for m in registry.metrics(bm, trace):
                assert callable(registry.reader(m["name"]))


def test_each_cell_reports_both_kinds_of_metric():
    bm = registry.benchmark()
    for wl in bm["workloads"]:
        e2e = {m["name"] for m in registry.metrics(bm, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.metrics(bm, True)


def test_new_pieces_are_found_by_name(tmp_path):
    """A copy of bench/ with one more traffic mix and one more metric:
    both are found without touching any existing file."""
    bench = tmp_path / "bench"
    shutil.copytree(registry.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "traffic" / "extra.json").write_text(json.dumps(
        {"n_req": 7, "horizon": 1024, "mixes": [["low.01"]]}))
    (bench / "metrics" / "extra_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    assert registry.traffic("extra", str(bench))["n_req"] == 7
    assert registry.reader("extra_metric", str(bench))(None) == 42.0


@pytest.mark.parametrize("name", ["smla-4layer", "smla-8layer"])
def test_a_configuration_without_a_reference_key_runs_the_default(name):
    from bench.reference import controller, params
    bm = registry.benchmark()
    cfg = registry.config(bm, name)
    assert "reference" not in cfg
    ref = registry.reference(cfg)
    assert ref.channel is params.channel
    assert ref.simulate_many is controller.simulate_many
    assert ref.n_ranks is params.n_ranks
    assert ref.unit_ns is params.unit_ns


def test_a_configuration_is_checked_by_the_reference_it_names(
        tiny, one_job, monkeypatch):
    """A whole run of a cell whose configuration names a stub reference:
    the generator, the accounting and the check call the stub's four
    functions, never the default reference's, and its answers decide
    ``correct``."""
    from bench.lib import check
    from bench.reference import controller, params
    from bench.tests.conftest import run_cell
    calls = {k: 0 for k in ("channel", "simulate_many", "n_ranks",
                            "unit_ns")}

    def counted(name, fn):
        def call(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return call

    def simulate_many(cells, fdtype=np.float32):
        calls["simulate_many"] += 1
        out = [controller.simulate(*c, fdtype) for c in cells]
        for o in out:
            o["n_act"] = o["n_act"] + stub.bump
        return out

    stub = types.ModuleType("bench.reference.stub_reference")
    stub.channel = counted("channel", params.channel)
    stub.n_ranks = counted("n_ranks", params.n_ranks)
    stub.unit_ns = counted("unit_ns", params.unit_ns)
    stub.simulate_many = simulate_many
    stub.bump = 0
    monkeypatch.setitem(sys.modules, stub.__name__, stub)

    def forbidden(*args, **kwargs):
        raise AssertionError("the default reference was called")
    monkeypatch.setattr(controller, "simulate_many", forbidden)
    real_config = registry.config
    monkeypatch.setattr(registry, "config",
                        lambda bm, name, root=registry.ROOT:
                        dict(real_config(bm, name, root),
                             reference="stub_reference"))
    monkeypatch.setattr(check, "SAMPLE", 3)

    out = run_cell("smla4-mp16")
    assert out["correct"], out["checks"]
    assert out["checks"]["cells_checked"]["value"] == 3
    assert calls["simulate_many"] == 1 and calls["channel"] == 3
    assert calls["n_ranks"] > 0 and calls["unit_ns"] > 0

    stub.bump = 1
    out = run_cell("smla4-mp16")
    assert not out["correct"]
    assert out["checks"]["int_mismatch"]["value"] == 3
