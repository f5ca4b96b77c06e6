"""The harness finds each configuration, traffic mix and metric by the
name BENCHMARK.json gives it, so that new ones are new files."""
import json
import shutil

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
