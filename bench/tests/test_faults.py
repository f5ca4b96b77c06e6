"""Each fault a cell can have, planted under a whole run at a tiny size,
turns ``correct`` false.  The runs skip only the look for a chip.  The
four-chip cell's faults run in a process of their own that sees four
host devices, so that its buckets take the sharded path."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.lib import check
from bench.tests.conftest import run_cell
from repro.core.smla import engine

CELLS = ("smla4-mp16", "smla8-policy")


@pytest.fixture
def every_cell_checked(monkeypatch):
    """Compare every finished cell, so that a fault in any row shows."""
    monkeypatch.setattr(check, "SAMPLE", 10**6)


@pytest.fixture
def fresh_executables():
    """Executables built around a planted fault must not outlive it."""
    engine._compiled.cache_clear()
    yield
    engine._compiled.cache_clear()


def _wrap_outputs(monkeypatch, change):
    real = engine.batched_simulate

    def broken(*args, **kwargs):
        out = {k: np.array(v) for k, v in real(*args, **kwargs).items()}
        return change(out)
    monkeypatch.setattr(engine, "batched_simulate", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tiny, one_job, every_cell_checked):
    out = run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["cells_checked"]["value"] == out["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(cell, tiny, one_job,
                                               every_cell_checked,
                                               fresh_executables,
                                               monkeypatch):
    monkeypatch.setattr(engine, "_STAGES", ())
    out = run_cell(cell)
    assert not out["correct"]
    assert out["checks"]["failed_cells"]["value"] == out["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out(cell, tiny, one_job, every_cell_checked,
                                 monkeypatch):
    def half(out):
        n = len(out["served"])
        keep = max(n // 2, 1)
        for k, v in out.items():
            mean = v[:keep].mean(axis=0)
            v[keep:] = mean.astype(v.dtype) if v.dtype != bool else mean > .5
        return out
    _wrap_outputs(monkeypatch, half)
    out = run_cell(cell)
    assert not out["correct"]
    assert out["checks"]["int_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(cell, tiny, one_job,
                                       every_cell_checked, monkeypatch):
    def bump(out):
        out["n_act"] = out["n_act"] + 1
        return out
    _wrap_outputs(monkeypatch, bump)
    out = run_cell(cell)
    assert not out["correct"]
    assert out["checks"]["int_mismatch"]["value"] == out["attempted"]


#: the faults between chips, planted under a whole run of the four-chip
#: cell on four host devices: each case's result line, by case
_X4_SCRIPT = '''
import json, sys, types

def main():
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    import numpy as np
    import pytest
    from bench.lib import account, check, window
    from bench.tests import conftest
    from repro.core.smla import engine

    last = jax.devices()[-1]

    def last_device_rows(v):
        return [s.index[0] for s in v.addressable_shards if s.device == last]

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        rows, = {{tuple(range(*sl.indices(len(v))))
                 for v in out.values() for sl in last_device_rows(v)}}
        n = len(out["n_act"])
        assert rows == tuple(range(n - n // 4, n)), rows
        host = {{k: np.array(v) for k, v in out.items()}}
        host["n_act"][list(rows)] += 1
        return host

    class LostShard:
        """An output whose last device's shard never arrives."""
        def __init__(self, v):
            self.v = v
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError(f"device {{last.id}} lost its shard of "
                               f"rows {{last_device_rows(self.v)}}")

    calls = [0]

    def failed_on_last(*args, **kwargs):
        out = real(*args, **kwargs)
        calls[0] += 1
        return ({{k: LostShard(v) for k, v in out.items()}}
                if calls[0] == 2 else out)

    loops = []

    def each_devices_loop(*args, **kwargs):
        """Sound; records the chunks each device's loop ran, its rows'
        largest ``chunks_run``, read from the device's own shard."""
        out = real(*args, **kwargs)
        got = {{s.device.id: int(np.max(np.asarray(s.data)))
               for s in out["chunks_run"].addressable_shards}}
        loops.append([got[d.id] for d in jax.devices()])
        return out

    windows = []

    def keep_window(*args, **kwargs):
        windows.append(real_window(*args, **kwargs))
        return windows[-1]

    real = engine.batched_simulate
    real_window = window.run
    for case, fault in (("sound", each_devices_loop), ("altered", altered),
                        ("failed", failed_on_last)):
        mp = pytest.MonkeyPatch()
        conftest.patch_tiny(mp)
        conftest.patch_one_job(mp)
        mp.setattr(check, "SAMPLE", 10**6)
        mp.setattr(engine, "batched_simulate", fault)
        mp.setattr(window, "run", keep_window)
        out = conftest.run_cell("smla4-mp16-x4")
        mp.undo()
        if case == "sound":
            run = types.SimpleNamespace(window=windows[-1])
            buckets = run.window.buckets
            out["steps"] = {{
                "layout": [[b.devices, b.loops] for b in buckets],
                "account": [account.loop_chunks(run, b) for b in buckets],
                "devices": loops[-len(buckets):]}}
        print(json.dumps(dict(out, case=case)), flush=True)

if __name__ == "__main__":
    main()
'''


@pytest.fixture(scope="module")
def four_chip_runs(tmp_path_factory):
    """A tiny run of ``smla4-mp16-x4`` on four host devices through the
    sharded path, sound and with each fault; ``{case: result}``."""
    root = Path(__file__).resolve().parents[2]
    script = tmp_path_factory.mktemp("x4") / "faults_x4.py"
    script.write_text(_X4_SCRIPT.format(root=str(root),
                                        src=str(root / "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    runs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    return {run["case"]: run for run in runs}


def test_sound_run_on_four_chips_is_correct(four_chip_runs):
    out = four_chip_runs["sound"]
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    assert out["checks"]["cells_checked"]["value"] == out["attempted"]


def test_each_chip_steps_its_own_rows(four_chip_runs):
    """The work accounting's loop per chip, from the bucket records and
    the cells' results, is what each chip's own shard of the output
    says its loop ran."""
    steps = four_chip_runs["sound"]["steps"]
    assert steps["layout"] == [[4, 4]] * len(steps["account"])
    assert steps["account"] == steps["devices"]


def test_answer_altered_on_the_last_chip(four_chip_runs):
    """Only the rows the last device holds are altered: one a bucket."""
    out = four_chip_runs["altered"]
    assert not out["correct"]
    assert out["checks"]["int_mismatch"]["value"] == out["window"]["buckets"]
    assert out["checks"]["failed_cells"]["value"] == 0


def test_bucket_failed_on_the_last_chip(four_chip_runs):
    out = four_chip_runs["failed"]
    assert not out["correct"]
    assert out["window"]["buckets"] == four_chip_runs["sound"]["window"][
        "buckets"] - 1
    assert 0 < out["checks"]["failed_cells"]["value"] < out["attempted"]
