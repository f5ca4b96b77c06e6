"""The control of the comparison: the reference put in the program's
place, with its instruction counts (``inst``, ``c_inst``, ``qinst``) in
bfloat16, the precision below the float32 the configuration states.  At
a tiny size it must fail the checks that the float32 reference passes.
(At the cells' own size ``bench/control.py`` runs it; PERF.md gives its
readings.)"""
import ml_dtypes
import pytest

from bench.lib import check, gen, registry
from bench.reference import controller
from bench.tests.conftest import tiny_traffic


@pytest.mark.parametrize("cell", ["smla4-mp16", "smla8-policy"])
def test_bfloat16_control_fails(cell):
    bm = registry.benchmark()
    wl = registry.workload(bm, cell)
    cfg = registry.config(bm, wl["config"])
    traffic = tiny_traffic(registry.traffic(wl["traffic"]))
    job = gen.make_job(cfg, traffic, 2**31 + 99, 0)
    cells = [check.reference_cell(cfg, traffic, c, p)
             for c, p in job.expanded()[::7]]
    want = controller.simulate_many(cells)
    control = controller.simulate_many(cells, ml_dtypes.bfloat16)
    n_int, gap = 0, 0.0
    for w, c in zip(want, control):
        assert check.compare(w, w) == (0, 0.0)
        i, g = check.compare(c, w)
        n_int, gap = n_int + i, max(gap, g)
    assert n_int > 0 and gap > check.FLOAT_LIMIT
