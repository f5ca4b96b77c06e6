"""SMLA simulator: paper Table 1/2 reproduction + dynamic invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis dev dependency")
import hypothesis.strategies as st

from repro.core.smla import energy as E
from repro.core.smla.analytic import compare_configs, table2, weighted_speedup
from repro.core.smla.config import IOModel, RankOrg, StackConfig, paper_configs
from repro.core.smla.engine import CoreParams, SimOptions, simulate
from repro.core.smla.traces import WORKLOADS, WorkloadSpec, core_traces

hypothesis.settings.register_profile("sim", max_examples=8, deadline=None)
hypothesis.settings.load_profile("sim")


# ----------------------------------------------------------------------------
# paper Table 2 (exact)
# ----------------------------------------------------------------------------

def test_table2_bandwidth():
    t2 = table2(layers=4)
    assert t2["baseline"]["bandwidth_gbps"] == pytest.approx(3.2)
    for k in ("dedicated_mlr", "dedicated_slr", "cascaded_mlr",
              "cascaded_slr"):
        assert t2[k]["bandwidth_gbps"] == pytest.approx(12.8)


def test_table2_transfer_times():
    t2 = table2(layers=4)
    assert t2["baseline"]["avg_transfer_ns"] == pytest.approx(20.0)
    assert t2["dedicated_mlr"]["avg_transfer_ns"] == pytest.approx(5.0)
    assert t2["dedicated_slr"]["avg_transfer_ns"] == pytest.approx(20.0)
    assert t2["cascaded_mlr"]["avg_transfer_ns"] == pytest.approx(5.0)
    # paper footnote: bottom 16.25 / 17.5 / 18.75 / top 20 -> avg 18.125
    assert t2["cascaded_slr"]["transfer_ns"] == pytest.approx(
        [16.25, 17.5, 18.75, 20.0])
    assert t2["cascaded_slr"]["avg_transfer_ns"] == pytest.approx(18.125)


def test_table2_ranks():
    t2 = table2(layers=4)
    assert t2["baseline"]["n_ranks"] == 4
    assert t2["dedicated_mlr"]["n_ranks"] == 1
    assert t2["cascaded_slr"]["n_ranks"] == 4


def test_layer_frequencies_cascaded():
    """§4.2.1: lower half at L*F, next quarter at L*F/2, top at F."""
    sc = StackConfig(layers=4, io_model=IOModel.CASCADED)
    assert [sc.layer_freq_mhz(i) for i in range(4)] == [800, 800, 400, 200]
    sc8 = StackConfig(layers=8, io_model=IOModel.CASCADED)
    assert [sc8.layer_freq_mhz(i) for i in range(8)] == \
        [1600] * 4 + [800, 800, 400, 200]


def test_table1_energy_model():
    """Calibration reproduces the paper's Table 1 exactly."""
    t1 = E.table1()
    assert t1["Precharge-Standby Current (mA)"] == [4.24, 5.39, 6.54, 8.84]
    assert t1["Active-Standby Current (mA)"] == [7.33, 8.50, 9.67, 12.0]
    assert t1["Active-Precharge wo Standby (nJ)"] == [1.36, 1.37, 1.38, 1.41]
    assert t1["Power-Down Current (mA)"] == [0.24] * 4
    assert t1["Read wo Standby (nJ)"] == [1.93] * 4


# ----------------------------------------------------------------------------
# dynamic simulator invariants
# ----------------------------------------------------------------------------

def _run(stack, specs, n_req=300, horizon=30_000, seed=0):
    traces = core_traces(seed, specs, n_req, stack.n_ranks,
                         stack.banks_per_rank)
    return simulate(stack, traces, SimOptions(horizon=horizon)), traces


@hypothesis.given(mpki=st.sampled_from([2.0, 10.0, 40.0]),
                  rowhit=st.sampled_from([0.2, 0.6, 0.9]),
                  seed=st.integers(0, 100))
def test_invariants_baseline(mpki, rowhit, seed):
    stack = paper_configs()["baseline"]
    specs = [WorkloadSpec("w", mpki, rowhit)] * 2
    m, traces = _run(stack, specs, seed=seed)
    served = np.asarray(m["served"])
    assert (served <= traces["inst"].shape[1]).all()        # no over-serving
    assert float(m["bandwidth_gbps"]) <= stack.peak_bandwidth_gbps + 1e-6
    assert 0.0 <= float(m["bus_util"]) <= 1.0 + 1e-6
    assert (np.asarray(m["ipc"]) >= 0).all()


def test_bandwidth_saturation_ratio():
    """Saturating streams: SMLA should deliver ~4x baseline bandwidth."""
    specs = [WorkloadSpec("stream", 200.0, 0.95)] * 4
    base, _ = _run(paper_configs()["baseline"], specs, n_req=2000,
                   horizon=50_000)
    cas, _ = _run(paper_configs()["cascaded_slr"], specs, n_req=2000,
                  horizon=50_000)
    ratio = float(cas["bandwidth_gbps"]) / float(base["bandwidth_gbps"])
    assert ratio > 3.0, ratio                     # 4x nominal, >3x measured
    assert float(base["bandwidth_gbps"]) <= 3.2 + 1e-6


def test_mlr_latency_vs_slr_parallelism():
    """Paper §5: MLR = lower transfer latency, SLR = more rank parallelism.
    Memory-intensive multiprogrammed mixes favour SLR."""
    specs = [WORKLOADS[i] for i in (20, 24, 27, 29)]
    res = compare_configs(specs, n_req=800, horizon=60_000)
    ws_slr = weighted_speedup(res["cascaded_slr"], res["baseline"])
    ws_mlr = weighted_speedup(res["cascaded_mlr"], res["baseline"])
    assert ws_slr > ws_mlr
    assert ws_slr > 1.2


def test_cascaded_beats_dedicated_energy():
    """§8.4: cascaded's tiered layer clocks -> lower standby energy."""
    specs = [WORKLOADS[i] for i in (18, 21, 26, 28)]
    res = compare_configs(specs, n_req=600, horizon=50_000)
    assert res["cascaded_slr"].standby_nj < res["dedicated_slr"].standby_nj
    assert res["cascaded_mlr"].standby_nj < res["dedicated_mlr"].standby_nj


def test_ops_energy_identical_across_ios():
    """Frequency-decoupled ACT/RD energy is IO-model independent (same
    work => same op counts within tolerance)."""
    specs = [WORKLOADS[5]] * 2
    res = compare_configs(specs, n_req=400, horizon=60_000)
    base = res["baseline"].ops_nj
    for k, r in res.items():
        assert abs(r.ops_nj - base) / base < 0.2, k


def test_fixed_work_completion():
    specs = [WorkloadSpec("w", 5.0, 0.5)] * 2
    stack = paper_configs()["cascaded_slr"]
    m, traces = _run(stack, specs, n_req=200, horizon=60_000)
    assert bool(np.asarray(m["complete"]).all())
    assert float(m["makespan_ns"]) < 60_000 * stack.unit_ns
