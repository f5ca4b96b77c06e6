"""Fault-injection & graceful-degradation invariants.

The load-bearing contracts:

* zero-fault bit-identity — a clean `FaultConfig` under ANY degradation
  mode lowers to exactly the historical params and metrics (the golden
  grid stays valid unregenerated);
* the fault x degradation cross-product is traced data: after the first
  compile, sweeping it adds ZERO compiles;
* bandwidth is monotone non-increasing in nested kill-sets under RETIME;
* weak-retention ranks refresh more, transient-error rates price ECC
  re-reads into bus time and read energy;
* `analytic.estimate_service_cycles` stays a true upper bound under
  every fault preset;
* eager construction-time validation raises clear ValueErrors instead of
  letting bad configs reach the tracer.

Shapes are deliberately reused across cases (fixed n_cores/n_req/
horizon; `to_params` always pads to the PHYSICAL rank count) so the
module costs a handful of XLA compiles.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.smla import analytic, engine
from repro.core.smla import energy as E
from repro.core.smla.config import StackConfig, paper_configs
from repro.core.smla.engine import SimOptions, simulate
from repro.core.smla.faults import (ECC_OFF, RETENTION_DERATES, DegradeMode,
                                    FaultConfig)
from repro.core.smla.traces import WorkloadSpec, core_traces

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
    _PROP_SETTINGS = hypothesis.settings(max_examples=20, deadline=None)
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

HORIZON = 3_000
N_REQ = 40
SEED = 11
STREAM = WorkloadSpec("stream.t", 50.0, 0.85, write_frac=1 / 3)


def _traces(sc: StackConfig, seed: int = SEED):
    return core_traces(seed, [STREAM, STREAM], N_REQ, sc.n_ranks,
                       sc.banks_per_rank)


def _with_faults(sc: StackConfig, **kw) -> StackConfig:
    return dataclasses.replace(sc, faults=FaultConfig(**kw))


# ---------------------------------------------------------------------------
# zero-fault bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(DegradeMode))
def test_clean_fault_params_bit_identical(mode):
    """A clean FaultConfig under any degrade mode lowers to the exact
    historical params — only the provenance selector differs."""
    for cname, sc in paper_configs(4).items():
        scf = _with_faults(sc, degrade=mode)
        p0, pf = sc.to_params(), scf.to_params()
        assert sorted(p0) == sorted(pf), cname
        for k in p0:
            if k == "degrade_sel":
                continue
            assert np.array_equal(np.asarray(p0[k]), np.asarray(pf[k])), \
                f"{cname}:{k}"
        assert int(pf["degrade_sel"]) == int(mode)
        assert int(pf["ecc_every"]) == int(ECC_OFF)


@pytest.mark.parametrize("mode", list(DegradeMode))
def test_clean_fault_metrics_bit_identical(mode):
    sc = paper_configs(4)["cascaded_slr"]
    tr = _traces(sc)
    m0 = simulate(sc, tr, SimOptions(horizon=HORIZON))
    mf = simulate(_with_faults(sc, degrade=mode), tr,
                  SimOptions(horizon=HORIZON))
    for k in m0:
        if k == "degrade_sel":
            continue
        assert np.array_equal(np.asarray(m0[k]), np.asarray(mf[k])), k


def test_legacy_params_without_fault_keys_are_inert():
    """A params dict predating the fault axes (no ref_derate/ecc_every/
    degrade_sel) must reproduce the clean engine exactly."""
    sc = paper_configs(4)["cascaded_slr"]
    tr = _traces(sc)
    p = sc.to_params()
    p["n_req"] = np.int32(tr["inst"].shape[1])
    legacy = {k: v for k, v in p.items()
              if k not in ("ref_derate", "ecc_every", "degrade_sel")}
    stack1 = {k: np.stack([v]) for k, v in p.items()}
    stack2 = {k: np.stack([v]) for k, v in legacy.items()}
    tb = {k: np.stack([v]) for k, v in tr.items()}
    opts = SimOptions(horizon=HORIZON)
    m1 = engine.batched_simulate(stack1, tb, opts, engine.CoreParams(),
                                 sc.banks_per_rank)
    m2 = engine.batched_simulate(stack2, tb, opts, engine.CoreParams(),
                                 sc.banks_per_rank)
    for k in m1:
        if k == "degrade_sel":
            continue
        assert np.array_equal(np.asarray(m1[k]), np.asarray(m2[k])), k


# ---------------------------------------------------------------------------
# degradation behaviour
# ---------------------------------------------------------------------------

def test_bandwidth_monotone_in_killed_layers():
    """Nested kill-sets under RETIME: more dead layers never raises
    bandwidth (the graceful slope is a slope, not a scatter).

    The chain uses survivor counts that DIVIDE the physical rank count
    (4 -> 2 -> 1): traffic addressed to a dead rank folds onto survivors
    mod R, so a non-divisor count (e.g. 3) folds unevenly — a double-
    loaded survivor can make the 3-rank stack slower than the balanced
    2-rank one on a locality-heavy stream, which is load imbalance, not
    a degradation-model violation."""
    for cname in ("cascaded_slr", "dedicated_slr", "cascaded_mlr"):
        sc = paper_configs(4)[cname]
        tr = _traces(sc)
        bws = []
        for kills in ((), (2, 3), (1, 2, 3)):
            m = simulate(_with_faults(sc, dead_layers=kills), tr,
                         SimOptions(horizon=HORIZON))
            assert np.asarray(m["complete"]).all(), (cname, kills)
            bws.append(float(m["bandwidth_gbps"]))
        for a, b in zip(bws, bws[1:]):
            assert b <= a * (1 + 1e-6), f"{cname}: {bws}"


def test_remap_non_divisor_fold_is_mod_r_and_uneven():
    """The documented-but-previously-unasserted REMAP fold imbalance,
    pinned: with a NON-DIVISOR survivor count (4 physical ranks, one
    dead layer -> R=3) traffic to dead ranks folds onto survivors
    exactly mod R, so survivor 0 absorbs rank 3's traffic while ranks 1
    and 2 keep only their own.

    Two observables:
    * the fold is literally ``rank % R`` — pre-folding the trace by hand
      is bit-identical to letting the engine fold it (idempotence pins
      the formula, not just 'some remapping happened');
    * the imbalance is real and costs time — a core whose traffic lands
      on the double-loaded survivor finishes strictly later than the
      same traffic aimed at an un-doubled survivor, all else equal."""
    sc = paper_configs(4)["dedicated_slr"]            # per-layer TSV groups
    scf = _with_faults(sc, dead_layers=(3,), degrade=DegradeMode.REMAP)

    # (a) idempotence: engine fold == hand fold, every metric
    tr = _traces(sc)
    pre = dict(tr, rank=(tr["rank"] % 3).astype(tr["rank"].dtype))
    m_raw = simulate(scf, tr, SimOptions(horizon=HORIZON))
    m_pre = simulate(scf, pre, SimOptions(horizon=HORIZON))
    assert int(np.asarray(tr["rank"]).max()) == 3     # fold engages
    for k in m_raw:
        assert np.array_equal(np.asarray(m_raw[k]),
                              np.asarray(m_pre[k])), k

    # (b) uneven loading: core0 hammers rank 0; core1's traffic either
    # folds ONTO rank 0 (addressed to dead rank 3 -> 3 % 3 == 0, the
    # double-loaded survivor) or goes to idle rank 1.  Same request
    # stream otherwise; the collision case must be strictly slower.
    n = 24
    base = {"inst": np.zeros((2, n), np.float32),
            "rank": np.zeros((2, n), np.int32),
            "bank": np.tile(np.arange(n, dtype=np.int32) % 2, (2, 1)),
            "row": np.tile(np.arange(n, dtype=np.int32), (2, 1)),
            "wr": np.zeros((2, n), np.int32)}
    collide = {k: v.copy() for k, v in base.items()}
    collide["rank"][1, :] = 3                         # folds onto rank 0
    spread = {k: v.copy() for k, v in base.items()}
    spread["rank"][1, :] = 1                          # its own survivor
    m_c = simulate(scf, collide, SimOptions(horizon=HORIZON))
    m_s = simulate(scf, spread, SimOptions(horizon=HORIZON))
    assert np.asarray(m_c["complete"]).all()
    assert np.asarray(m_s["complete"]).all()
    assert float(m_c["makespan_ns"]) > float(m_s["makespan_ns"]), \
        "mod-R double-loading stopped costing time — fold model changed"


def test_stuck_group_degrades_like_dead_layer():
    """A stuck TSV group removes its layer from service exactly like a
    dead die (the energy model, not the timing model, distinguishes
    them)."""
    sc = paper_configs(4)["cascaded_slr"]
    tr = _traces(sc)
    m_dead = simulate(_with_faults(sc, dead_layers=(3,)), tr,
                      SimOptions(horizon=HORIZON))
    m_stuck = simulate(_with_faults(sc, stuck_groups=(3,)), tr,
                       SimOptions(horizon=HORIZON))
    for k in m_dead:
        assert np.array_equal(np.asarray(m_dead[k]),
                              np.asarray(m_stuck[k])), k


def test_retime_beats_collapse():
    sc = paper_configs(4)["cascaded_slr"]
    tr = _traces(sc)
    bw = {}
    for mode in (DegradeMode.RETIME, DegradeMode.COLLAPSE):
        m = simulate(_with_faults(sc, dead_layers=(3,), degrade=mode), tr,
                     SimOptions(horizon=HORIZON))
        assert np.asarray(m["complete"]).all()
        bw[mode] = float(m["bandwidth_gbps"])
    assert bw[DegradeMode.RETIME] > bw[DegradeMode.COLLAPSE]


def test_fault_axis_adds_zero_compiles():
    """After one clean compile, the whole fault x degradation grid (and a
    validate=True variant after its own single compile) reuses the
    executable: every fault consequence is traced data."""
    sc = paper_configs(4)["cascaded_slr"]
    tr = _traces(sc)
    opts = SimOptions(horizon=HORIZON)
    simulate(sc, tr, opts)                        # compile
    c0 = engine.compile_count()
    grid = [FaultConfig(dead_layers=k, degrade=m)
            for k in ((3,), (1, 2)) for m in DegradeMode]
    grid += [FaultConfig(weak_ranks=(0,), retention_derate=4),
             FaultConfig(ecc_rate=0.1), FaultConfig(stuck_groups=(2,))]
    for fc in grid:
        simulate(dataclasses.replace(sc, faults=fc), tr, opts)
    assert engine.compile_count() == c0, "fault axis recompiled"
    vopts = SimOptions(horizon=HORIZON, validate=True)
    simulate(sc, tr, vopts)                       # one compile for validate
    c1 = engine.compile_count()
    for fc in grid[:3]:
        simulate(dataclasses.replace(sc, faults=fc), tr, vopts)
    assert engine.compile_count() == c1, "validate mode recompiled"


# ---------------------------------------------------------------------------
# weak retention & ECC
# ---------------------------------------------------------------------------

def test_weak_retention_refreshes_more():
    sc = dataclasses.replace(paper_configs(4)["cascaded_slr"],
                             t_refi_ns=1200.0)
    tr = _traces(sc)
    m0 = simulate(sc, tr, SimOptions(horizon=HORIZON))
    m4 = simulate(_with_faults(sc, weak_ranks=(0, 1), retention_derate=4),
                  tr, SimOptions(horizon=HORIZON))
    assert int(m4["refresh_cycles"]) > int(m0["refresh_cycles"])
    assert int(m4["ref_debt_end"]) == 0


def test_derate_ignored_when_refresh_disabled():
    """tREFI=0 means refresh is off; derating must not turn it on."""
    sc = dataclasses.replace(paper_configs(4)["cascaded_slr"],
                             t_refi_ns=0.0)        # refresh disabled
    tr = _traces(sc)
    m = simulate(_with_faults(sc, weak_ranks=(0,), retention_derate=4),
                 tr, SimOptions(horizon=HORIZON))
    assert int(m["refresh_cycles"]) == 0


def test_ecc_rereads_counted_and_priced():
    sc = paper_configs(4)["cascaded_slr"]
    tr = _traces(sc)
    m0 = simulate(sc, tr, SimOptions(horizon=HORIZON))
    me = simulate(_with_faults(sc, ecc_rate=0.25), tr,
                  SimOptions(horizon=HORIZON))
    assert int(m0["n_ecc_reread"]) == 0
    assert int(me["n_ecc_reread"]) > 0
    assert int(me["bus_cycles"]) > int(m0["bus_cycles"])
    # the energy model charges each re-read as an extra read
    e0 = E.energy_from_metrics(sc, m0)
    ee = E.energy_from_metrics(sc, {**me, "makespan_ns": m0["makespan_ns"],
                                    "bus_util": m0["bus_util"]})
    assert ee.ops_nj > e0.ops_nj


# ---------------------------------------------------------------------------
# analytic upper bound
# ---------------------------------------------------------------------------

def test_estimate_stays_upper_bound_under_faults():
    presets = [FaultConfig(),
               FaultConfig(dead_layers=(3,)),
               FaultConfig(dead_layers=(2, 3), degrade=DegradeMode.REMAP),
               FaultConfig(dead_layers=(3,), degrade=DegradeMode.COLLAPSE),
               FaultConfig(weak_ranks=(0,), retention_derate=4),
               FaultConfig(ecc_rate=0.2)]
    cfgs = {n: dataclasses.replace(sc, t_refi_ns=1200.0)
            for n, sc in paper_configs(4).items()
            if n in ("cascaded_slr", "cascaded_mlr", "dedicated_slr")}
    core = engine.CoreParams()
    cases = []
    for sc in cfgs.values():
        tr = _traces(sc)
        for fc in presets:
            cases.append((dataclasses.replace(sc, faults=fc), tr))
    horizon = max(analytic.estimate_service_cycles(s, t, core)
                  for s, t in cases)
    horizon = int(horizon) + 64
    for s, t in cases:
        m = simulate(s, t, SimOptions(horizon=horizon), core)
        assert np.asarray(m["complete"]).all(), \
            f"{s.faults.tag}: estimate was not sufficient as a horizon"
        est = analytic.estimate_service_cycles(s, t, core)
        measured = float(m["makespan_ns"]) / s.unit_ns
        assert measured <= est, \
            f"{s.io_model.name}/{s.faults.tag}: measured {measured} " \
            f"> estimate {est}"


# ---------------------------------------------------------------------------
# eager validation
# ---------------------------------------------------------------------------

def test_eager_stack_validation():
    sc = paper_configs(4)["cascaded_slr"]
    with pytest.raises(ValueError, match="layers"):
        dataclasses.replace(sc, layers=0)
    with pytest.raises(ValueError, match="banks_per_rank"):
        dataclasses.replace(sc, banks_per_rank=0)
    with pytest.raises(ValueError, match="t_rcd_ns"):
        dataclasses.replace(sc, t_rcd_ns=-1.0)
    with pytest.raises(ValueError, match="base_freq_mhz"):
        dataclasses.replace(sc, base_freq_mhz=0.0)


def test_eager_fault_validation():
    sc = paper_configs(4)["cascaded_slr"]
    with pytest.raises(ValueError, match="survive"):
        _with_faults(sc, dead_layers=(0, 1, 2, 3))
    with pytest.raises(ValueError, match="layers"):
        _with_faults(sc, dead_layers=(7,))
    with pytest.raises(ValueError, match="retention_derate"):
        FaultConfig(retention_derate=3)
    with pytest.raises(ValueError, match="ecc_rate"):
        FaultConfig(ecc_rate=0.9)
    with pytest.raises(ValueError, match="negative"):
        FaultConfig(dead_layers=(-1,))


def test_eager_simoptions_validation():
    with pytest.raises(ValueError, match="chunk"):
        SimOptions(horizon=100, chunk=0)


def test_fault_tags():
    assert FaultConfig().tag == "clean"
    fc = FaultConfig(dead_layers=(3, 2), weak_ranks=(1, 0),
                     retention_derate=4, ecc_rate=0.05,
                     degrade=DegradeMode.REMAP)
    assert fc.dead_layers == (2, 3)               # normalised
    assert fc.tag == "kill23+weak01x4+ecc0.05-remap"


# ---------------------------------------------------------------------------
# energy model
# ---------------------------------------------------------------------------

def test_dead_layer_draws_no_standby():
    sc = paper_configs(4)["cascaded_slr"]
    e0 = E.stack_energy(sc, 1000.0, 10, 10, 0.5)
    ek = E.stack_energy(_with_faults(sc, dead_layers=(3,)),
                        1000.0, 10, 10, 0.5)
    es = E.stack_energy(_with_faults(sc, stuck_groups=(3,)),
                        1000.0, 10, 10, 0.5)
    assert ek.standby_nj < e0.standby_nj
    # a stuck-group layer is alive: it keeps drawing standby current
    assert es.standby_nj == e0.standby_nj
    assert ek.ops_nj == e0.ops_nj


def test_price_refresh_is_optional_and_additive():
    sc = dataclasses.replace(paper_configs(4)["cascaded_slr"],
                             t_refi_ns=1200.0)
    tr = _traces(sc)
    m = simulate(sc, tr, SimOptions(horizon=HORIZON))
    assert int(m["refresh_cycles"]) > 0
    e_off = E.energy_from_metrics(sc, m)
    e_on = E.energy_from_metrics(sc, m, price_refresh=True)
    assert e_on.standby_nj >= e_off.standby_nj
    assert e_on.ops_nj == e_off.ops_nj


# ---------------------------------------------------------------------------
# hypothesis properties (pure-python layout invariants: no sim, no compile)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _LAYERS = 4

    @st.composite
    def fault_configs(draw):
        idx = st.sets(st.integers(0, _LAYERS - 1), max_size=_LAYERS - 1)
        return FaultConfig(
            dead_layers=tuple(draw(idx)),
            stuck_groups=tuple(draw(st.sets(
                st.integers(0, _LAYERS - 1), max_size=1))),
            weak_ranks=tuple(draw(idx)),
            retention_derate=draw(st.sampled_from(RETENTION_DERATES)),
            ecc_rate=draw(st.sampled_from([0.0, 0.05, 0.25])),
            degrade=draw(st.sampled_from(list(DegradeMode))))

    @_PROP_SETTINGS
    @hypothesis.given(fc=fault_configs())
    def test_fault_layout_invariants(fc):
        try:
            fc.validate_for(_LAYERS)
        except ValueError:
            hypothesis.assume(False)              # all layers dead
        for cname, sc in paper_configs(_LAYERS).items():
            scf = dataclasses.replace(sc, faults=fc)
            lay = scf.fault_layout()
            n_surv = len(lay["survivors"])
            assert 1 <= lay["n_ranks"] <= sc.n_ranks
            assert n_surv == _LAYERS - len(fc.effective_dead(_LAYERS))
            assert len(lay["dur"]) == lay["n_ranks"]
            assert (np.asarray(lay["dur"]) >= 1).all()
            assert len(lay["ref_derate"]) == lay["n_ranks"]
            assert set(np.asarray(lay["ref_derate"]).tolist()) <= \
                {1, fc.retention_derate}
            if not fc.effective_dead(_LAYERS):
                # no layer lost (weak ranks and ECC keep every rank):
                # the clean rank count under every mode
                assert lay["n_ranks"] == sc.n_ranks
            elif fc.degrade == DegradeMode.COLLAPSE:
                assert lay["n_ranks"] == 1
            # params always pad to the PHYSICAL rank count: the fault
            # axis can never change static shapes
            p = scf.to_params()
            assert np.shape(p["dur"]) == (sc.n_ranks,)

    @_PROP_SETTINGS
    @hypothesis.given(fc=fault_configs())
    def test_fault_tag_roundtrip_stability(fc):
        assert FaultConfig(
            dead_layers=fc.dead_layers, stuck_groups=fc.stuck_groups,
            weak_ranks=fc.weak_ranks,
            retention_derate=fc.retention_derate, ecc_rate=fc.ecc_rate,
            degrade=fc.degrade).tag == fc.tag
