"""Engine invariants under write traffic, refresh, and power-down.

Two tiers share one invariant checker:

* deterministic parametrized sweeps over the five IO models — these run in
  a bare environment (no hypothesis) and keep the new engine paths covered
  locally;
* hypothesis property tests over randomly drawn small configs/traces —
  skipped when hypothesis is absent, exercised in CI.

Shapes are deliberately reused across cases (fixed n_cores/n_req/horizon,
rank counts from the standard configs) so the whole module costs a handful
of XLA compiles, not one per example.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.smla import energy as E
from repro.core.smla import engine, policies
from repro.core.smla.config import (ControllerPolicy, RefreshGranularity,
                                    RefreshPostpone, RowPolicy,
                                    SelfRefreshPolicy, StackConfig,
                                    paper_configs)
from repro.core.smla.engine import SimOptions, simulate
from repro.core.smla.traces import (WorkloadSpec, core_traces,
                                    lm_serving_trace, synthetic_trace)

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
    # per-test settings, NOT settings.load_profile: loading a profile at
    # import time would clobber the session-wide default other hypothesis
    # modules (e.g. test_attention.py) rely on at run time
    _PROP_SETTINGS = hypothesis.settings(max_examples=8, deadline=None)
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

N_CORES = 2
N_REQ = 60
HORIZON = 3_000


def _run(stack: StackConfig, spec: WorkloadSpec, seed: int):
    traces = core_traces(seed, [spec] * N_CORES, N_REQ, stack.n_ranks,
                         stack.banks_per_rank)
    return simulate(stack, traces, SimOptions(HORIZON)), traces


def _check_invariants(stack: StackConfig, m: dict, traces: dict):
    """The engine invariants every (config, trace) pair must satisfy."""
    served = np.asarray(m["served"])
    n_req = traces["inst"].shape[1]
    p = stack.to_params()

    # no core is served more requests than its trace holds
    assert (served <= n_req).all()

    # request conservation: enqueued = retired + outstanding at horizon
    assert int(m["n_enqueued"]) == int(served.sum()) + int(m["n_outstanding"])

    # every retired/granted write came from the trace
    assert 0 <= int(m["n_wr"]) <= int(traces["wr"].sum())
    assert int(m["wr_bus_cycles"]) <= int(m["bus_cycles"])

    # no bus group is double-booked: per group the granted occupancy fits
    # in the makespan (plus one in-flight transfer per group if the run
    # was cut off by the horizon)
    mk_cyc = round(float(m["makespan_ns"]) / stack.unit_ns)
    n_groups = int(p["n_groups"])
    slack = 0 if bool(np.asarray(m["complete"]).all()) else \
        int(p["dur"].max()) * n_groups
    assert int(m["bus_cycles"]) <= mk_cyc * n_groups + slack

    # cascaded-SLR slot discipline: every grant starts in its rank's slot
    if bool(p["slotted"]):
        assert int(m["n_slot_grants"]) == int(m["n_grants"])

    # refresh accounting is bounded by the schedule (per-bank refresh
    # fires banks-per-rank times as often for the shorter tRFCpb)
    t_refi, t_rfc = int(p["t_refi"]), int(p["t_rfc"])
    if (t_refi > 0 and stack.policy.refresh_gran
            == RefreshGranularity.PER_BANK):
        t_refi = max(t_refi // stack.banks_per_rank, 1)
        t_rfc = policies.t_rfc_per_bank(t_rfc)
    if t_refi > 0:
        max_events = stack.n_ranks * (HORIZON // t_refi + 1)
        assert int(m["refresh_cycles"]) <= max_events * t_rfc
        # whole-rank blackout cycles are bounded by the refresh windows
        assert 0 <= int(m["ref_rank_blocked_cycles"]) <= max_events * t_rfc
    else:
        assert int(m["refresh_cycles"]) == 0
        assert int(m["ref_rank_blocked_cycles"]) == 0

    # closed-page is structurally conflict-free (no row is ever open)
    if stack.policy.row == RowPolicy.CLOSED_PAGE:
        assert int(m["n_row_conflicts"]) == 0

    # refresh accounting fix, pinned: per-cycle accrual never exceeds one
    # count per rank per makespan cycle
    assert int(m["refresh_cycles"]) <= mk_cyc * stack.n_ranks

    # JEDEC postpone debt: bounded by the cap, and fully repaid unless
    # the horizon cut the drain short (the loop then reports running to
    # its chunk bound)
    assert 0 <= int(m["ref_debt_max"]) <= policies.DEBT_CAP
    assert int(m["ref_debt_end"]) == 0 or int(m["chunks_run"]) \
        == engine.n_chunks(HORIZON, engine.DEFAULT_CHUNK)
    assert int(m["ref_postponed"]) >= 0 and int(m["ref_pulled_in"]) >= 0
    if stack.policy.ref_postpone == RefreshPostpone.STRICT:
        assert int(m["ref_postponed"]) == 0 and int(m["ref_debt_max"]) == 0

    # deep-state residencies partition rank-cycles: power-down,
    # self-refresh, and whole-rank refresh blackout are pairwise disjoint
    # by construction, so no rank-cycle is ever double-counted
    assert -1e-6 <= float(m["pd_frac"]) <= 1.0 + 1e-6
    assert -1e-6 <= float(m["sr_frac"]) <= 1.0 + 1e-6
    assert float(m["pd_frac"]) + float(m["sr_frac"]) <= 1.0 + 1e-6
    assert (int(m["pd_cycles"]) + int(m["sr_cycles"])
            + int(m["ref_rank_blocked_cycles"])) <= mk_cyc * stack.n_ranks
    if stack.policy.self_refresh == SelfRefreshPolicy.OFF:
        assert int(m["sr_cycles"]) == 0 and int(m["n_sr_exit"]) == 0

    # chunked execution ran at least one chunk and never past the horizon
    assert 1 <= int(m["chunks_run"]) <= -(-HORIZON // 1)

    assert float(m["bandwidth_gbps"]) <= stack.peak_bandwidth_gbps + 1e-6
    assert 0.0 <= float(m["bus_util"]) <= 1.0 + 1e-6


# ----------------------------------------------------------------------------
# deterministic tier (runs without hypothesis)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("cname", list(paper_configs(4)))
def test_invariants_all_io_models(cname):
    stack = dataclasses.replace(paper_configs(4)[cname],
                                t_refi_ns=1500.0)     # several refreshes
    spec = WorkloadSpec("w", 25.0, 0.5, write_frac=0.4)
    m, traces = _run(stack, spec, seed=5)
    assert int(traces["wr"].sum()) > 0
    _check_invariants(stack, m, traces)


@pytest.mark.parametrize("pname", sorted(policies.non_default_presets()))
def test_invariants_all_policies(pname):
    """Every engine invariant holds under every non-default controller
    policy, on the IO model most sensitive to it (cascaded SLR: slotted
    transfers + per-rank groups exercise all gating paths)."""
    pol = policies.POLICY_PRESETS[pname]
    stack = dataclasses.replace(paper_configs(4)["cascaded_slr"],
                                t_refi_ns=1500.0, policy=pol)
    spec = WorkloadSpec("w", 25.0, 0.5, write_frac=0.4)
    m, traces = _run(stack, spec, seed=5)
    _check_invariants(stack, m, traces)


def test_writes_off_is_exact_noop():
    """write_frac=0 traces + arbitrary write timings must reproduce the
    read-only engine bit-for-bit (the write machinery is inert), and a
    trace without a `wr` field must equal one with an all-zero field."""
    stack = paper_configs(4)["cascaded_slr"]
    spec = WorkloadSpec("r", 20.0, 0.6, write_frac=0.0)
    m_default, traces = _run(stack, spec, seed=3)
    assert int(traces["wr"].sum()) == 0

    no_write_timing = dataclasses.replace(stack, t_wr_ns=0.0, t_wtr_ns=0.0)
    m_zeroed = simulate(no_write_timing, traces, SimOptions(HORIZON))
    legacy = {k: v for k, v in traces.items() if k != "wr"}
    m_legacy = simulate(stack, legacy, SimOptions(HORIZON))
    for k in m_default:
        a = np.asarray(m_default[k])
        assert np.array_equal(a, np.asarray(m_zeroed[k])), k
        assert np.array_equal(a, np.asarray(m_legacy[k])), k


def test_refresh_off_is_exact_noop():
    """refresh=False must match t_refi==0 behaviour exactly, and enabling
    an aggressive refresh must cost cycles (served no earlier)."""
    base = paper_configs(4)["baseline"]
    spec = WorkloadSpec("w", 30.0, 0.4, write_frac=0.3)
    off = dataclasses.replace(base, refresh=False)
    m_off, traces = _run(off, spec, seed=11)
    assert int(m_off["refresh_cycles"]) == 0
    fast = dataclasses.replace(base, t_refi_ns=500.0)
    m_fast = simulate(fast, traces, SimOptions(HORIZON))
    assert int(m_fast["refresh_cycles"]) > 0
    assert float(m_fast["makespan_ns"]) >= float(m_off["makespan_ns"])


def test_write_traffic_slows_fixed_work():
    """Same arrival process, writes on vs off: write recovery + turnaround
    can only lengthen (never shorten) the fixed-work makespan."""
    stack = dataclasses.replace(paper_configs(4)["baseline"],
                                refresh=False)
    ro = synthetic_trace(7, WorkloadSpec("a", 40.0, 0.5, write_frac=0.0),
                         N_REQ, stack.n_ranks, stack.banks_per_rank)
    wr = dict(ro, wr=(np.arange(N_REQ) % 2).astype(np.int32))  # 50% writes
    m_ro = simulate(stack, {k: np.stack([v] * N_CORES) for k, v in ro.items()},
                    SimOptions(HORIZON))
    m_wr = simulate(stack, {k: np.stack([v] * N_CORES) for k, v in wr.items()},
                    SimOptions(HORIZON))
    assert int(m_wr["n_wr"]) > 0
    assert float(m_wr["makespan_ns"]) >= float(m_ro["makespan_ns"])


def test_powerdown_fraction_tracks_intensity():
    """A nearly idle workload powers the ranks down almost always; a
    saturating stream almost never."""
    stack = dataclasses.replace(paper_configs(4)["baseline"], refresh=False)
    m_idle, _ = _run(stack, WorkloadSpec("idle", 0.8, 0.6), seed=2)
    m_hot, _ = _run(stack, WorkloadSpec("hot", 200.0, 0.9, write_frac=0.3),
                    seed=2)
    assert float(m_idle["pd_frac"]) > float(m_hot["pd_frac"])
    assert float(m_idle["pd_frac"]) > 0.3


def test_legacy_params_without_write_refresh_timings():
    """Params dicts predating the write/refresh extension (no t_wr / t_wtr
    / t_refi / t_rfc / t_pd keys) must still run through the batched path,
    behaving exactly as the pre-write-era engine: writes/refresh machinery
    inert and NO power-down residency (t_pd defaults to never, not 0)."""
    sc = paper_configs(4)["baseline"]
    spec = WorkloadSpec("r", 15.0, 0.5)
    traces = core_traces(0, [spec] * N_CORES, N_REQ, sc.n_ranks,
                         sc.banks_per_rank)
    p = sc.to_params()
    for k in ("t_wr", "t_wtr", "t_refi", "t_rfc", "t_pd", "t_sr", "t_xsr",
              "sr_sel", "post_sel"):
        del p[k]
    p["n_req"] = np.int32(N_REQ)
    out = engine.batched_simulate(
        {k: np.stack([v]) for k, v in p.items()},
        {k: np.stack([v]) for k, v in traces.items()},
        SimOptions(HORIZON), engine.CoreParams(), sc.banks_per_rank)
    assert int(np.asarray(out["pd_cycles"])[0]) == 0
    legacy_like = dataclasses.replace(sc, refresh=False, t_wr_ns=0.0,
                                      t_wtr_ns=0.0, pd_idle_ns=1e9)
    ref = simulate(legacy_like, traces, SimOptions(HORIZON))
    for k in ref:
        assert np.array_equal(np.asarray(out[k])[0], np.asarray(ref[k])), k


def test_chunks_run_is_diagnostic_only():
    """Deterministic tier of the chunk-invariance property: any chunk
    width reproduces the full-horizon metrics bit-for-bit; only the
    chunks_run diagnostic varies, bounded by ceil(horizon/chunk)."""
    stack = dataclasses.replace(paper_configs(4)["cascaded_slr"],
                                t_refi_ns=1500.0)
    spec = WorkloadSpec("w", 25.0, 0.5, write_frac=0.4)
    traces = core_traces(5, [spec] * N_CORES, N_REQ, stack.n_ranks,
                         stack.banks_per_rank)
    full = simulate(stack, traces, SimOptions(HORIZON, chunk=None))
    assert int(full["chunks_run"]) == 1
    for chunk in (100, 512, 2048):
        m = simulate(stack, traces, SimOptions(HORIZON, chunk=chunk))
        for k in full:
            if k == "chunks_run":
                continue
            assert np.array_equal(np.asarray(m[k]),
                                  np.asarray(full[k])), (chunk, k)
        assert 1 <= int(m["chunks_run"]) <= -(-HORIZON // chunk)


def test_lm_serving_trace_kv_writes():
    """The decode trace's KV-append writes: requested fraction, and rows
    that advance monotonically (append locality), not uniform-random."""
    t = lm_serving_trace(0, 600, 4, 2, kv_write_frac=0.12)
    frac = t["wr"].sum() / 600
    assert 0.05 < frac < 0.2
    wrows = t["row"][t["wr"] != 0].astype(np.int64)
    steps = np.diff(wrows) % 4096
    assert (steps <= 1).all()                  # sequential append walk


# ----------------------------------------------------------------------------
# paper Table 1 write / power-down rows through the metrics path
# ----------------------------------------------------------------------------

def test_table1_write_and_powerdown_priced_from_metrics():
    stack = dataclasses.replace(paper_configs(4)["baseline"],
                                t_refi_ns=1500.0)
    spec = WorkloadSpec("w", 25.0, 0.5, write_frac=0.4)
    m, _ = _run(stack, spec, seed=5)
    n_wr, pd_frac = int(m["n_wr"]), float(m["pd_frac"])
    assert n_wr > 0 and pd_frac > 0.0

    eb = E.energy_from_metrics(stack, m)
    # Table 1 write row: each measured write is priced E_WR instead of E_RD
    eb_reads_only = E.energy_from_metrics(stack, m, n_wr=0)
    assert eb.ops_nj - eb_reads_only.ops_nj == pytest.approx(
        n_wr * (E.E_WR_NJ - E.E_RD_NJ))
    # Table 1 power-down row: the measured residency draws 0.24 mA
    eb_no_pd = E.energy_from_metrics(stack, m, pd_frac=0.0)
    assert eb.standby_nj < eb_no_pd.standby_nj

    # full power-down window reproduces the 0.24 mA row exactly
    t_ns = 1e6
    full_pd = E.stack_energy(stack, t_ns, n_act=0, n_rd=0, active_frac=0.0,
                             pd_frac=1.0)
    assert full_pd.standby_nj == pytest.approx(
        stack.layers * E.PD_MA * stack.vdd * t_ns * 1e-3)


# ----------------------------------------------------------------------------
# queue reductions: dense forms against the segment ops they replaced
# ----------------------------------------------------------------------------

def _segment_counts(mask, idx, n):
    """The reference: the scatter-add the engine used to count with."""
    return jax.ops.segment_sum(jnp.where(mask, 1, 0), idx, num_segments=n)


@pytest.mark.parametrize("R,B,QT,rows", [(4, 8, 32, 1), (8, 2, 16, 14),
                                         (8, 8, 8, 4), (2, 4, 64, 3),
                                         (1, 1, 4, 1)])
def test_counts_by_matches_segment_sum(R, B, QT, rows):
    """`policies.counts_by` counts what `segment_sum` counted, per rank and
    per (rank, bank), row by row of a vmapped batch; entries with no
    weight may point anywhere, a padded rank above `n_ranks` included,
    and count nowhere."""
    rng = np.random.default_rng([R, B, QT, rows])
    n_ranks = max(R - 1, 1)
    mask = rng.random((rows, QT)) < 0.6
    qr = np.where(mask, rng.integers(0, n_ranks, (rows, QT)),
                  rng.integers(0, R, (rows, QT)))
    qr[~mask & (rng.random((rows, QT)) < 0.5)] = R - 1   # a padded rank
    qb = rng.integers(0, B, (rows, QT))
    mask, qr, qb = jnp.asarray(mask), jnp.asarray(qr), jnp.asarray(qb)

    counts = jax.vmap(policies.counts_by, (0, 0, None))
    reference = jax.vmap(_segment_counts, (0, 0, None))
    per_rank = counts(mask, qr, R)
    np.testing.assert_array_equal(per_rank, reference(mask, qr, R))
    per_bank = counts(mask, qr * B + qb, R * B)
    np.testing.assert_array_equal(per_bank,
                                  reference(mask, qr * B + qb, R * B))
    # the refresh stage's per-rank in-flight test, from the per-bank counts
    np.testing.assert_array_equal(
        per_bank.reshape(rows, R, B).sum(axis=2) > 0, per_rank > 0)
    assert per_rank.dtype == jnp.int32
    # a padded rank never counts: every weighted entry names a real one
    np.testing.assert_array_equal(np.asarray(per_rank)[:, n_ranks:], 0)


@pytest.mark.parametrize("n_cores,Wd,rows", [(4, 8, 4), (2, 8, 14),
                                             (16, 1, 1)])
def test_window_reshape_reductions_match_segment_ops(n_cores, Wd, rows):
    """Per-core reductions over the static window segments
    (slot // Wd, every segment non-empty) as a (n_cores, Wd) reshape equal
    `segment_sum` / `segment_max` / `segment_min` over the core index."""
    rng = np.random.default_rng([n_cores, Wd, rows])
    QT = n_cores * Wd
    qc = jnp.arange(QT, dtype=jnp.int32) // Wd
    fin = jnp.asarray(rng.random((rows, QT)) < 0.3)
    tag = jnp.asarray(rng.integers(0, 1000, (rows, QT)), jnp.int32)
    inst = jnp.where(jnp.asarray(rng.random((rows, QT)) < 0.5),
                     jnp.asarray(rng.random((rows, QT)) * 1e4, jnp.float32),
                     jnp.float32(1e30))

    def dense(fin, tag, inst):
        by_core = lambda x: x.reshape(n_cores, Wd)  # noqa: E731
        return (by_core(jnp.where(fin, 1, 0)).sum(axis=1),
                by_core(jnp.where(fin, tag, -1)).max(axis=1),
                by_core(tag).min(axis=1), by_core(inst).min(axis=1))

    def segments(fin, tag, inst):
        return (jax.ops.segment_sum(jnp.where(fin, 1, 0), qc,
                                    num_segments=n_cores),
                jax.ops.segment_max(jnp.where(fin, tag, -1), qc,
                                    num_segments=n_cores),
                jax.ops.segment_min(tag, qc, num_segments=n_cores),
                jax.ops.segment_min(inst, qc, num_segments=n_cores))

    for got, want in zip(jax.vmap(dense)(fin, tag, inst),
                         jax.vmap(segments)(fin, tag, inst)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------------------
# hypothesis tier (CI)
# ----------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @_PROP_SETTINGS
    @hypothesis.given(
        cname=st.sampled_from(sorted(paper_configs(4))),
        layers=st.sampled_from([2, 4]),
        mpki=st.sampled_from([2.0, 15.0, 60.0]),
        rowhit=st.sampled_from([0.2, 0.6, 0.9]),
        write_frac=st.sampled_from([0.0, 0.3, 0.7]),
        refi_ns=st.sampled_from([0.0, 900.0, 7800.0]),
        pname=st.sampled_from(sorted(policies.POLICY_PRESETS)),
        seed=st.integers(0, 50),
    )
    def test_invariants_random(cname, layers, mpki, rowhit, write_frac,
                               refi_ns, pname, seed):
        stack = dataclasses.replace(
            paper_configs(layers)[cname],
            refresh=refi_ns > 0, t_refi_ns=refi_ns or 7800.0,
            policy=policies.POLICY_PRESETS[pname])
        spec = WorkloadSpec("w", mpki, rowhit, write_frac=write_frac)
        m, traces = _run(stack, spec, seed)
        _check_invariants(stack, m, traces)

    @_PROP_SETTINGS
    @hypothesis.given(
        cname=st.sampled_from(sorted(paper_configs(4))),
        mpki=st.sampled_from([10.0, 40.0]),
        write_frac=st.sampled_from([0.2, 0.5]),
        seed=st.integers(0, 50),
    )
    def test_per_bank_never_blocks_more_random(cname, mpki, write_frac,
                                               seed):
        """Property form of the per-bank refresh invariant: for random
        configs/traces, per-bank refresh never blacks out more whole-rank
        cycles than all-bank on the same run."""
        ab = dataclasses.replace(paper_configs(4)[cname], t_refi_ns=1200.0)
        pb = dataclasses.replace(ab, policy=ControllerPolicy(
            refresh_gran=RefreshGranularity.PER_BANK))
        spec = WorkloadSpec("w", mpki, 0.5, write_frac=write_frac)
        m_ab, traces = _run(ab, spec, seed)
        m_pb = simulate(pb, traces, SimOptions(HORIZON))
        assert int(m_pb["ref_rank_blocked_cycles"]) <= \
            int(m_ab["ref_rank_blocked_cycles"])

    @_PROP_SETTINGS
    @hypothesis.given(
        cname=st.sampled_from(sorted(paper_configs(4))),
        mpki=st.sampled_from([10.0, 40.0]),
        rowhit=st.sampled_from([0.3, 0.8]),
        seed=st.integers(0, 50),
    )
    def test_closed_page_zero_hits_random(cname, mpki, rowhit, seed):
        """Property form: closed-page never records a row hit or a row
        conflict, whatever the trace locality."""
        stack = dataclasses.replace(
            paper_configs(4)[cname], t_refi_ns=1500.0,
            policy=ControllerPolicy(row=RowPolicy.CLOSED_PAGE))
        spec = WorkloadSpec("w", mpki, rowhit, write_frac=0.3)
        m, _ = _run(stack, spec, seed)
        assert int(m["n_row_conflicts"]) == 0
        if bool(np.asarray(m["complete"]).all()) \
                and int(m["n_outstanding"]) == 0:
            assert int(m["n_act"]) == int(m["n_grants"])

    @_PROP_SETTINGS
    @hypothesis.given(
        cname=st.sampled_from(sorted(paper_configs(4))),
        chunk=st.sampled_from([64, 300, 1024, HORIZON, HORIZON + 999]),
        mpki=st.sampled_from([2.0, 25.0, 60.0]),
        write_frac=st.sampled_from([0.0, 0.4]),
        seed=st.integers(0, 50),
    )
    def test_chunks_run_never_changes_metrics_random(cname, chunk, mpki,
                                                     write_frac, seed):
        """Property form: for random configs/traces, every metric except
        the chunks_run diagnostic is invariant to the chunk width."""
        stack = dataclasses.replace(paper_configs(4)[cname],
                                    t_refi_ns=1500.0)
        spec = WorkloadSpec("w", mpki, 0.5, write_frac=write_frac)
        traces = core_traces(seed, [spec] * N_CORES, N_REQ, stack.n_ranks,
                             stack.banks_per_rank)
        full = simulate(stack, traces, SimOptions(HORIZON, chunk=None))
        m = simulate(stack, traces, SimOptions(HORIZON, chunk=chunk))
        for k in full:
            if k == "chunks_run":
                continue
            assert np.array_equal(np.asarray(m[k]),
                                  np.asarray(full[k])), (cname, chunk, k)
        assert 1 <= int(m["chunks_run"]) <= -(-HORIZON // min(chunk,
                                                              HORIZON))

    @_PROP_SETTINGS
    @hypothesis.given(
        cname=st.sampled_from(sorted(paper_configs(4))),
        mpki=st.sampled_from([0.5, 5.0, 40.0]),
        write_frac=st.sampled_from([0.0, 0.4]),
        refi_ns=st.sampled_from([400.0, 1500.0]),
        seed=st.integers(0, 50),
    )
    def test_deep_state_accounting_random(cname, mpki, write_frac, refi_ns,
                                          seed):
        """Property form of the refresh/power interaction invariants:
        under the combined self-refresh + postpone policy, for random
        configs and traces, no rank-cycle is double-counted across
        power-down, self-refresh, and refresh blackout; debt never
        exceeds the JEDEC cap and is repaid before the loop exits."""
        stack = dataclasses.replace(
            paper_configs(4)[cname], t_refi_ns=refi_ns,
            policy=ControllerPolicy(
                self_refresh=SelfRefreshPolicy.ENABLED,
                ref_postpone=RefreshPostpone.POSTPONE_8X))
        spec = WorkloadSpec("w", mpki, 0.5, write_frac=write_frac)
        m, traces = _run(stack, spec, seed)
        _check_invariants(stack, m, traces)
        mk_cyc = round(float(m["makespan_ns"]) / stack.unit_ns)
        assert (int(m["pd_cycles"]) + int(m["sr_cycles"])
                + int(m["ref_rank_blocked_cycles"])) \
            <= mk_cyc * stack.n_ranks
        assert int(m["ref_debt_max"]) <= policies.DEBT_CAP

    @_PROP_SETTINGS
    @hypothesis.given(mpki=st.sampled_from([5.0, 40.0]),
                      seed=st.integers(0, 50))
    def test_writes_off_matches_read_only_random(mpki, seed):
        """Property form of the no-op check over random traces/configs."""
        stack = paper_configs(4)["dedicated_slr"]
        spec = WorkloadSpec("r", mpki, 0.5, write_frac=0.0)
        traces = core_traces(seed, [spec] * N_CORES, N_REQ, stack.n_ranks,
                             stack.banks_per_rank)
        zeroed = dataclasses.replace(stack, t_wr_ns=0.0, t_wtr_ns=0.0)
        a = simulate(stack, traces, SimOptions(HORIZON))
        b = simulate(zeroed, traces, SimOptions(HORIZON))
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
