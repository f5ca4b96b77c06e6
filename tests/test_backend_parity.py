"""Backend parity tier: the Pallas cycle engine against the scan engine.

`SimOptions(backend="pallas")` routes `simulate`/`batched_simulate`/
`run_sweep` through `core/smla/pallas_engine.sim_cell_blocks` — the
staged per-cycle pipeline fused into one kernel over cell blocks.  The
kernel body reuses `engine._sim_core`, so parity is expected by
construction; this module makes that a contract:

* the golden grid (`tests/golden/smla_small_grid.json`) must pass under
  the pallas backend unregenerated — integers exact, floats to the same
  1e-6 rtol the scan backend is held to across platforms;
* the full POLICY_PRESETS x 5-IO-model cross-product must agree between
  a pallas *sweep* (batched, makespan-bucketed, padded into cell blocks)
  and per-cell scan `simulate()` calls — pad cells and bucket shuffling
  must never leak into any metric;
* the policy cross-product stays ONE shape group under pallas: the
  compile counter may grow only by the auto-chunk ladder widths;
* (hypothesis) across backends AND different chunk widths, every metric
  except `chunks_run` is invariant — chunking is an execution detail,
  `chunks_run` its only observable.

Runs through the Pallas interpreter (`interpret=True`): the kernel does
not lower through Mosaic for the TPU (see `core/smla/pallas_engine.py`).
"""
import json

import numpy as np
import pytest

from repro.core.smla import engine, policies, sweep
from repro.core.smla.config import paper_configs
from repro.core.smla.engine import SimOptions, simulate
from repro.core.smla.traces import WorkloadSpec, core_traces
from repro.core.smla import golden
from repro.core.smla.golden import RTOL
from test_golden import GOLDEN_PATH

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
    _PROP_SETTINGS = hypothesis.settings(max_examples=6, deadline=None)
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

HORIZON = 3_000
N_REQ = 60
SEED = 7

PALLAS = SimOptions(horizon=HORIZON, backend="pallas", interpret=True)


def _diff_metrics(name, got, want, *, skip=()):
    """Per-metric diffs between two metric dicts (ints/bools exact,
    floats to the golden rtol)."""
    errors = []
    for k in sorted(want):
        if k in skip:
            continue
        g, w = np.asarray(got[k]), np.asarray(want[k])
        if np.issubdtype(w.dtype, np.floating):
            ok = np.allclose(g, w, rtol=RTOL, atol=0.0)
        else:
            ok = np.array_equal(g, w)
        if not ok:
            errors.append(f"{name}:{k} got {g.tolist()} want {w.tolist()}")
    return errors


def test_pallas_requires_interpret_off_tpu():
    """On non-TPU hosts the compiled pallas path must refuse loudly,
    pointing at interpret=True, instead of failing inside Mosaic."""
    if jax_backend_is_tpu():
        pytest.skip("compiled pallas is legitimate here")
    cells = golden.grid_cells()[:1]
    with pytest.raises(ValueError, match="interpret=True"):
        simulate(cells[0].stack, cells[0].traces,
                 SimOptions(horizon=HORIZON, backend="pallas"))


def jax_backend_is_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def test_pallas_matches_golden_grid():
    """The checked-in golden numbers, byte-for-byte, through the kernel."""
    want = json.loads(GOLDEN_PATH.read_text())["cells"]
    opts = SimOptions(horizon=golden.HORIZON, backend="pallas",
                      interpret=True)
    res = sweep.run_sweep(sweep.SweepSpec(tuple(golden.grid_cells()),
                                          options=opts))
    assert res.backend == "pallas"
    errors = golden.mismatches(golden.pinned_metrics(res), want)
    assert not errors, \
        "pallas backend drifted from golden:\n" + "\n".join(errors)


def test_pallas_sweep_matches_scan_simulate_policy_grid():
    """Sweep-vs-simulate bit-identity across backends, over the full
    POLICY_PRESETS x 5-IO-model cross-product.  The pallas sweep runs
    batched/bucketed/padded; the reference is the unbatched scan
    `simulate()` — so this covers backend parity AND pad/bucket
    invariance in one pass."""
    w = WorkloadSpec("mix.1", 18.0, 0.6, write_frac=0.2)
    base = [sweep.make_cell(cname, sc, [w, w], N_REQ, seed=SEED)
            for cname, sc in paper_configs(4).items()]
    cells = sweep.policy_cells(base, tuple(policies.POLICY_PRESETS.values()))

    c0 = engine.compile_count()
    res = sweep.run_sweep(sweep.SweepSpec(
        tuple(cells),
        options=SimOptions(horizon=HORIZON, backend="pallas",
                           interpret=True)))
    compiles = engine.compile_count() - c0
    # the policy axis must not multiply pallas compiles: one shape group,
    # at most one compile per auto-chunk ladder width
    assert compiles <= len(set(res.chunks)), \
        f"pallas policy grid took {compiles} compiles " \
        f"(want <= {len(set(res.chunks))} chunk widths)"

    errors = []
    for cell in cells:
        ref = simulate(cell.stack, cell.traces, SimOptions(horizon=HORIZON))
        errors += _diff_metrics(cell.name, res[cell.name], ref,
                                skip=("chunks_run",))
    assert not errors, \
        "pallas sweep diverged from scan simulate():\n" + "\n".join(errors)


def test_pallas_matches_scan_on_fault_grid():
    """Backend parity across the fault x degradation axes: the fault
    consequences are traced data (re-timed durations, degraded rank
    counts, refresh derates, ECC cadence), so the SAME kernel must
    reproduce the scan backend on every degraded layout — including the
    new `n_ecc_reread` counter, which is integer and therefore exact."""
    from repro.core.smla.faults import DegradeMode, FaultConfig
    import dataclasses
    w = WorkloadSpec("mix.1", 18.0, 0.6, write_frac=0.2)
    base = [sweep.make_cell(cname, sc, [w, w], N_REQ, seed=SEED)
            for cname, sc in paper_configs(4).items()
            if cname in ("cascaded_slr", "cascaded_mlr", "dedicated_slr")]
    base = [dataclasses.replace(
        c, stack=dataclasses.replace(c.stack, t_refi_ns=1200.0))
        for c in base]
    faults = (FaultConfig(),
              FaultConfig(dead_layers=(3,)),
              FaultConfig(dead_layers=(2, 3), degrade=DegradeMode.REMAP),
              FaultConfig(dead_layers=(3,), degrade=DegradeMode.COLLAPSE),
              FaultConfig(weak_ranks=(0,), retention_derate=4,
                          ecc_rate=0.2))
    cells = sweep.fault_cells(base, faults)
    res = sweep.run_sweep(sweep.SweepSpec(
        tuple(cells),
        options=SimOptions(horizon=HORIZON, backend="pallas",
                           interpret=True)))
    errors = []
    for cell in cells:
        ref = simulate(cell.stack, cell.traces, SimOptions(horizon=HORIZON))
        errors += _diff_metrics(cell.name, res[cell.name], ref,
                                skip=("chunks_run",))
    assert not errors, \
        "pallas fault grid diverged from scan simulate():\n" \
        + "\n".join(errors)


def test_pallas_single_cell_matches_scan():
    """Unbatched path: `simulate()` itself under both backends, equal
    chunking — every metric including `chunks_run` must agree."""
    cells = golden.grid_cells()[:4]
    opts_scan = SimOptions(horizon=HORIZON, chunk=256)
    opts_pl = SimOptions(horizon=HORIZON, chunk=256, backend="pallas",
                         interpret=True)
    errors = []
    for cell in cells:
        ref = simulate(cell.stack, cell.traces, opts_scan)
        got = simulate(cell.stack, cell.traces, opts_pl)
        errors += _diff_metrics(cell.name, got, ref)
    assert not errors, "\n".join(errors)


if HAVE_HYPOTHESIS:

    @_PROP_SETTINGS
    @hypothesis.given(
        mpki=st.floats(0.5, 50.0),
        locality=st.floats(0.1, 0.9),
        write_frac=st.floats(0.0, 0.5),
        seed=st.integers(0, 2**16),
        config=st.sampled_from(sorted(paper_configs(4))),
    )
    def test_only_chunks_run_may_differ(mpki, locality, write_frac, seed,
                                        config):
        """Chunk width and backend are execution details: for any
        workload, scan/no-early-exit vs pallas/chunk=256 must agree on
        every metric except `chunks_run` (the chunking observable).
        Shapes are fixed (n_req/horizon/config family) so the whole
        property costs a handful of compiles."""
        stack = paper_configs(4)[config]
        w = WorkloadSpec("prop", mpki, locality, write_frac=write_frac)
        traces = core_traces(seed, [w, w], N_REQ, stack.n_ranks,
                             stack.banks_per_rank)
        ref = simulate(stack, traces, SimOptions(horizon=HORIZON,
                                                 chunk=None))
        got = simulate(stack, traces,
                       SimOptions(horizon=HORIZON, chunk=256,
                                  backend="pallas",
                                  interpret=True))
        errors = _diff_metrics(f"{config}", got, ref, skip=("chunks_run",))
        assert not errors, "\n".join(errors)
