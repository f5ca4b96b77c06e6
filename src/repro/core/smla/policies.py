"""Controller policy as traced integer selectors.

The paper evaluates one fixed memory controller — FR-FCFS scheduling,
open-page row management, all-bank per-rank refresh, writes competing
inline with reads.  SMLA's benefit is known to be sensitive to all four
choices (NOM's inter-bank windows reshape bank-level parallelism,
arXiv:2004.09923; die-stacked bandwidth wins hinge on the access patterns
the row policy mediates, arXiv:1608.07485), so this module exposes each
choice as a **traced int32 selector** carried in the engine's params dict:

* ``sched_sel``  — `SchedPolicy`:        FR-FCFS | FCFS
* ``row_sel``    — `RowPolicy`:          open-page | closed-page
* ``ref_sel``    — `RefreshGranularity`: all-bank | per-bank round-robin
* ``drain_sel``  — `WriteDrainPolicy`:   inline | drain-when-full |
                                          opportunistic low-watermark
* ``sr_sel``     — `SelfRefreshPolicy`:  off | self-refresh entry (a rank
                                          idle past t_sr drops below
                                          power-down; exit charges t_xsr)
* ``post_sel``   — `RefreshPostpone`:    strict deadline | JEDEC-style 8x
                                          postpone with drain-aware pull-in
* ``clk_sel``    — `LayerClockPolicy`:   uniform | DVFS-style per-layer
                                          clock gating (a Dedicated-IO SLR
                                          layer's link drops to the
                                          Cascaded tier clock; transfer
                                          durations stretch by the
                                          per-rank ``clk_div`` vector,
                                          standby energy falls)
* ``ooo_sel``    — `OooSelect`:          in-order | row grouping |
                                          direction batching | both — the
                                          out-of-order selection over the
                                          tagged transaction window
                                          (window *depth* is the static
                                          ``CoreParams.window`` knob;
                                          the selection is traced)

Because the selectors are traced (not Python closure constants), one
compiled engine program serves the whole policy cross-product with the
same padded shapes — exactly like it already serves the config grid.
Every helper below is written so that the *default* selector value
reduces to the pre-policy engine arithmetic bit-for-bit: `jnp.where`
branches fall back to the historical expression, in the same integer
domain, so `tests/golden/smla_small_grid.json` passes unregenerated.

Score encoding (int32-safe): the schedule score is ``bonus - qarr`` with
``qarr < horizon < 2**30``.  A row hit adds ``BIG`` (2**30) under FR-FCFS;
a write during a drain-when-full burst adds ``BIG + BIG//2`` (fits int32)
so draining writes outrank even row-hit reads, as real write bursts do.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.smla.config import (ControllerPolicy, LayerClockPolicy,
                                    OooSelect, RefreshGranularity,
                                    RefreshPostpone, RowPolicy, SchedPolicy,
                                    SelfRefreshPolicy, WriteDrainPolicy)

#: score/sentinel magnitude shared with the engine (engine.BIG aliases
#: this) — the int32 score encoding above depends on it staying 2**30.
#: A numpy (not jnp) scalar on purpose: jax inlines it as a jaxpr
#: literal, so kernel bodies using it (the Pallas backend traces the
#: stages inside `pl.pallas_call`, which forbids captured device-array
#: constants) stay closure-free; arithmetic/promotion is identical.
BIG = np.int32(2**30)

#: params keys carrying the traced policy selectors, in to_params order.
#: `clk_sel` (DVFS-style per-layer clock gating) additionally carries its
#: per-rank divider vector in the separate dur-shaped `clk_div` param —
#: the selector alone decides whether the dividers apply.
#: `degrade_sel` (fault degradation mode, core/smla/faults.py) rides
#: here too: its layout consequences are lowered Python-side by
#: `StackConfig.fault_layout`, the selector itself is carried traced for
#: provenance (it surfaces in the metrics dict) and defaults to 0
#: (RETIME — inert on a clean stack) like every other selector.
SELECTOR_KEYS = ("sched_sel", "row_sel", "ref_sel", "drain_sel",
                 "sr_sel", "post_sel", "clk_sel", "degrade_sel", "ooo_sel")

#: out-of-order window bonuses (`OooSelect`), additive on top of
#: `schedule_bonus`: a row-group match adds BIG>>2, a direction-batch
#: match BIG>>3.  Worst-case total score stays int32-safe
#: (1.5*BIG drain + 0.375*BIG ooo = 1.875*BIG < 2**31), and the tier
#: order is preserved for every horizon < 2**27 (far above any real
#: makespan): drain-burst writes (1.5*BIG - qarr) still outrank a
#: row-hit read with both OoO bonuses (<= 1.375*BIG), and a row hit
#: (>= BIG - qarr) still outranks any miss (dir bonus only,
#: <= BIG>>3).  numpy scalars like BIG: Pallas kernel bodies must
#: stay closure-free.
OOO_ROW_BONUS = np.int32(BIG >> 2)
OOO_DIR_BONUS = np.int32(BIG >> 3)

#: JEDEC maximum number of postponed refresh commands per rank (the "8x
#: postpone" of LPDDR/DDR4): the engine's per-rank debt counter is capped
#: here, tested as a hard invariant (`ref_debt_max <= DEBT_CAP`, debt
#: drained to zero before the chunked loop may exit).
DEBT_CAP = 8


def t_rfc_per_bank(t_rfc):
    """JEDEC-style per-bank refresh occupancy: tRFCpb ~= tRFC/2 (rounded
    up).  Single source of truth — the engine's refresh stage, the
    analytic estimate, and the invariant tests must all agree on it.
    Works on traced arrays and Python ints alike."""
    return (t_rfc + 1) // 2


def drain_watermarks(q_size: int, n_cores: int, mshr: int,
                     window: int = 1) -> tuple[int, int]:
    """(high, low) write-drain watermarks.

    Watermarks are fractions (3/4, 1/4) of the *reachable* queue
    occupancy — min(q_size, n_cores * mshr * window), since enqueue is
    MSHR-gated (the transaction window multiplies the per-core in-flight
    cap, `CoreParams.window`) — not of the raw queue depth; otherwise a
    deep queue in front of few cores could never arm the drain burst.
    window=1 reproduces the historical values exactly."""
    cap = max(min(q_size, n_cores * mshr * window), 1)
    return max((3 * cap) // 4, 1), cap // 4


# ----------------------------------------------------------------------------
# named presets (the benchmark / test policy axis)
# ----------------------------------------------------------------------------

#: the paper's fixed controller — the engine's bit-identical default
PAPER_DEFAULT = ControllerPolicy()

#: one single-axis flip per policy dimension plus the all-flipped corner;
#: the fig_policy benchmark sweeps exactly these against the default
POLICY_PRESETS: dict[str, ControllerPolicy] = {
    "default": PAPER_DEFAULT,
    "fcfs": ControllerPolicy(scheduler=SchedPolicy.FCFS),
    "closed_page": ControllerPolicy(row=RowPolicy.CLOSED_PAGE),
    "per_bank_refresh": ControllerPolicy(
        refresh_gran=RefreshGranularity.PER_BANK),
    "drain_when_full": ControllerPolicy(
        write_drain=WriteDrainPolicy.DRAIN_WHEN_FULL),
    "opportunistic_drain": ControllerPolicy(
        write_drain=WriteDrainPolicy.OPPORTUNISTIC),
    "self_refresh": ControllerPolicy(
        self_refresh=SelfRefreshPolicy.ENABLED),
    "postpone_8x": ControllerPolicy(
        ref_postpone=RefreshPostpone.POSTPONE_8X),
    "layer_gated": ControllerPolicy(
        layer_clock=LayerClockPolicy.GATED),
    "ooo_rowdir": ControllerPolicy(ooo=OooSelect.ROW_DIR),
    "all_flipped": ControllerPolicy(
        scheduler=SchedPolicy.FCFS, row=RowPolicy.CLOSED_PAGE,
        refresh_gran=RefreshGranularity.PER_BANK,
        write_drain=WriteDrainPolicy.OPPORTUNISTIC,
        self_refresh=SelfRefreshPolicy.ENABLED,
        ref_postpone=RefreshPostpone.POSTPONE_8X),
}

#: the refresh/power corner of the cross-product, as one named axis for
#: `benchmarks/paper_fig_refresh.py`: the paper's controller, each new
#: refresh/power knob alone, their combination, and per-bank + postpone
#: (postponed refreshes pulled in at per-bank granularity — the fully
#: drain-aware scheduler).
REFRESH_PRESETS: dict[str, ControllerPolicy] = {
    "default": PAPER_DEFAULT,
    "self_refresh": POLICY_PRESETS["self_refresh"],
    "postpone_8x": POLICY_PRESETS["postpone_8x"],
    "sr_postpone": ControllerPolicy(
        self_refresh=SelfRefreshPolicy.ENABLED,
        ref_postpone=RefreshPostpone.POSTPONE_8X),
    "pb_postpone": ControllerPolicy(
        refresh_gran=RefreshGranularity.PER_BANK,
        ref_postpone=RefreshPostpone.POSTPONE_8X),
}


def non_default_presets() -> dict[str, ControllerPolicy]:
    return {k: v for k, v in POLICY_PRESETS.items() if not v.is_default}


# ----------------------------------------------------------------------------
# traced views of the selectors (one call per simulation, shared by stages)
# ----------------------------------------------------------------------------

def selector_view(params: dict) -> dict:
    """Boolean/int views of the traced selectors the engine stages branch
    on.  All leaves are traced scalars; nothing here is a compile-time
    constant."""
    return {
        "fcfs": params["sched_sel"] == int(SchedPolicy.FCFS),
        "closed_page": params["row_sel"] == int(RowPolicy.CLOSED_PAGE),
        "per_bank": params["ref_sel"] == int(RefreshGranularity.PER_BANK),
        "drain_full": params["drain_sel"]
        == int(WriteDrainPolicy.DRAIN_WHEN_FULL),
        "drain_opp": params["drain_sel"]
        == int(WriteDrainPolicy.OPPORTUNISTIC),
        "sr": params["sr_sel"] == int(SelfRefreshPolicy.ENABLED),
        "postpone": params["post_sel"] == int(RefreshPostpone.POSTPONE_8X),
        "clk_gated": params["clk_sel"] == int(LayerClockPolicy.GATED),
        # OoO window selection decomposes into two independent bits: row
        # grouping (ROW_GROUP | ROW_DIR) and direction batching
        # (DIR_BATCH | ROW_DIR) — both False under IN_ORDER
        "ooo_row": (params["ooo_sel"] == int(OooSelect.ROW_GROUP))
        | (params["ooo_sel"] == int(OooSelect.ROW_DIR)),
        "ooo_dir": (params["ooo_sel"] == int(OooSelect.DIR_BATCH))
        | (params["ooo_sel"] == int(OooSelect.ROW_DIR)),
    }


def refresh_timings(pol: dict, t_refi, t_rfc, banks: int,
                    refresh_en) -> tuple:
    """(t_refi_eff, t_rfc_eff) for the selected refresh granularity.

    Per-bank refresh fires `banks` times as often (tREFI/B) but each event
    occupies a single bank for the JEDEC-style shorter tRFCpb ~= tRFC/2;
    all-bank keeps the historical values untouched (bit-identity)."""
    per_bank = pol["per_bank"]
    t_refi_eff = jnp.where(per_bank & refresh_en,
                           jnp.maximum(t_refi // banks, 1), t_refi)
    t_rfc_eff = jnp.where(per_bank, t_rfc_per_bank(t_rfc), t_rfc)
    return t_refi_eff, t_rfc_eff


def refresh_bank_mask(pol: dict, ref_bank, banks: int):
    """(R, B) mask of banks a starting refresh event covers: the whole
    rank (all-bank) or only the round-robin target bank (per-bank — the
    rank's other banks keep serving through the NOM-style inter-bank
    window)."""
    one_hot = jnp.arange(banks, dtype=jnp.int32)[None, :] == ref_bank[:, None]
    return jnp.where(pol["per_bank"], one_hot, True)


def counts_by(mask, idx, n: int):
    """(n,) int32: how many queue entries with `mask` set have `idx` equal
    to each bin; an index outside [0, n) counts nowhere.

    The same counts as ``jax.ops.segment_sum(mask, idx, n)``, computed as
    one compare against the bins and a sum over the queue axis: the
    scatter-add that ``segment_sum`` lowers to applies its updates one by
    one on the TPU, several times the cost of this one fusion."""
    hit = (idx[:, None] == jnp.arange(n, dtype=idx.dtype)) & mask[:, None]
    return hit.sum(axis=0, dtype=jnp.int32)


def refresh_demand(pol: dict, draining, qv, qphase, qwr, qr, n_ranks: int):
    """(R,) mask: does rank r have *demand* a postponed refresh would
    serve sooner?  Demand is any valid queue entry for the rank — except
    writes currently held by an unarmed drain-when-full policy: while the
    burst is not armed those writes are not issuable anyway, so the
    write-shadow window is exactly where owed refreshes pull in (the
    ROADMAP's drain-aware refresh scheduling)."""
    held_wr = pol["drain_full"] & ~draining
    counted = qv & (qphase >= 1) & ~(qwr & held_wr)
    return counts_by(counted, qr, n_ranks) > 0


def cas_refresh_block(pol: dict, ref_due, ref_bank, qr, qb):
    """Queue-entry mask: new CAS issue blocked because the entry's target
    is draining for a due refresh.  All-bank drains the whole rank (the
    historical behaviour); per-bank drains only the target bank."""
    return ref_due[qr] & jnp.where(pol["per_bank"], qb == ref_bank[qr], True)


def schedule_bonus(pol: dict, hit, drain_write):
    """Per-entry score bonus.  FR-FCFS boosts row hits by BIG (FCFS
    ignores row state); a write in a drain-when-full burst outranks
    everything (BIG + BIG//2, int32-safe)."""
    bonus = jnp.where(hit & ~pol["fcfs"], BIG, 0)
    return jnp.where(drain_write, BIG + (BIG >> 1), bonus)


def ooo_schedule_bonus(pol: dict, hit, dir_match):
    """Additive CAS-selection bonus from the OoO window selection
    (`OooSelect`): row grouping favours entries hitting the open row
    (meaningful under FCFS, where `schedule_bonus` ignores row state, and
    sub-tier under FR-FCFS); direction batching favours entries matching
    the bus group's last granted direction, so the scheduler feeds the
    bus same-direction runs that amortise tWTR.  Identically zero under
    IN_ORDER — the historical score is untouched bit-for-bit."""
    return (jnp.where(pol["ooo_row"] & hit, OOO_ROW_BONUS, 0)
            + jnp.where(pol["ooo_dir"] & dir_match, OOO_DIR_BONUS, 0))


def ooo_transfer_bonus(pol: dict, whit, dir_match):
    """Additive bus-grant bonus from the OoO window selection: row
    grouping completes page-hit transfers (`whit`, recorded at CAS
    issue) ahead of bank-cycle ones; direction batching keeps granting
    the direction the group last moved, turning read/write interleave
    into runs.  Identically zero under IN_ORDER, so the historical
    oldest-first grant order is untouched bit-for-bit."""
    return (jnp.where(pol["ooo_row"] & whit, OOO_ROW_BONUS, 0)
            + jnp.where(pol["ooo_dir"] & dir_match, OOO_DIR_BONUS, 0))


def write_eligible(pol: dict, draining, n_wq, any_read, lo: int):
    """May waiting writes issue this cycle?

    INLINE: always (the paper's controller).  DRAIN_WHEN_FULL: only
    during a drain burst — or when no read is issuable, which also
    guarantees fixed work completes.  OPPORTUNISTIC: above the low
    watermark, or whenever the scheduler would otherwise idle reads."""
    full = draining | ~any_read
    opp = (n_wq >= lo) | ~any_read
    return jnp.where(pol["drain_full"], full,
                     jnp.where(pol["drain_opp"], opp, True))


def update_drain_state(draining, n_wq, hi: int, lo: int):
    """Drain-burst hysteresis: arm at the high watermark, disarm at the
    low one.  Evolves (inertly) under every policy; only
    DRAIN_WHEN_FULL's eligibility and priority read it."""
    return jnp.where(n_wq >= hi, True,
                     jnp.where(n_wq <= lo, False, draining))


def issue_row_update(pol: dict, row, ready, t_rp):
    """(new_bank_row, new_bank_busy) for the issued access' bank.

    Open-page keeps the row open and frees the bank at CAS-ready (the
    historical behaviour); closed-page auto-precharges — the row is never
    recorded open (zero row hits, structurally) and the bank stays busy
    tRP past ready."""
    closed = pol["closed_page"]
    new_row = jnp.where(closed, -1, row)
    new_busy = ready + jnp.where(closed, t_rp, 0)
    return new_row, new_busy


def write_recovery_extra(pol: dict, t_rp):
    """Closed-page writes auto-precharge after write recovery: tRP added
    on top of tWR.  Zero under open-page (bit-identity)."""
    return jnp.where(pol["closed_page"], t_rp, 0)
