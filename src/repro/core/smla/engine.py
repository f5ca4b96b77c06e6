"""Cycle-level 3D-stacked DRAM simulator (the paper's evaluation vehicle),
as a vectorised scan over fast cycles with chunked early exit.

Time unit: one *fast cycle* = 1 / (L * F)  (1.25 ns for the paper's 4-layer,
200 MHz Wide-IO baseline) — every Table-2 quantity is an integer multiple.

The per-cycle step is a fixed pipeline of composable **stage functions**
(`_STAGES`), each taking and returning the scan state plus a per-cycle
`aux` dict of transients:

    refresh -> enqueue -> schedule -> transfer -> retire -> progress -> power

* `_stage_refresh`   per-rank tREFI counters; a due rank (all-bank) or its
  round-robin target bank (per-bank) drains, then refreshes for tRFC —
  rows close, transfers stall.  tREFI == 0 disables refresh exactly.
  Under JEDEC-style postponing a due refresh defers while demand is
  queued (per-rank debt, cap 8) and owed refreshes pull in during idle
  or write-drain shadow windows; a rank in self-refresh suspends its
  deadlines entirely (it refreshes internally).
* `_stage_enqueue`   round-robin one core per cycle into the core's
  tagged transaction-window segment (depth min(mshr * `CoreParams.
  window`, q_size) per core, `q_size` the shared credit cap; tags are
  program-order indices).  A full window or exhausted credit stalls the
  core — no request is ever dropped.
* `_stage_schedule`  one CAS per cycle, picked over the whole window by
  the scheduler policy (FR-FCFS row hits first, or strict FCFS) plus the
  OoO window selection (`OooSelect`: row grouping / direction batching
  sub-tier bonuses) over the row policy's bank state (open-page keeps
  rows open; closed-page auto-precharges — zero row hits, structurally)
  under the write-drain policy's eligibility (inline, drain-when-full
  burst, or opportunistic low-watermark).
* `_stage_transfer`  one bus start per group per cycle; cascaded-SLR time
  slots, write recovery (tWR) and write-to-read turnaround (tWTR); under
  `OooSelect` row grouping completes page-hit transfers first and
  direction batching extends same-direction runs to amortise tWTR.
* `_stage_retire`    completed transfers retire out of order; tags and
  MSHRs free (`n_ooo_retire` counts completions ahead of an older
  same-core tag).
* `_stage_progress`  3-wide 3.2 GHz cores, MSHR-limited, instruction-
  window runahead (the paper's Table-3 core model).
* `_stage_power`     power-down / self-refresh residency: a rank idle
  t_pd consecutive cycles accumulates `pd_cycles`; under the self-
  refresh policy a rank idle t_sr cycles (debt clear) drops deeper into
  self-refresh (`sr_cycles`, exit charges t_xsr), so
  `energy.stack_energy` prices Table 1's 0.24 mA power-down and the
  deeper retention-only state with *measured* residencies.

IO models (paper §4/§5): BASELINE (one full-width bus, 4L cycles/req),
DEDICATED MLR (L cycles), DEDICATED SLR (per-rank W/L group, 4L cycles),
CASCADED MLR (full-bus time slots, L cycles), CASCADED SLR (rank r owns
slot t mod L == r, (beats-1)*L+1 cycles).

Every per-config quantity the stages need — timing vector, per-rank
transfer durations, bus-group map, slotted flag, layer count, actual
rank/request counts, **and the four controller-policy selectors** (see
``core/smla/policies.py``) — is a *traced* input (``StackConfig.
to_params``), not a Python closure constant.  Only array shapes are
static, so one jitted program serves every configuration AND every point
of the policy cross-product with the same padded shapes, and
``sweep.run_sweep`` can vmap it over a stacked (config, workload, policy)
cell axis.  With default policies the pipeline is bit-identical to the
historical monolithic step — pinned by ``tests/golden/smla_small_grid.
json``.  Compiled executables are cached per static signature;
``compile_count()`` exposes the number of distinct executables,
``compile_stats()`` how many of them XLA compiled or loaded from the
persistent cache and the seconds each took, and ``reset_compile_count()``
rebases both (tests assert deltas, never absolutes).

Execution is *chunked*: instead of one fixed `lax.scan` over the full
horizon, a `lax.while_loop` runs fixed-width scan chunks (``chunk`` fast
cycles each, default ``DEFAULT_CHUNK``) and terminates as soon as every
core has ``served >= n_req`` — so wall time is proportional to the
simulated *makespan*, not to the horizon.  Steps past the horizon in the
final partial chunk are gated to exact no-ops, and all fixed-work
counters freeze once work completes (``work_left`` gating plus a per-core
freeze of the instruction counter at completion), so chunked results are
bit-identical to a full-horizon run for every metric.  The number of
chunks actually executed is returned as the ``chunks_run`` diagnostic —
the only metric allowed to depend on the chunk size.  Under `vmap`, JAX's
while-loop batching masks finished cells, so each cell of a stacked batch
freezes (and reports ``chunks_run``) at its *own* exit point; the batch
runs until its slowest member finishes, which is why ``sweep.run_sweep``
buckets cells by estimated makespan before stacking.

Execution is selected by a single frozen ``SimOptions`` value (horizon,
chunk, backend, interpret) threaded through every entry point and the
compile cache.  ``backend="scan"`` is this module's reference pipeline;
``backend="pallas"`` runs the *same* ``_sim_core`` inside a Pallas kernel
tiled over blocks of the stacked cell axis (``core/smla/pallas_engine``),
meant to keep the whole per-cell state dict on-chip across the chunked
while-loop; it runs in interpreter mode only (see that module).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.smla import policies
from repro.core.smla.config import StackConfig
from repro.core.smla.policies import BIG

#: fast cycles per early-exit scan chunk; ``chunk=None`` disables chunking
#: (one chunk spanning the whole horizon — the full-horizon reference run).
#: 1024 measured best on the fig11 grid: fine enough exit granularity
#: without noticeable while-loop dispatch overhead.  ``sweep.run_sweep``
#: additionally derives finer per-bucket widths for fast buckets
#: (``SimOptions.chunk="auto"``), clamped to this value.
DEFAULT_CHUNK = 1024

#: ``SimOptions.chunk`` sentinel: let the executor pick the width —
#: ``sweep.run_sweep`` derives one per makespan bucket (its ladder),
#: ``simulate``/``batched_simulate`` fall back to ``DEFAULT_CHUNK``.
AUTO = "auto"

#: execution backends: ``"scan"`` is the reference ``lax.scan`` pipeline
#: (state round-trips HBM every chunk); ``"pallas"`` fuses the whole
#: chunked while-loop into a Pallas kernel over cell blocks
#: (``core/smla/pallas_engine.py``), interpreter mode only.
BACKENDS = ("scan", "pallas")


@dataclasses.dataclass(frozen=True)
class SimOptions:
    """The execution surface of the cycle engine, in one hashable value.

    Replaces the keyword-only kwargs that accreted across ``simulate`` /
    ``batched_simulate`` / ``run_sweep`` (horizon positional int,
    ``chunk=``, per-call backend flags): one frozen dataclass is threaded
    through every entry point AND keys the compile cache, so two runs
    with equal options provably share one executable per shape group.

    horizon    fast-cycle scan horizon (safety net; the chunked engine
               exits at the measured makespan).
    chunk      early-exit scan-chunk width: int pins a width, ``None``
               disables chunking (one full-horizon chunk), ``AUTO``
               (default) lets the executor pick — per-bucket ladder in
               ``sweep.run_sweep``, ``DEFAULT_CHUNK`` elsewhere.
    backend    ``"scan"`` (reference) or ``"pallas"`` (fused kernel; bit-
               compatible, see ``pallas_engine`` for the documented float
               tolerance).
    interpret  run the Pallas kernel in interpreter mode — required on
               every platform, as the kernel does not lower through
               Mosaic (see ``pallas_engine``); ignored by ``"scan"``.
    validate   debug mode: wrap the compiled program in
               ``jax.experimental.checkify`` NaN / negative-cycle guards
               (both backends — the checks run on the kernel's outputs).
               A violated guard raises ``checkify.JaxRuntimeError`` with
               the failing metric named, instead of silently propagating
               garbage into figures.  Off by default (one extra pass over
               the outputs; results are bit-identical either way).
    compile_cache_dir
               directory for JAX's *persistent* compilation cache.  Every
               entry point applies the cache before compiling, so the XLA
               executables behind each shape group survive the process: a
               journal resume (or any re-run of the same grid)
               deserialises the compiled program instead of paying the
               multi-second XLA compile again.  ``JAX_COMPILATION_CACHE_DIR``
               in the environment wins over this field; with neither set
               the cache lives at ``DEFAULT_COMPILE_CACHE_DIR``.  Results
               are bit-identical with or without the cache.
    """
    horizon: int
    chunk: int | None | str = AUTO
    backend: str = "scan"
    interpret: bool = False
    validate: bool = False
    compile_cache_dir: str | None = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend={self.backend!r} not in {BACKENDS}")
        if not (self.compile_cache_dir is None
                or isinstance(self.compile_cache_dir, str)):
            raise ValueError(f"compile_cache_dir="
                             f"{self.compile_cache_dir!r}: want str or None")
        if not (self.chunk is None or self.chunk == AUTO
                or isinstance(self.chunk, (int, np.integer))):
            raise ValueError(f"chunk={self.chunk!r}: want int, None or "
                             f"{AUTO!r}")
        if (isinstance(self.chunk, (int, np.integer))
                and not isinstance(self.chunk, bool) and int(self.chunk) < 1):
            raise ValueError(f"chunk={self.chunk!r}: want >= 1")
        if int(self.horizon) < 1:
            raise ValueError(f"horizon={self.horizon!r}: want >= 1")

    def with_chunk(self, chunk: int | None) -> "SimOptions":
        return dataclasses.replace(self, chunk=chunk)

    def resolved(self) -> "SimOptions":
        """AUTO chunk -> DEFAULT_CHUNK (single-batch executors; the sweep
        resolves AUTO per makespan bucket before it gets here)."""
        if self.chunk == AUTO:
            return dataclasses.replace(self, chunk=DEFAULT_CHUNK)
        return self


def _require_options(options, fn_name: str) -> SimOptions:
    """The execution surface is a SimOptions, full stop.  (The PR-6
    deprecation shim — positional int horizon + ``chunk=`` kwarg — had
    its one release of overlap and is gone; fail with a migration hint
    instead of a cryptic attribute error.)"""
    if not isinstance(options, SimOptions):
        raise TypeError(
            f"{fn_name}: pass SimOptions(horizon=..., chunk=...) — the "
            f"legacy positional-int horizon surface was removed "
            f"(got {type(options).__name__})")
    return options


def _check_backend(options: SimOptions) -> None:
    if (options.backend == "pallas" and not options.interpret
            and jax.default_backend() != "tpu"):
        raise ValueError(
            "backend='pallas' compiles through Mosaic, which needs a TPU; "
            "on CPU/GPU pass SimOptions(..., interpret=True) to run the "
            "kernel in interpreter mode (same semantics, no fusion)")


#: persistent compilation cache when neither ``JAX_COMPILATION_CACHE_DIR``
#: nor ``SimOptions.compile_cache_dir`` names one: a fixed directory in
#: the source checkout (listed in .gitignore).  The path is part of what
#: makes a cache hit, so it must not vary between runs.
DEFAULT_COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[os.pardir] * 4,
    ".jax_cache"))

#: last cache directory applied to the process-global jax config — the
#: applier is idempotent so hot sweep loops don't re-touch jax.config.
_CACHE_DIR_APPLIED = [None]


def compile_cache_dir(options: SimOptions) -> str:
    """The persistent-cache directory `options` runs under:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``options.compile_cache_dir``, else `DEFAULT_COMPILE_CACHE_DIR`."""
    return os.path.normpath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                            or options.compile_cache_dir
                            or DEFAULT_COMPILE_CACHE_DIR)


def _apply_compile_cache(options: SimOptions) -> None:
    """Point JAX's persistent compilation cache at
    `compile_cache_dir(options)`.

    The thresholds are dropped to "cache everything" (min compile time 0,
    no minimum entry size): the sweep's executables are few and large, and
    a journal resume that recompiles them from scratch wastes more wall
    time than the grid itself on small-to-medium grids.  The jax config is
    process-global; this helper only touches it when the directory
    actually changes.  ``jax_enable_compilation_cache=False`` still turns
    the cache off."""
    cache_dir = compile_cache_dir(options)
    if _CACHE_DIR_APPLIED[0] == cache_dir:
        return
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _CACHE_DIR_APPLIED[0] = cache_dir


def effective_chunk(horizon: int, chunk: int | None) -> int:
    """The scan-chunk width actually used for `horizon`: clamped to
    [1, horizon]; None means one full-horizon chunk.  Single source of
    truth for every consumer of the chunking policy (the engine itself,
    perf reporting, CI gates)."""
    return horizon if chunk is None else max(1, min(int(chunk), horizon))


def n_chunks(horizon: int, chunk: int | None) -> int:
    """Maximum while-loop iterations for (horizon, chunk): the bound
    `chunks_run` reaches when early exit never engages."""
    return -(-horizon // effective_chunk(horizon, chunk))


@dataclasses.dataclass(frozen=True)
class CoreParams:
    mshr: int = 8
    inst_window: float = 128.0   # instruction-window runahead
    inst_per_fast_cycle: float = 12.0   # 3-wide * 3.2GHz * 1.25ns
    #: controller request-queue credit cap (static).  Total window
    #: occupancy across cores never exceeds it; a core at the cap stalls
    #: enqueue — requests are never dropped (invariant tested in
    #: tests/test_policies.py).  Also feeds the write-drain watermarks
    #: (`policies.drain_watermarks`: 3/4 and 1/4 of the reachable
    #: occupancy min(q_size, n_cores*mshr*window)).
    q_size: int = 32
    #: tagged transaction-window depth multiplier (static, like q_size:
    #: it sizes the window arrays, so changing it recompiles).  Each core
    #: owns a private segment of min(mshr * window, q_size) in-flight
    #: entries carrying tag/rank/bank/row/direction/age; enqueue
    #: allocates tags in program order, schedule and transfer select
    #: over the whole window (`OooSelect` decides how), retire completes
    #: out of order and frees tags.  window=1 is the bit-identical
    #: historical datapath: the per-core MSHR file IS the window.
    window: int = 1


# ----------------------------------------------------------------------------
# pipeline stages
#
# Each stage is `(st, aux, t, ctx) -> (st, aux)`: `st` is the scan-carried
# state (mutated via dict assignment on a per-step shallow copy), `aux`
# holds per-cycle transients handed down the pipeline (work_left, the
# refresh-due mask, ...), `ctx` the per-simulation constants: traced
# params, trace arrays, policy selector views, static shape ints.
# ----------------------------------------------------------------------------


def _stage_refresh(st, aux, t, ctx):
    """Refresh (before issue: a started refresh blocks its target).

    All-bank (default): a due rank waits until it has no busy bank AND no
    issued/granted request in flight (phase >= 2) — a refresh must not
    close a row under an already-CAS'd request or start mid-data-burst —
    then all its banks refresh for tRFC.  Per-bank: only the round-robin
    target bank must drain; the rank's other banks keep scheduling and
    transferring through the refresh (the NOM-style inter-bank window).
    New CAS issue to the draining target is blocked in `_stage_schedule`,
    so the drain completes in bounded time either way.

    Postponing (JEDEC 8x, `RefreshPostpone.POSTPONE_8X`): a deadline that
    fires while the rank has queued *demand* (`policies.refresh_demand`:
    any entry except writes held by an unarmed drain-when-full burst)
    defers instead of draining — the per-rank debt counter records the
    owed refresh, hard-capped at `policies.DEBT_CAP` (= 8), where the
    strict drain-and-refresh behaviour resumes.  Owed refreshes pull in
    one per tRFC as soon as the rank (target bank under per-bank) is
    drained during an idle or write-shadow window; a pull-in repays debt
    without advancing `ref_next`.  The chunked while-loop refuses to exit
    while any debt remains, so debt provably drains to zero.

    A rank in self-refresh refreshes internally: its external deadlines
    are suspended here (never due) and restarted by `_stage_power` at
    exit."""
    R, B, pol = ctx["R"], ctx["B"], ctx["pol"]
    qv, qphase, qr, qb = st["qv"], st["qphase"], st["qr"], st["qb"]
    qwr = st["qwr"]
    bank_busy, bank_row = st["bank_busy"], st["bank_row"]
    ref_next, ref_until, ref_bank = (st["ref_next"], st["ref_until"],
                                     st["ref_bank"])
    ref_debt, in_sr = st["ref_debt"], st["in_sr"]
    t_rfc_eff, t_refi_eff = ctx["t_rfc_eff"], ctx["t_refi_eff"]

    ref_due = ctx["refresh_en"] & (t >= ref_next) & ctx["real_rank"] \
        & ~in_sr
    demand = policies.refresh_demand(pol, st["draining"], qv, qphase, qwr,
                                     qr, R)
    postpone = pol["postpone"] & ref_due & demand \
        & (ref_debt < policies.DEBT_CAP)
    ref_debt = ref_debt + jnp.where(postpone, 1, 0)
    ref_next = jnp.where(postpone, ref_next + t_refi_eff, ref_next)
    ref_due = ref_due & ~postpone

    # issued/granted entries per (rank, bank); qr < R and qb < B (the
    # traces are reduced modulo n_ranks and B), so a rank's count is the
    # sum of its banks'
    in_flight_rb = policies.counts_by(qv & (qphase >= 2), qr * B + qb,
                                      R * B).reshape(R, B)
    # all-bank drain condition: the whole rank idle, nothing in flight
    bank_idle = (bank_busy <= t).all(axis=1)
    in_flight = in_flight_rb.sum(axis=1) > 0
    can_ab = bank_idle & ~in_flight
    # per-bank drain condition: only the target bank idle / drained
    ranks = jnp.arange(R, dtype=jnp.int32)
    can_pb = (bank_busy[ranks, ref_bank] <= t) \
        & ~(in_flight_rb[ranks, ref_bank] > 0)
    can_start = jnp.where(pol["per_bank"], can_pb, can_ab)
    start_sched = ref_due & can_start
    # drain-aware pull-in: an owed refresh executes while the rank has no
    # demand and its target is drained (postpone and pull-in are mutually
    # exclusive: one needs demand, the other its absence)
    pull = pol["postpone"] & (ref_debt > 0) & ~demand & ~ref_due \
        & can_start & ~in_sr
    ref_start = start_sched | pull
    ref_debt = ref_debt - jnp.where(pull, 1, 0)

    covered = ref_start[:, None] & policies.refresh_bank_mask(
        pol, ref_bank, B)
    bank_busy = jnp.where(covered, t + t_rfc_eff, bank_busy)
    bank_row = jnp.where(covered, -1, bank_row)          # rows close
    ref_until = jnp.where(covered, t + t_rfc_eff, ref_until)
    ref_next = jnp.where(start_sched, ref_next + t_refi_eff, ref_next)
    st["ref_bank"] = jnp.where(ref_start & pol["per_bank"],
                               (ref_bank + 1) % B, ref_bank)
    # counters accumulate only while work remains, so fixed-work metrics
    # cover the makespan, not the idle tail of the scan horizon.
    # refresh_cycles accrues per cycle (one count per refresh event in
    # progress: a whole rank under all-bank, a bank under per-bank), so a
    # run completing mid-refresh counts only the cycles inside the
    # makespan — charging the full tRFC at event start overcounted.
    in_ref = ref_until > t
    n_ref_ev = jnp.where(pol["per_bank"], in_ref.sum(),
                         in_ref.all(axis=1).sum())
    st["refresh_cycles"] = st["refresh_cycles"] + jnp.where(
        aux["work_left"], n_ref_ev, 0)
    # rank-cycles with EVERY bank under refresh: the whole-rank blackout
    # all-bank refresh imposes and per-bank refresh exists to avoid.
    all_blocked = in_ref.all(axis=1) & ctx["real_rank"]
    st["ref_rank_blocked"] = st["ref_rank_blocked"] + jnp.where(
        aux["work_left"], all_blocked.sum(), 0)
    st["ref_postponed"] = st["ref_postponed"] + jnp.where(
        aux["work_left"], postpone.sum(), 0)
    st["ref_pulled_in"] = st["ref_pulled_in"] + jnp.where(
        aux["work_left"], pull.sum(), 0)
    # structural bound, tracked ungated: debt only decays once work is
    # done (no demand -> no postpone), so the max is chunk-invariant
    st["ref_debt_max"] = jnp.maximum(st["ref_debt_max"], ref_debt.max())

    st.update(bank_busy=bank_busy, bank_row=bank_row,
              ref_next=ref_next, ref_until=ref_until, ref_debt=ref_debt)
    aux["ref_due"] = ref_due
    aux["ref_target"] = ref_bank          # pre-increment round-robin target
    return st, aux


def _stage_enqueue(st, aux, t, ctx):
    """Enqueue (round-robin one core per cycle) into the core's private
    window segment.  The tag is the request's program-order index
    (`c_next`) — monotone and unique per core, so retire can observe
    out-of-order completion.  A full segment, exhausted shared credit
    (`q_size`), or full MSHR file stalls the core — `do_enq` stays False
    and the request is retried next round; nothing is ever dropped.

    window=1 equivalence with the historical shared queue: the segment
    has min(mshr, q_size) slots and per-core occupancy equals `c_out`,
    so `mshr_ok & credit_ok` implies a free segment slot (c_out <= total
    occupancy < q_size and c_out < mshr) — the admission decision is
    bit-identical, only the slot *position* differs, and every consumer
    selects by score/segment reductions, never by slot order."""
    n_req, tr, Wd = ctx["n_req"], ctx["traces"], ctx["Wd"]
    cid = t % ctx["n_cores"]
    nxt = st["c_next"][cid]
    has_req = nxt < n_req
    idx = jnp.minimum(nxt, n_req - 1)
    arrived = tr["inst"][cid, idx] <= st["c_inst"][cid]
    mshr_ok = st["c_out"][cid] < ctx["core"].mshr * ctx["core"].window
    credit_ok = jnp.where(st["qv"], 1, 0).sum() < ctx["core"].q_size
    seg = jax.lax.dynamic_slice(st["qv"], (cid * Wd,), (Wd,))
    free_slot = cid * Wd + jnp.argmin(seg)    # first False in the segment
    slot_ok = ~st["qv"][free_slot]
    do_enq = has_req & arrived & mshr_ok & credit_ok & slot_ok

    def put(field, val):
        cur = st[field]
        st[field] = cur.at[free_slot].set(
            jnp.where(do_enq, val, cur[free_slot]))

    put("qv", True)
    put("qtag", nxt)
    put("qr", tr["rank"][cid, idx])
    put("qb", tr["bank"][cid, idx])
    put("qrow", tr["row"][cid, idx])
    put("qinst", tr["inst"][cid, idx])
    put("qarr", t)
    put("qphase", 1)
    put("qwr", tr["wr"][cid, idx])
    put("whit", False)
    st["c_next"] = st["c_next"].at[cid].add(jnp.where(do_enq, 1, 0))
    st["c_out"] = st["c_out"].at[cid].add(jnp.where(do_enq, 1, 0))
    return st, aux


def _stage_schedule(st, aux, t, ctx):
    """Scheduler: one CAS command per cycle.

    Candidates are phase-1 entries whose bank is free and not blocked by
    a due refresh (whole rank under all-bank, target bank under
    per-bank).  The write-drain policy decides whether waiting writes are
    eligible this cycle; the scheduler policy ranks candidates (FR-FCFS
    row-hit bonus or plain FCFS age order, drain-burst writes first); the
    row policy decides what the issue does to the bank (open-page keeps
    the row open, closed-page auto-precharges)."""
    pol = ctx["pol"]
    qv, qr, qb, qrow = st["qv"], st["qr"], st["qb"], st["qrow"]
    qarr, qphase, qwr = st["qarr"], st["qphase"], st["qwr"]
    bank_busy, bank_row = st["bank_busy"], st["bank_row"]
    t_rcd, t_rp, t_cl = ctx["t_rcd"], ctx["t_rp"], ctx["t_cl"]

    b_busy = bank_busy[qr, qb] <= t
    ref_blk = policies.cas_refresh_block(pol, aux["ref_due"],
                                         aux["ref_target"], qr, qb)
    # a rank in self-refresh issues nothing until `_stage_power` has
    # charged its t_xsr exit (all-False under the default policy)
    cand0 = qv & (qphase == 1) & b_busy & ~ref_blk & ~st["in_sr"][qr]

    # write-drain eligibility (inert under the default INLINE policy).
    # Two write counts with different jobs: the burst *hysteresis* arms
    # on whole-queue write occupancy (any phase — the watermarks are
    # fractions of reachable occupancy and an entry holds its slot until
    # retire; counting phase-1 waiters only let fast-transfer configs
    # race writes past phase 1 faster than they accumulated, so
    # DRAIN_WHEN_FULL could never arm — bugfix), while OPPORTUNISTIC's
    # low-watermark *eligibility* keeps measuring the waiting backlog
    # (in-flight writes need no further issue decisions).
    n_wq_wait = jnp.where(qv & (qphase == 1) & qwr, 1, 0).sum()
    n_wq_occ = jnp.where(qv & qwr, 1, 0).sum()
    draining = policies.update_drain_state(st["draining"], n_wq_occ,
                                           ctx["wq_hi"], ctx["wq_lo"])
    st["n_drain_bursts"] = st["n_drain_bursts"] + jnp.where(
        aux["work_left"] & draining & ~st["draining"], 1, 0)
    st["draining"] = draining
    any_read = (cand0 & ~qwr).any()
    wr_ok = policies.write_eligible(pol, draining, n_wq_wait, any_read,
                                    ctx["wq_lo"])
    cand = cand0 & (~qwr | wr_ok)

    open_row = bank_row[qr, qb]
    hit = open_row == qrow
    closed = open_row < 0
    drain_write = pol["drain_full"] & draining & qwr
    # OoO window selection (additive sub-tier bonuses, zero under
    # IN_ORDER): prefer the open row, or the bus group's last granted
    # direction (`grp_last_wr` — updated at grant in `_stage_transfer`)
    dir_match = qwr == st["grp_last_wr"][ctx["group_of_rank"][qr]]
    # score: policy bonus first, then age (smaller arrival = older)
    score = jnp.where(cand,
                      policies.schedule_bonus(pol, hit, drain_write)
                      + policies.ooo_schedule_bonus(pol, hit, dir_match)
                      - qarr,
                      -BIG)
    pick = jnp.argmax(score)
    can_issue = cand[pick]
    lat = jnp.where(hit[pick], t_cl,
                    jnp.where(closed[pick], t_rcd + t_cl,
                              t_rp + t_rcd + t_cl)).astype(jnp.int32)
    ready = t + lat
    pr, pb = qr[pick], qb[pick]
    new_row, new_busy = policies.issue_row_update(pol, qrow[pick], ready,
                                                  t_rp)
    st["bank_busy"] = bank_busy.at[pr, pb].set(
        jnp.where(can_issue, new_busy, bank_busy[pr, pb]))
    st["bank_row"] = bank_row.at[pr, pb].set(
        jnp.where(can_issue, new_row, bank_row[pr, pb]))
    st["qphase"] = qphase.at[pick].set(
        jnp.where(can_issue, 2, qphase[pick]))
    st["qready"] = st["qready"].at[pick].set(
        jnp.where(can_issue, ready, st["qready"][pick]))
    # record the row-hit bit on the entry: `_stage_transfer` completes
    # whit transfers ahead of bank-cycle ones under ROW_GROUP/ROW_DIR
    st["whit"] = st["whit"].at[pick].set(
        jnp.where(can_issue, hit[pick], st["whit"][pick]))
    st["n_act"] = st["n_act"] + jnp.where(can_issue & ~hit[pick], 1, 0)
    st["n_row_hit"] = st["n_row_hit"] + jnp.where(
        can_issue & hit[pick], 1, 0)
    st["n_conflict"] = st["n_conflict"] + jnp.where(
        can_issue & ~hit[pick] & ~closed[pick], 1, 0)
    return st, aux


def _stage_transfer(st, aux, t, ctx):
    """Bus grant: one transfer start per group per cycle.  Padded groups
    (g >= n_groups) never match any valid entry's group_of_rank, so the
    extra iterations are exact no-ops.

    OoO window selection (zero effect under IN_ORDER): row grouping
    completes page-hit transfers (`whit`) ahead of bank-cycle ones;
    direction batching keeps granting the group's last direction
    (`grp_last_wr`).  `wtr_stall` attributes the turnaround cost the
    batching amortises: cycles a free bus group granted nothing while a
    read sat blocked solely by the write-to-read window."""
    R, pol = ctx["R"], ctx["pol"]
    qv, qr, qb, qarr, qwr = st["qv"], st["qr"], st["qb"], st["qarr"], st["qwr"]
    qphase, qready, qdone = st["qphase"], st["qready"], st["qdone"]
    bank_busy = st["bank_busy"]
    grp_busy, grp_wr_until = st["grp_busy"], st["grp_wr_until"]
    grp_last_wr = st["grp_last_wr"]
    ref_until = st["ref_until"]
    t_wr, t_wtr = ctx["t_wr"], ctx["t_wtr"]

    qphase = jnp.where(qv & (qphase == 2) & (qready <= t), 3, qphase)
    slot_match = (t % ctx["L"]) == (qr % ctx["L"])
    n_grants, n_slot_grants = st["n_grants"], st["n_slot_grants"]
    n_ecc = st["n_ecc_reread"]
    bus_cycles, wr_bus_cycles = st["bus_cycles"], st["wr_bus_cycles"]
    wtr_stall = st["wtr_stall"]
    wr_extra = policies.write_recovery_extra(pol, ctx["t_rp"])
    for g in range(R):
        in_g = ctx["group_of_rank"][qr] == g
        base3 = qv & (qphase == 3) & in_g
        # slotted (cascaded SLR): rank may start only in its time slot;
        # a refreshing bank transfers nothing until its tRFC elapses.
        base3 = base3 & (~ctx["slotted"] | slot_match)
        base3 = base3 & (ref_until[qr, qb] <= t)
        # reads wait out the group's write-to-read turnaround window
        wtr_ok = qwr | (grp_wr_until[g] <= t)
        cand3 = base3 & wtr_ok & (grp_busy[g] <= t)
        dir_match = qwr == grp_last_wr[g]
        score3 = jnp.where(
            cand3,
            policies.ooo_transfer_bonus(pol, st["whit"], dir_match) - qarr,
            -BIG)
        p3 = jnp.argmax(score3)
        go = cand3[p3]
        # transient-error pricing (faults.FaultConfig.ecc_rate): every
        # ecc_every-th bus grant, when it is a read, detects an error
        # and re-occupies its group for a second transfer (ECC
        # re-read).  ecc_every = ECC_OFF (the clean default) never
        # fires: grant counters stay far below 2**30.
        reread = go & ~qwr[p3] \
            & (n_grants % ctx["ecc_every"] == ctx["ecc_every"] - 1)
        d = ctx["dur"][qr[p3]] + jnp.where(reread, ctx["dur"][qr[p3]], 0)
        n_ecc = n_ecc + jnp.where(reread, 1, 0)
        go_wr = go & qwr[p3]
        grp_busy = grp_busy.at[g].set(jnp.where(go, t + d, grp_busy[g]))
        qphase = qphase.at[p3].set(jnp.where(go, 4, qphase[p3]))
        qdone = qdone.at[p3].set(jnp.where(go, t + d, qdone[p3]))
        # write recovery: the bank stays busy tWR past the last beat
        # (plus the closed-page auto-precharge, when selected); write-to-
        # read turnaround arms the group's read blocker.
        r3, b3 = qr[p3], qb[p3]
        bank_busy = bank_busy.at[r3, b3].set(
            jnp.where(go_wr,
                      jnp.maximum(bank_busy[r3, b3], t + d + t_wr + wr_extra),
                      bank_busy[r3, b3]))
        grp_wr_until = grp_wr_until.at[g].set(
            jnp.where(go_wr, t + d + t_wtr, grp_wr_until[g]))
        grp_last_wr = grp_last_wr.at[g].set(
            jnp.where(go, qwr[p3], grp_last_wr[g]))
        # turnaround-stall attribution: the group's bus is free, nothing
        # was granted, and at least one read passed every filter except
        # the write-to-read window — a cycle direction batching exists
        # to win back.  Gated like the other per-cycle counters so it
        # freezes at the makespan.
        stall = (grp_busy[g] <= t) & ~go & (base3 & ~wtr_ok).any()
        wtr_stall = wtr_stall + jnp.where(aux["work_left"] & stall, 1, 0)
        bus_cycles = bus_cycles + jnp.where(go, d, 0)
        wr_bus_cycles = wr_bus_cycles + jnp.where(go_wr, d, 0)
        n_grants = n_grants + jnp.where(go, 1, 0)
        n_slot_grants = n_slot_grants + jnp.where(go & slot_match[p3], 1, 0)
    st.update(qphase=qphase, qdone=qdone, bank_busy=bank_busy,
              grp_busy=grp_busy, grp_wr_until=grp_wr_until,
              grp_last_wr=grp_last_wr,
              bus_cycles=bus_cycles, wr_bus_cycles=wr_bus_cycles,
              n_grants=n_grants, n_slot_grants=n_slot_grants,
              n_ecc_reread=n_ecc, wtr_stall=wtr_stall)
    return st, aux


def _stage_retire(st, aux, t, ctx):
    """Retire completed transfers out of order; free window slots (tags)
    and MSHRs.  `n_ooo_retire` counts retires completing ahead of an
    older outstanding tag from the same core — the split-transaction
    observable (nonzero even at window=1 under FR-FCFS, which already
    completes across banks out of order; the tagged window makes it
    measurable and lets `OooSelect` widen it deliberately)."""
    n_cores, Wd = ctx["n_cores"], ctx["Wd"]
    qv, qphase, qdone, qwr = (st["qv"], st["qphase"], st["qdone"],
                              st["qwr"])
    fin = qv & (qphase == 4) & (qdone <= t)
    # per-core reductions over each core's own Wd-slot window segment
    by_core = lambda x: x.reshape(n_cores, Wd)  # noqa: E731
    fin_per_core = by_core(jnp.where(fin, 1, 0)).sum(axis=1)
    st["served"] = st["served"] + fin_per_core
    st["c_finish"] = jnp.maximum(st["c_finish"], by_core(
        jnp.where(fin, t, -1)).max(axis=1))
    st["c_out"] = st["c_out"] - fin_per_core
    st["n_wr"] = st["n_wr"] + jnp.where(fin & qwr, 1, 0).sum()
    # a retire is out-of-order when the same core still has an older tag
    # in flight (valid, not retiring this cycle)
    rem_tag = jnp.where(qv & ~fin, st["qtag"], BIG)
    min_rem = by_core(rem_tag).min(axis=1, keepdims=True)
    st["n_ooo_retire"] = st["n_ooo_retire"] + jnp.where(
        by_core(fin) & (min_rem < by_core(st["qtag"])), 1, 0).sum()
    st["qv"] = qv & ~fin
    st["qphase"] = jnp.where(fin, 0, qphase)
    return st, aux


def _stage_progress(st, aux, t, ctx):
    """Core progress: oldest outstanding instruction per core limits the
    runahead window.  A core's instruction counter freezes once its fixed
    work is done: post-completion progress never feeds back into the
    simulation (no requests left to arrive) and would otherwise make the
    `inst` metric depend on how far past the makespan the scan runs — the
    one obstacle to horizon-independent (early-exit) execution."""
    n_cores, n_req, core = ctx["n_cores"], ctx["n_req"], ctx["core"]
    tr_inst = ctx["traces"]["inst"]
    inst_or_big = jnp.where(st["qv"], st["qinst"], jnp.float32(1e30))
    oldest = inst_or_big.reshape(n_cores, ctx["Wd"]).min(axis=1)
    window_ok = (st["c_inst"] - oldest) < core.inst_window
    nxt_inst = jnp.where(st["c_next"] < n_req,
                         tr_inst[jnp.arange(n_cores),
                                 jnp.minimum(st["c_next"], n_req - 1)],
                         jnp.float32(1e30))
    advance = window_ok & (st["served"] < n_req)
    st["c_inst"] = jnp.minimum(
        st["c_inst"] + jnp.where(advance, core.inst_per_fast_cycle, 0.0),
        nxt_inst)
    return st, aux


def _stage_power(st, aux, t, ctx):
    """Power-down and self-refresh residency.

    A real rank with no busy bank and no queued request is idle; after
    t_pd consecutive idle cycles it is counted in power-down.  Under
    `SelfRefreshPolicy.ENABLED` a rank idle t_sr consecutive cycles with
    no outstanding refresh debt drops below power-down into self-refresh:
    it refreshes internally (`_stage_refresh` suspends its deadlines) and
    stays there until a request targets it, at which point the exit
    charges t_xsr before any bank can serve and the external deadline
    restarts one full interval after the exit completes (the internal
    refresh just covered the rank).  A self-refreshing rank-cycle counts
    in sr_cycles and never also in pd_cycles — the two residencies (and
    refresh blackout, which keeps banks busy) are disjoint by
    construction."""
    R, pol = ctx["R"], ctx["pol"]
    pending = policies.counts_by(st["qv"], st["qr"], R) > 0
    rank_idle = (st["bank_busy"] <= t).all(axis=1) & ~pending \
        & ctx["real_rank"]
    st["idle_since"] = jnp.where(rank_idle, st["idle_since"], t + 1)
    idle_for = t - st["idle_since"]
    enter = pol["sr"] & rank_idle & (idle_for >= ctx["t_sr"]) \
        & (st["ref_debt"] == 0)
    exit_ = st["in_sr"] & pending
    in_sr = (st["in_sr"] | enter) & ~exit_
    st["bank_busy"] = jnp.where(
        exit_[:, None], jnp.maximum(st["bank_busy"], t + ctx["t_xsr"]),
        st["bank_busy"])
    st["ref_next"] = jnp.where(exit_, t + ctx["t_xsr"] + ctx["t_refi_eff"],
                               st["ref_next"])
    st["in_sr"] = in_sr
    st["n_sr_exit"] = st["n_sr_exit"] + jnp.where(
        aux["work_left"], exit_.sum(), 0)
    st["sr_cycles"] = st["sr_cycles"] + jnp.where(
        aux["work_left"], in_sr.sum(), 0)
    in_pd = rank_idle & (idle_for >= ctx["t_pd"]) & ~in_sr
    st["pd_cycles"] = st["pd_cycles"] + jnp.where(
        aux["work_left"], in_pd.sum(), 0)
    return st, aux


#: the controller pipeline, in execution order (order is load-bearing:
#: the golden grid pins the exact cycle-level semantics it produces)
_STAGES = (_stage_refresh, _stage_enqueue, _stage_schedule,
           _stage_transfer, _stage_retire, _stage_progress, _stage_power)

#: prefix of every name scope `_sim_core` puts on its ops
SCOPE_PREFIX = "smla."
#: scope of the live-step gate, which keeps a step past the horizon from
#: changing the state
GATE_SCOPE = "gate"
#: the attributable name scopes of one fast cycle, each under
#: `SCOPE_PREFIX`: the seven stages (a stage's function name without
#: ``_stage_``) and the gate.  A profiler trace of the device reduces by
#: these names (``bench/lib/probe.py``).
STAGE_SCOPES = tuple(f.__name__.removeprefix("_stage_")
                     for f in _STAGES) + (GATE_SCOPE,)
#: scope of the chunk loop's own control (its condition and the chunk's
#: cycle numbers): not a stage, so a reduction counts it as unscoped
LOOP_SCOPE = "loop"


def _scope(name: str):
    """`jax.named_scope` of one of `_sim_core`'s parts: trace-time
    metadata on the ops, with no effect on the compiled program."""
    return jax.named_scope(SCOPE_PREFIX + name)


def _sim_core(params: dict, traces: dict, horizon: int, core: CoreParams,
              banks: int, chunk: int | None = None) -> dict:
    """One full simulation; every config quantity in `params` — including
    the controller-policy selectors — is traced.

    traces: dict of (n_cores, n_req_max) arrays; the cell's real request
    count is params['n_req'] (padding beyond it is never read).

    `chunk` fast cycles are scanned per while-loop iteration; the loop
    exits at the first chunk boundary where all cores completed their
    fixed work (or at the horizon).  `chunk=None` means one full-horizon
    chunk.  Results are bit-identical across chunk sizes; only the
    `chunks_run` diagnostic varies.
    """
    n_cores, n_req_max = traces["inst"].shape
    R = params["dur"].shape[0]                      # padded rank count
    B = banks
    Q = core.q_size
    # tagged transaction window: each core owns a private segment of Wd
    # slots in one flat (n_cores * Wd,) array; `q_size` is the shared
    # credit cap on total occupancy.  window=1 admits exactly the
    # historical shared queue (see `_stage_enqueue`).
    Wd = min(core.mshr * max(int(core.window), 1), Q)
    QT = n_cores * Wd
    n_req = params["n_req"]
    t_refi, t_rfc = params["t_refi"], params["t_rfc"]
    pol = policies.selector_view(params)
    refresh_en = t_refi > 0
    t_refi_eff, t_rfc_eff = policies.refresh_timings(pol, t_refi, t_rfc, B,
                                                     refresh_en)
    # weak-retention derating (faults.FaultConfig.weak_ranks): JEDEC
    # 2x/4x tREFI shortening per rank.  All-ones derate broadcasts the
    # historical scalar interval to (R,) with identical values, so the
    # clean path stays bit-identical; the refresh_en guard keeps a
    # disabled refresh (t_refi == 0) disabled.
    derate = params["ref_derate"]
    t_refi_eff = jnp.where((derate > 1) & refresh_en,
                           jnp.maximum(t_refi_eff // jnp.maximum(derate, 1),
                                       1),
                           t_refi_eff)
    wq_hi, wq_lo = policies.drain_watermarks(Q, n_cores, core.mshr,
                                             core.window)
    # DVFS-style per-layer clock gating: under LayerClockPolicy.GATED each
    # rank's transfer duration stretches by its traced divider (ones for
    # every organisation without private per-layer links, so the default
    # path is bit-identical).  Applied once here — every stage reads the
    # effective duration through ctx["dur"].
    dur_eff = jnp.where(pol["clk_gated"],
                        params["dur"] * params["clk_div"], params["dur"])
    ctx = {
        "n_cores": n_cores, "R": R, "B": B, "L": params["layers"],
        "core": core, "n_req": n_req,
        "t_rcd": params["t_rcd"], "t_rp": params["t_rp"],
        "t_cl": params["t_cl"], "t_wr": params["t_wr"],
        "t_wtr": params["t_wtr"], "t_pd": params["t_pd"],
        "t_sr": params["t_sr"], "t_xsr": params["t_xsr"],
        "refresh_en": refresh_en,
        "t_refi_eff": t_refi_eff, "t_rfc_eff": t_rfc_eff,
        "dur": dur_eff, "group_of_rank": params["group_of_rank"],
        "slotted": params["slotted"], "ecc_every": params["ecc_every"],
        "real_rank": jnp.arange(R, dtype=jnp.int32) < params["n_ranks"],
        "pol": pol,
        "wq_hi": wq_hi, "wq_lo": wq_lo,
        # window layout: the owning core of each flat slot is a static
        # function of position (slot // Wd) — no per-entry core field, and
        # a per-core reduction is a (n_cores, Wd) reshape
        "Wd": Wd,
        "traces": {
            "inst": traces["inst"].astype(jnp.float32),
            "rank": traces["rank"].astype(jnp.int32) % params["n_ranks"],
            "bank": traces["bank"].astype(jnp.int32) % B,
            "row": traces["row"].astype(jnp.int32),
            "wr": traces["wr"].astype(jnp.int32) != 0,
        },
    }

    def step(st, t):
        t = t.astype(jnp.int32)
        aux = {"work_left": (st["served"] < n_req).any()}
        for stage in _STAGES:
            with _scope(stage.__name__.removeprefix("_stage_")):
                st, aux = stage(st, aux, t, ctx)
        return st, None

    i32 = jnp.int32
    st = dict(
        qv=jnp.zeros(QT, bool), qtag=jnp.zeros(QT, i32),
        qr=jnp.zeros(QT, i32), qb=jnp.zeros(QT, i32),
        qrow=jnp.zeros(QT, i32), qinst=jnp.zeros(QT, jnp.float32),
        qarr=jnp.zeros(QT, i32), qphase=jnp.zeros(QT, i32),
        qready=jnp.zeros(QT, i32), qdone=jnp.zeros(QT, i32),
        qwr=jnp.zeros(QT, bool), whit=jnp.zeros(QT, bool),
        bank_busy=jnp.zeros((R, B), i32),
        bank_row=-jnp.ones((R, B), i32),
        grp_busy=jnp.zeros(R, i32),
        grp_wr_until=jnp.zeros(R, i32),
        grp_last_wr=jnp.zeros(R, bool),
        # stagger refresh across ranks (rank r's first tREFI deadline at
        # (r+1)/n_ranks of the interval) — synchronized deadlines would
        # black out the whole channel every tREFI, which real controllers
        # avoid; padded ranks are gated by real_rank regardless.
        ref_next=(t_refi_eff * (jnp.arange(R, dtype=i32)
                                % jnp.maximum(params["n_ranks"], 1) + 1)
                  // jnp.maximum(params["n_ranks"], 1)).astype(i32),
        ref_until=jnp.zeros((R, B), i32),
        ref_bank=jnp.zeros(R, i32),
        ref_debt=jnp.zeros(R, i32),
        in_sr=jnp.zeros(R, bool),
        idle_since=jnp.zeros(R, i32),
        draining=jnp.zeros((), bool),
        c_inst=jnp.zeros(n_cores, jnp.float32),
        c_next=jnp.zeros(n_cores, i32), c_out=jnp.zeros(n_cores, i32),
        served=jnp.zeros(n_cores, i32), c_finish=jnp.zeros(n_cores, i32),
        n_act=jnp.zeros((), i32), n_conflict=jnp.zeros((), i32),
        bus_cycles=jnp.zeros((), i32), wr_bus_cycles=jnp.zeros((), i32),
        n_wr=jnp.zeros((), i32), refresh_cycles=jnp.zeros((), i32),
        ref_rank_blocked=jnp.zeros((), i32),
        ref_postponed=jnp.zeros((), i32), ref_pulled_in=jnp.zeros((), i32),
        ref_debt_max=jnp.zeros((), i32),
        pd_cycles=jnp.zeros((), i32),
        sr_cycles=jnp.zeros((), i32), n_sr_exit=jnp.zeros((), i32),
        n_drain_bursts=jnp.zeros((), i32),
        n_grants=jnp.zeros((), i32), n_slot_grants=jnp.zeros((), i32),
        n_ecc_reread=jnp.zeros((), i32),
        n_row_hit=jnp.zeros((), i32), wtr_stall=jnp.zeros((), i32),
        n_ooo_retire=jnp.zeros((), i32),
    )
    # ---- chunked execution with early exit --------------------------------
    # Fixed-width scan chunks under a while loop: exit at the first chunk
    # boundary where every core's fixed work is done.  Steps with
    # t >= horizon (final partial chunk only) are gated to exact no-ops, so
    # any chunk size replays the full-horizon scan cycle-for-cycle up to
    # the exit point — and past it every metric is provably frozen
    # (`work_left` gating, empty queue, per-core c_inst freeze).
    chunk_c = effective_chunk(horizon, chunk)
    k_max = n_chunks(horizon, chunk)

    def gated_step(s, t):
        # step() writes into its argument dict, so hand it a shallow copy
        # to keep `s` as the pre-step state the gate can fall back to.
        new_s, _ = step(dict(s), t)
        with _scope(GATE_SCOPE):
            live = t < horizon
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(live, n, o), new_s, s), None

    def loop_cond(carry):
        s, k = carry
        # postponed-refresh debt must drain before the loop may exit: the
        # post-makespan pull-ins run in these extra cycles with every
        # fixed-work metric already frozen, so `ref_debt_end == 0` is a
        # testable invariant under any chunk width.  Debt is identically
        # zero under the default (strict) policy — the condition then
        # reduces to the historical work-only predicate bit-for-bit.
        with _scope(LOOP_SCOPE):
            return (k < k_max) & ((s["served"] < n_req).any()
                                  | (s["ref_debt"] > 0).any())

    def loop_body(carry):
        s, k = carry
        with _scope(LOOP_SCOPE):
            ts = k * chunk_c + jnp.arange(chunk_c, dtype=jnp.int32)
        s, _ = jax.lax.scan(gated_step, s, ts)
        return s, k + 1

    final, chunks_run = jax.lax.while_loop(loop_cond, loop_body,
                                           (st, jnp.int32(0)))
    served, c_finish, c_inst = (final["served"], final["c_finish"],
                                final["c_inst"])

    unit_ns = params["unit_ns"]
    t_ns = horizon * unit_ns
    complete = served >= n_req                       # per-core fixed work
    # fixed-work IPC: total trace instructions / per-core completion time
    finish_ns = jnp.maximum(c_finish, 1) * unit_ns
    total_inst = ctx["traces"]["inst"][jnp.arange(n_cores), n_req - 1]
    ipc = jnp.where(complete, total_inst / (finish_ns * 3.2),
                    c_inst / (t_ns * 3.2))           # fallback: horizon
    makespan_ns = jnp.max(jnp.where(complete, finish_ns, t_ns))
    bw = (served.sum() * params["request_bytes"]
          / makespan_ns)                             # GB/s over work
    makespan_cycles = makespan_ns / unit_ns
    n_ranks_f = params["n_ranks"].astype(jnp.float32)
    return {
        "ipc": ipc,
        "served": served,
        "complete": complete,
        "bandwidth_gbps": bw,
        "n_act": final["n_act"],
        "n_row_conflicts": final["n_conflict"],
        "n_wr": final["n_wr"],
        "bus_cycles": final["bus_cycles"],
        "wr_bus_cycles": final["wr_bus_cycles"],
        "refresh_cycles": final["refresh_cycles"],
        "ref_rank_blocked_cycles": final["ref_rank_blocked"],
        "ref_postponed": final["ref_postponed"],
        "ref_pulled_in": final["ref_pulled_in"],
        "ref_debt_max": final["ref_debt_max"],
        "ref_debt_end": final["ref_debt"].sum(),
        "pd_cycles": final["pd_cycles"],
        "pd_frac": (final["pd_cycles"].astype(jnp.float32)
                    / jnp.maximum(makespan_cycles * n_ranks_f, 1.0)),
        "sr_cycles": final["sr_cycles"],
        "sr_frac": (final["sr_cycles"].astype(jnp.float32)
                    / jnp.maximum(makespan_cycles * n_ranks_f, 1.0)),
        "n_sr_exit": final["n_sr_exit"],
        "n_drain_bursts": final["n_drain_bursts"],
        "n_grants": final["n_grants"],
        "n_slot_grants": final["n_slot_grants"],
        # fault diagnostics: ECC re-reads granted, and the degradation-
        # mode selector echoed back so sweep rows are self-describing
        "n_ecc_reread": final["n_ecc_reread"],
        "degrade_sel": params["degrade_sel"],
        # OoO window attribution: CAS issues that hit the open row, bus
        # cycles lost to write-to-read turnaround with a read waiting,
        # and retires completing ahead of an older same-core tag
        "n_row_hit": final["n_row_hit"],
        "wtr_stall_cycles": final["wtr_stall"],
        "n_ooo_retire": final["n_ooo_retire"],
        "n_enqueued": final["c_next"].sum(),
        "n_outstanding": jnp.where(final["qv"], 1, 0).sum(),
        "bus_util": final["bus_cycles"] / jnp.maximum(
            makespan_cycles
            * jnp.maximum(params["n_groups"], 1).astype(jnp.float32), 1),
        "horizon_ns": jnp.asarray(t_ns, jnp.float32),
        "makespan_ns": makespan_ns,
        "inst": c_inst,
        # diagnostic: scan chunks actually executed (< ceil(horizon/chunk)
        # when early exit engaged).  The only metric that may legitimately
        # differ across chunk sizes.
        "chunks_run": chunks_run,
    }


# ----------------------------------------------------------------------------
# compile cache
# ----------------------------------------------------------------------------

_COMPILE_COUNT = [0]

#: params every trace/param dict must carry; used to default legacy inputs.
_TIMING_DEFAULTS = ("t_wr", "t_wtr", "t_refi", "t_rfc", "t_pd", "t_sr",
                    "t_xsr", "ecc_every")

#: timing keys whose legacy default is "never" (BIG), not "disabled" (0):
#: an idleness threshold of 0 would mean *instant* power-down/self-refresh
#: (and an ECC cadence of 0 would divide by zero — BIG means no re-reads).
_NEVER_DEFAULTS = ("t_pd", "t_sr", "ecc_every")


def compile_count() -> int:
    """Misses of the executable cache (`_compiled`) so far: one per
    static signature, whether XLA then compiled it or loaded it from the
    persistent cache.  `compile_stats` tells the two apart."""
    return _COMPILE_COUNT[0]


def reset_compile_count() -> None:
    """Rebase the compile counter and `compile_stats` (the executable
    cache itself is kept, so this never *causes* recompiles).  Tests
    assert on deltas around this — the process-global absolute value is
    order-dependent across tests."""
    _COMPILE_COUNT[0] = 0
    for k in _XLA_STATS:
        _XLA_STATS[k] = 0


@dataclasses.dataclass(frozen=True)
class CompileStats:
    """What building the engine's executables cost, since the last
    `reset_compile_count` (``compile_stats()``)."""
    #: misses of the executable cache, as `compile_count`
    lru_misses: int
    #: executables XLA compiled (persistent-cache misses, or no cache)
    xla_compiles: int
    #: executables loaded from the persistent compilation cache
    cache_loads: int
    #: seconds in XLA compiles, and in persistent-cache loads
    compile_s: float
    load_s: float
    #: seconds tracing the engine to a jaxpr and lowering it to MLIR
    trace_lower_s: float


#: the `jax.monitoring` events `compile_stats` reads
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
#: `CompileStats` fields past `lru_misses`, since the last reset
_XLA_STATS = dict(xla_compiles=0, cache_loads=0, compile_s=0.0,
                  load_s=0.0, trace_lower_s=0.0)
_XLA_LISTENING = [False]
#: per thread: whether it is inside a call of an engine executable
#: (`_engine_call`), and whether the backend compile in progress there
#: loaded from the persistent cache
_XLA_CURRENT = threading.local()


@contextlib.contextmanager
def _engine_call():
    """Count this thread's compile events as the engine's while the
    block runs: the call of an executable `_compiled` returned, which
    traces, lowers and compiles (or loads) that executable on its first
    call and nothing else.  JAX's events cannot say so themselves: a
    cache hit names no function, and the unbatched path's is unnamed."""
    _XLA_CURRENT.engine = True
    try:
        yield
    finally:
        _XLA_CURRENT.engine = False


def _on_compile_start(event: str, _value, **_kwargs) -> None:
    if event == _BACKEND_COMPILE:
        _XLA_CURRENT.loaded = False


def _on_event(event: str, **_kwargs) -> None:
    if event == _CACHE_HIT:
        _XLA_CURRENT.loaded = True


def _on_duration(event: str, secs: float, **_kwargs) -> None:
    if not getattr(_XLA_CURRENT, "engine", False):
        return
    if event == _BACKEND_COMPILE:
        if getattr(_XLA_CURRENT, "loaded", False):
            _XLA_STATS["cache_loads"] += 1
            _XLA_STATS["load_s"] += secs
        else:
            _XLA_STATS["xla_compiles"] += 1
            _XLA_STATS["compile_s"] += secs
    elif event in _TRACE_LOWER:
        _XLA_STATS["trace_lower_s"] += secs


def _listen_to_compiles() -> None:
    """Register the `jax.monitoring` listeners behind `compile_stats`,
    once per process, before the engine's first compile."""
    if _XLA_LISTENING[0]:
        return
    jax.monitoring.register_scalar_listener(_on_compile_start)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _XLA_LISTENING[0] = True


def compile_stats() -> CompileStats:
    """Compiles and persistent-cache loads of the engine's executables
    since the last `reset_compile_count`, from JAX's compile events
    (``jax.monitoring``) inside the engine's own calls; compiles of
    anything else in the process are not counted."""
    return CompileStats(lru_misses=_COMPILE_COUNT[0], **_XLA_STATS)


def _with_wr(traces: dict) -> dict:
    """Default a missing write field to all-reads.

    Must happen OUTSIDE the jitted function: a changed dict structure would
    re-trace without registering in the compile counter."""
    if "wr" in traces:
        return traces
    t = dict(traces)
    t["wr"] = jnp.zeros(t["inst"].shape, jnp.int32)
    return t


def _with_timing_defaults(params: dict) -> dict:
    """Default missing write/refresh timings to 0 (disabled), missing
    idleness thresholds to effectively-never (`_NEVER_DEFAULTS`), and
    missing policy selectors to the paper's controller (all zeros): a
    legacy params dict must reproduce the pre-write-era, pre-policy
    engine exactly."""
    missing = [k for k in _TIMING_DEFAULTS if k not in params]
    missing += [k for k in policies.SELECTOR_KEYS if k not in params]
    need_div = "clk_div" not in params
    need_derate = "ref_derate" not in params
    if not missing and not need_div and not need_derate:
        return params
    p = dict(params)
    for k in missing:
        fill = BIG if k in _NEVER_DEFAULTS else 0
        p[k] = jnp.full(np.shape(p["t_cl"]), fill, jnp.int32)
    if need_div:
        # dur-shaped, not t_cl-shaped: the clock-gating dividers multiply
        # the per-rank transfer durations; ones = ungated
        p["clk_div"] = jnp.ones(np.shape(p["dur"]), jnp.int32)
    if need_derate:
        # dur-shaped like clk_div: per-rank tREFI derating; ones = nominal
        p["ref_derate"] = jnp.ones(np.shape(p["dur"]), jnp.int32)
    return p


#: metrics SimOptions(validate=True) guards: every float must be finite,
#: every cycle/event counter non-negative.  Applied to the program's
#: *outputs*, so the same guards serve the scan pipeline and the Pallas
#: kernel uniformly.
_VALIDATE_FINITE = ("bandwidth_gbps", "ipc", "bus_util", "pd_frac",
                    "sr_frac", "makespan_ns")
_VALIDATE_NONNEG = ("makespan_ns", "served", "bus_cycles", "wr_bus_cycles",
                    "refresh_cycles", "pd_cycles", "sr_cycles", "n_grants",
                    "n_act", "n_wr", "n_ecc_reread", "ref_debt_end",
                    "n_row_hit", "wtr_stall_cycles", "n_ooo_retire",
                    "chunks_run")


def _validate_metrics(out: dict) -> None:
    """checkify NaN / negative-cycle guards over a metrics dict (batched
    or single-cell: `jnp.all` reduces over whatever axes exist)."""
    from jax.experimental import checkify
    for k in _VALIDATE_FINITE:
        checkify.check(jnp.all(jnp.isfinite(out[k])),
                       f"validate: non-finite {k}")
    for k in _VALIDATE_NONNEG:
        checkify.check(jnp.all(out[k] >= 0), f"validate: negative {k}")


def cell_mesh(n_dev: int) -> jax.sharding.Mesh:
    """1-D ``cells`` mesh over the first `n_dev` devices.  The axis is
    Auto: under an Explicit axis (``jax.make_mesh``'s default) the
    engine's scatters would need an ``out_sharding`` on every ``.at[]``
    update, while Auto leaves their sharding to the partitioner."""
    return jax.make_mesh((n_dev,), ("cells",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n_dev])


@functools.lru_cache(maxsize=None)
def _compiled(options: SimOptions, core: CoreParams, banks: int,
              shapes_key: tuple, batched: bool, shard: int = 0):
    """One jitted executable per static signature.

    shapes_key pins (n_cells, n_cores, n_req_max, r_max); `options` (with
    the chunk already resolved — never AUTO) carries the remaining static
    quantities (horizon, chunk, backend, interpret, validate), so each
    cache miss corresponds to exactly one XLA compilation of the returned
    function.  Under ``validate=True`` only the *output guards* are
    transformed through `checkify` — the simulation itself (whose
    batched `lax.while_loop` checkify cannot transform) runs untouched,
    the checks consume its metrics dict inside the same jit, and the
    wrapper re-raises any tripped guard on the host — still exactly one
    compile per signature.

    ``shard > 1`` selects the *reduce-tree cond* multi-device path: the
    vmapped pipeline is wrapped in a fully-manual ``shard_map`` over the
    cell axis, so each of the first `shard` devices runs its own chunked
    ``while_loop`` whose early-exit cond reduces only over its local cell
    shard — no cross-device all-reduce per chunk, and a device whose
    shard finishes early stops issuing chunks instead of spinning until
    the globally slowest cell exits.  Metrics (including ``chunks_run``,
    which becomes per-shard) stay bit-identical to the single-device
    path because each cell still freezes at its own exit point.  The
    stacked cell axis must be divisible by `shard` (``sweep.run_sweep``
    rounds bucket sizes up to a device multiple).
    """
    assert options.chunk != AUTO, "resolve AUTO before the compile cache"
    if shard > 1 and options.backend != "scan":
        raise ValueError(
            f"local-cond cell sharding (shard={shard}) is only available "
            f"on the scan backend; backend={options.backend!r} shards "
            f"through the global-cond NamedSharding path instead")
    _COMPILE_COUNT[0] += 1
    _listen_to_compiles()
    if options.backend == "pallas":
        from repro.core.smla import pallas_engine   # lazy: imports us back
        raw = functools.partial(
            pallas_engine.sim_cell_blocks, horizon=options.horizon,
            core=core, banks=banks, chunk=options.chunk,
            interpret=options.interpret)
        if batched:
            base = raw
        else:
            def base(params, traces):
                lift = functools.partial(jax.tree_util.tree_map,
                                         lambda x: jnp.asarray(x)[None])
                out = raw(lift(params), lift(traces))
                return jax.tree_util.tree_map(lambda x: x[0], out)
    else:
        fn = functools.partial(_sim_core, horizon=options.horizon,
                               core=core, banks=banks, chunk=options.chunk)
        base = jax.vmap(fn) if batched else fn
        if shard > 1:
            pspec = jax.sharding.PartitionSpec("cells")
            # check_vma=False: the replication checker has no rule for
            # while_loop; manual sharding is still valid — every output
            # carries the partitioned cell axis.
            base = jax.shard_map(base, mesh=cell_mesh(shard),
                                 in_specs=(pspec, pspec),
                                 out_specs=pspec, check_vma=False)
    if not options.validate:
        return jax.jit(base)
    from jax.experimental import checkify

    def _checked(out):
        _validate_metrics(out)
        return out
    check = checkify.checkify(_checked, errors=checkify.user_checks)

    def guarded(params, traces):
        # checkify wraps only the output guards (pure elementwise checks),
        # never the simulation's while-loop, so it lowers on both backends
        # batched or not
        return check(base(params, traces))
    cfn = jax.jit(guarded)

    def run(params, traces):
        err, out = cfn(params, traces)
        err.throw()
        return out
    return run


def batched_simulate(params: dict, traces: dict,
                     options: SimOptions, core: CoreParams,
                     banks: int, *,
                     local_cond_devices: int = 0) -> dict:
    """Run a stacked batch of cells: every leaf has a leading cell axis.

    `options` is the execution surface (`SimOptions`).  Inputs may carry
    a per-device sharding over the cell axis (see ``sweep.run_sweep``);
    the jitted program then partitions along it.
    ``local_cond_devices=n > 1`` instead compiles the reduce-tree cond
    path: a fully-manual shard_map over the first `n` devices where each
    device's while_loop exits on its *local* shard (scan backend only;
    n_cells must be divisible by n)."""
    fn, args = _batched_call(params, traces, options, core, banks,
                             local_cond_devices, "batched_simulate")
    with _engine_call():
        return fn(*args)


def batched_executable(params: dict, traces: dict,
                       options: SimOptions, core: CoreParams,
                       banks: int, *,
                       local_cond_devices: int = 0) -> tuple:
    """The program `batched_simulate` runs for these arguments, as one
    object: ``(compiled, args)``, where ``compiled(*args)`` makes the
    same call and ``compiled.as_text()`` is the optimised module whose
    instruction names a profiler's device op events carry.  Where
    `batched_simulate` ran these shapes before, it is the executable JAX
    already holds, and nothing is built; else `compile_stats` counts its
    compile or persistent-cache load.  Not under ``validate=True``,
    which wraps the program."""
    fn, args = _batched_call(params, traces, options, core, banks,
                             local_cond_devices, "batched_executable")
    if not hasattr(fn, "lower"):
        raise ValueError("batched_executable: validate=True wraps the "
                         "program in host-side checks")
    with _engine_call():
        return fn.lower(*args).compile(), args


def _batched_call(params: dict, traces: dict, options: SimOptions,
                  core: CoreParams, banks: int, local_cond_devices: int,
                  fn_name: str) -> tuple:
    """The executable of a batched call, and the arguments it takes."""
    options = _require_options(options, fn_name).resolved()
    _check_backend(options)
    _apply_compile_cache(options)
    shard = int(local_cond_devices) if int(local_cond_devices) > 1 else 0
    n_cells, n_cores, n_req_max = traces["inst"].shape
    if shard and n_cells % shard:
        raise ValueError(f"local_cond_devices={shard}: n_cells={n_cells} "
                         f"must be a device multiple")
    r_max = params["dur"].shape[1]
    fn = _compiled(options, core, banks,
                   (n_cells, n_cores, n_req_max, r_max), True, shard)
    return fn, (_with_timing_defaults(params), _with_wr(traces))


def simulate(stack: StackConfig, traces: dict, options: SimOptions,
             core: CoreParams = CoreParams()) -> dict:
    """traces: dict of (C, n_req) arrays (inst f32; rank/bank/row i32;
    optional wr i32, defaulting to all-reads).  `options` as in
    `batched_simulate`.  Returns metrics dict of scalars / per-core
    arrays (all jnp)."""
    options = _require_options(options, "simulate").resolved()
    _check_backend(options)
    _apply_compile_cache(options)
    n_cores, n_req = traces["inst"].shape
    params = stack.to_params()
    params["n_req"] = np.int32(n_req)
    fn = _compiled(options, core, stack.banks_per_rank,
                   (1, n_cores, n_req, stack.n_ranks), False)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    traces = _with_wr({k: jnp.asarray(v) for k, v in traces.items()})
    with _engine_call():
        return fn(params, traces)
