"""Plain reference of one 3D-stacked DRAM channel and its cores, against
which the benchmark decides ``correct``.

One cell at a time, request by request, one fast cycle after another,
in plain Python: no vectors, no padding, no batching.  It imports
nothing of the program.  Its channel comes from ``params.py`` (the
paper's Table 2 timings, worked out from the benchmark's configuration
file), its traces from the benchmark's generator.

What happens in one fast cycle ``t``, in this order:

1. Refresh.  Each rank's refresh falls due every tREFI (per-bank
   refresh: every tREFI / banks, one bank at a time, round robin, for
   tRFC / 2).  The first deadlines are staggered: rank r's at
   (r + 1) / ranks of the interval.  A due rank starts its refresh once
   the banks it covers are idle and hold no issued request; until then
   no new column command goes to them.  A refresh closes the rows it
   covers and keeps them busy for tRFC.  Under 8x postponing a deadline
   that finds requests waiting for the rank is deferred, up to 8 owed;
   an owed refresh is pulled in when the rank has none waiting.  A rank
   in self-refresh has no external deadlines.
2. Arrival.  One core a cycle, round robin, may hand its next request to
   the controller: once its instruction count has reached the request's,
   while it has fewer than mshr x window requests outstanding and the
   controller holds fewer than q_size.
3. Column command.  At most one a cycle, to a waiting request whose bank
   is idle.  FR-FCFS takes row hits first, then the oldest; FCFS the
   oldest.  A write in a drain burst comes before everything.  The
   request's data is ready after tCL (row hit), tRCD + tCL (bank
   closed) or tRP + tRCD + tCL (another row open).  Open page leaves the
   row open and frees the bank when the data is ready; closed page
   precharges, so the bank stays busy tRP longer and no row stays open.
   Drain when full holds writes back until the writes held reach 3/4 of
   the reachable queue, then drains them down to 1/4; opportunistic
   lets writes go while 1/4 or more wait; both let writes go when no
   read can.
4. Transfer.  Each bus starts at most one transfer a cycle, the oldest
   ready request first.  It holds the bus for the rank's transfer time.
   A write keeps its bank busy for tWR after its last beat (and tRP
   more under closed page), and blocks reads on its bus for tWTR.
   Cascaded SLR: a rank may start only in its own time slot.
   Out-of-order selection (row grouping, direction batching) adds its
   preferences below the scheduler's.
5. Retire.  A finished transfer frees its queue entry and its MSHR.
6. Core progress.  A core retires 12 instructions a fast cycle while its
   oldest outstanding request is less than the instruction window
   behind it, and never passes its next request's instruction count.
7. Power.  A rank with idle banks and no queued request is idle; after
   t_pd idle cycles it counts as powered down.  Under self-refresh, after
   t_sr idle cycles with no refresh owed it enters self-refresh, and the
   next request for it pays t_xsr to leave.

Counters that describe the work stop once every core has served its
``n_req`` requests; the cycle loop runs on until no refresh is owed.

``fdtype`` is the type of the instruction counts.  The configuration
states float32; the control of the comparison runs with bfloat16.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os

import numpy as np

from bench.reference.params import Channel

#: score tiers of the column-command and transfer choices: a tier's
#: bonus always outweighs any difference in arrival time
DRAIN_BONUS = 3 << 29
HIT_BONUS = 1 << 30
ROW_BONUS = 1 << 28
DIR_BONUS = 1 << 27
#: most refreshes a rank may owe under 8x postponing
DEBT_CAP = 8
#: most worker processes of ``simulate_many``: a run compares 8 cells
WORKERS = 8


class Request:
    __slots__ = ("core", "tag", "rank", "bank", "row", "inst", "write",
                 "arrival", "phase", "ready", "done", "hit")

    # phase: 1 waiting for its column command, 2 column command issued,
    # 3 data ready, 4 on the bus

    def __init__(self, core, tag, rank, bank, row, inst, write, arrival):
        self.core, self.tag = core, tag
        self.rank, self.bank, self.row = rank, bank, row
        self.inst, self.write, self.arrival = inst, write, arrival
        self.phase, self.ready, self.done, self.hit = 1, 0, 0, False


class Sim:
    def __init__(self, ch: Channel, traces: dict, core: dict, horizon: int,
                 fdtype=np.float32):
        self.ch, self.horizon = ch, horizon
        self.f = fdtype
        pol = ch.policy
        self.fcfs = pol["scheduler"] == "FCFS"
        self.closed_page = pol["row"] == "CLOSED_PAGE"
        self.per_bank = pol["refresh_gran"] == "PER_BANK"
        self.drain_full = pol["write_drain"] == "DRAIN_WHEN_FULL"
        self.drain_opp = pol["write_drain"] == "OPPORTUNISTIC"
        self.self_refresh = pol["self_refresh"] == "ENABLED"
        self.postpone = pol["ref_postpone"] == "POSTPONE_8X"
        self.ooo_row = pol["ooo"] in ("ROW_GROUP", "ROW_DIR")
        self.ooo_dir = pol["ooo"] in ("DIR_BATCH", "ROW_DIR")

        R, B = ch.n_ranks, ch.banks
        self.inst = np.asarray(traces["inst"]).astype(fdtype)
        self.t_rank = (np.asarray(traces["rank"]) % R).tolist()
        self.t_bank = (np.asarray(traces["bank"]) % B).tolist()
        self.t_row = np.asarray(traces["row"]).tolist()
        self.t_wr = (np.asarray(traces["wr"]) != 0).tolist()
        self.n_cores = self.inst.shape[0]
        self.n_req = ch.n_req

        self.mshr = core["mshr"] * core["window"]
        self.q_size = core["q_size"]
        self.inst_window = fdtype(core["inst_window"])
        self.inst_step = fdtype(core["inst_per_fast_cycle"])
        self.inf = fdtype(1e30)
        reach = max(min(self.q_size, self.n_cores * self.mshr), 1)
        self.wq_hi, self.wq_lo = max(3 * reach // 4, 1), reach // 4

        self.refresh = ch.t_refi > 0
        if self.per_bank:
            self.t_refi = max(ch.t_refi // B, 1) if self.refresh else 0
            self.t_rfc = (ch.t_rfc + 1) // 2
        else:
            self.t_refi, self.t_rfc = ch.t_refi, ch.t_rfc

        self.queue: list[Request] = []
        self.bank_busy = [[0] * B for _ in range(R)]
        self.open_row = [[-1] * B for _ in range(R)]
        self.ref_until = [[0] * B for _ in range(R)]
        self.ref_end = 0          # the latest refresh's end
        self.ref_next = [self.t_refi * (r + 1) // R for r in range(R)]
        self.ref_bank = [0] * R
        self.debt = [0] * R
        self.in_sr = [False] * R
        self.idle_since = [0] * R
        self.bus_busy = [0] * ch.n_buses
        self.bus_wr_until = [0] * ch.n_buses
        self.bus_last_wr = [False] * ch.n_buses
        self.draining = False

        self.c_inst = [fdtype(0)] * self.n_cores
        self.c_next = [0] * self.n_cores
        self.c_out = [0] * self.n_cores
        self.served = [0] * self.n_cores
        self.c_finish = [0] * self.n_cores
        self.n = dict.fromkeys((
            "n_act", "n_row_conflicts", "n_row_hit", "n_wr", "bus_cycles",
            "wr_bus_cycles", "n_grants", "n_slot_grants", "refresh_cycles",
            "ref_rank_blocked_cycles", "ref_postponed", "ref_pulled_in",
            "ref_debt_max", "pd_cycles", "sr_cycles", "n_sr_exit",
            "n_drain_bursts", "wtr_stall_cycles", "n_ooo_retire"), 0)

    # ---- 1. refresh -------------------------------------------------------

    def refresh_stage(self, t: int, work_left: bool):
        """Returns, per rank, whether a refresh is due and waits to
        start (it blocks new column commands), and each rank's target
        bank."""
        ch, R, B = self.ch, self.ch.n_ranks, self.ch.banks
        due = [False] * R
        target = list(self.ref_bank)
        n = self.n
        if not self.refresh or (
                not any(t >= self.ref_next[r] and not self.in_sr[r]
                        for r in range(R)) and not any(self.debt)):
            self.count_refresh(t, work_left)
            return due, target
        held_wr = self.drain_full and not self.draining
        demand = [False] * R
        issued = [[False] * B for _ in range(R)]
        for q in self.queue:
            if not (q.write and held_wr):
                demand[q.rank] = True
            if q.phase >= 2:
                issued[q.rank][q.bank] = True
        for r in range(R):
            if self.in_sr[r]:
                continue
            is_due = t >= self.ref_next[r]
            if (is_due and self.postpone and demand[r]
                    and self.debt[r] < DEBT_CAP):
                self.debt[r] += 1
                self.ref_next[r] += self.t_refi
                is_due = False
                if work_left:
                    n["ref_postponed"] += 1
            tb = target[r]
            if self.per_bank:
                can_start = (self.bank_busy[r][tb] <= t
                             and not issued[r][tb])
            else:
                can_start = (max(self.bank_busy[r]) <= t
                             and not any(issued[r]))
            pull = (self.postpone and not is_due and self.debt[r] > 0
                    and not demand[r] and can_start)
            if (is_due and can_start) or pull:
                covered = [tb] if self.per_bank else range(B)
                for b in covered:
                    self.bank_busy[r][b] = t + self.t_rfc
                    self.ref_until[r][b] = t + self.t_rfc
                    self.open_row[r][b] = -1
                self.ref_end = max(self.ref_end, t + self.t_rfc)
                if pull:
                    self.debt[r] -= 1
                    if work_left:
                        n["ref_pulled_in"] += 1
                else:
                    self.ref_next[r] += self.t_refi
                if self.per_bank:
                    self.ref_bank[r] = (tb + 1) % B
            due[r] = is_due
        n["ref_debt_max"] = max(n["ref_debt_max"], max(self.debt))
        self.count_refresh(t, work_left)
        return due, target

    def count_refresh(self, t: int, work_left: bool):
        """Refresh cycles (one per refresh in progress: a rank under
        all-bank refresh, a bank under per-bank) and rank-cycles with
        every bank refreshing."""
        n = self.n
        if work_left and self.ref_end > t:
            for r in range(self.ch.n_ranks):
                busy = [u > t for u in self.ref_until[r]]
                if all(busy):
                    n["ref_rank_blocked_cycles"] += 1
                n["refresh_cycles"] += (sum(busy) if self.per_bank
                                        else int(all(busy)))

    # ---- 2. arrival -------------------------------------------------------

    def arrival_stage(self, t: int):
        c = t % self.n_cores
        i = self.c_next[c]
        if (i < self.n_req and self.inst[c, i] <= self.c_inst[c]
                and self.c_out[c] < self.mshr
                and len(self.queue) < self.q_size):
            self.queue.append(Request(
                c, i, self.t_rank[c][i], self.t_bank[c][i],
                self.t_row[c][i], self.inst[c, i], self.t_wr[c][i], t))
            self.c_next[c] += 1
            self.c_out[c] += 1

    # ---- 3. column command ------------------------------------------------

    def command_stage(self, t: int, due, target, work_left: bool):
        ch, n = self.ch, self.n
        n_writes = n_wait = 0
        ready = []
        for q in self.queue:
            r = q.rank
            if q.write:
                n_writes += 1
                n_wait += q.phase == 1
            if (q.phase == 1 and self.bank_busy[r][q.bank] <= t
                    and not self.in_sr[r]
                    and not (due[r] and (not self.per_bank
                                         or q.bank == target[r]))):
                ready.append(q)
        was = self.draining
        if n_writes >= self.wq_hi:
            self.draining = True
        elif n_writes <= self.wq_lo:
            self.draining = False
        if work_left and self.draining and not was:
            n["n_drain_bursts"] += 1
        any_read = any(not q.write for q in ready)
        if self.drain_full:
            writes_ok = self.draining or not any_read
        elif self.drain_opp:
            writes_ok = n_wait >= self.wq_lo or not any_read
        else:
            writes_ok = True
        best, best_key = None, None
        for q in ready:
            if q.write and not writes_ok:
                continue
            hit = self.open_row[q.rank][q.bank] == q.row
            if self.drain_full and self.draining and q.write:
                bonus = DRAIN_BONUS
            else:
                bonus = HIT_BONUS if hit and not self.fcfs else 0
            if self.ooo_row and hit:
                bonus += ROW_BONUS
            if (self.ooo_dir and q.write
                    == self.bus_last_wr[ch.bus_of_rank[q.rank]]):
                bonus += DIR_BONUS
            key = (bonus, -q.arrival)
            if best is None or key > best_key:
                best, best_key = q, key
        if best is None:
            return
        q, r, b = best, best.rank, best.bank
        row = self.open_row[r][b]
        if row == q.row:
            lat = ch.t_cl
        elif row < 0:
            lat = ch.t_rcd + ch.t_cl
        else:
            lat = ch.t_rp + ch.t_rcd + ch.t_cl
        q.phase, q.ready, q.hit = 2, t + lat, row == q.row
        if self.closed_page:
            self.open_row[r][b] = -1
            self.bank_busy[r][b] = t + lat + ch.t_rp
        else:
            self.open_row[r][b] = q.row
            self.bank_busy[r][b] = t + lat
        if q.hit:
            n["n_row_hit"] += 1
        else:
            n["n_act"] += 1
            if row >= 0:
                n["n_row_conflicts"] += 1

    # ---- 4. transfer ------------------------------------------------------

    def transfer_stage(self, t: int, work_left: bool):
        ch, n = self.ch, self.n
        on_bus: list[list[Request]] = [[] for _ in range(ch.n_buses)]
        for q in self.queue:
            if q.phase == 2 and q.ready <= t:
                q.phase = 3
            if (q.phase == 3 and self.ref_until[q.rank][q.bank] <= t
                    and (not ch.slotted
                         or t % ch.layers == q.rank % ch.layers)):
                on_bus[ch.bus_of_rank[q.rank]].append(q)
        for g, waiting in enumerate(on_bus):
            turnaround = self.bus_wr_until[g] > t
            free = self.bus_busy[g] <= t
            best, best_key = None, None
            if free:
                for q in waiting:
                    if turnaround and not q.write:
                        continue
                    bonus = 0
                    if self.ooo_row and q.hit:
                        bonus += ROW_BONUS
                    if self.ooo_dir and q.write == self.bus_last_wr[g]:
                        bonus += DIR_BONUS
                    key = (bonus, -q.arrival)
                    if best is None or key > best_key:
                        best, best_key = q, key
            if best is None:
                # a read held back by the write-to-read turnaround alone
                if (free and work_left and turnaround
                        and any(not q.write for q in waiting)):
                    n["wtr_stall_cycles"] += 1
                continue
            q = best
            d = ch.dur[q.rank]
            self.bus_busy[g] = t + d
            q.phase, q.done = 4, t + d
            if q.write:
                extra = ch.t_rp if self.closed_page else 0
                self.bank_busy[q.rank][q.bank] = max(
                    self.bank_busy[q.rank][q.bank], t + d + ch.t_wr + extra)
                self.bus_wr_until[g] = t + d + ch.t_wtr
                n["wr_bus_cycles"] += d
            self.bus_last_wr[g] = q.write
            n["bus_cycles"] += d
            n["n_grants"] += 1
            if t % ch.layers == q.rank % ch.layers:
                n["n_slot_grants"] += 1

    # ---- 5. retire --------------------------------------------------------

    def retire_stage(self, t: int):
        done, left = [], []
        for q in self.queue:
            (done if q.phase == 4 and q.done <= t else left).append(q)
        if not done:
            return
        self.queue = left
        for q in done:
            self.served[q.core] += 1
            self.c_out[q.core] -= 1
            self.c_finish[q.core] = t
            if q.write:
                self.n["n_wr"] += 1
            if any(o.core == q.core and o.tag < q.tag for o in left):
                self.n["n_ooo_retire"] += 1

    # ---- 6. core progress -------------------------------------------------

    def progress_stage(self):
        oldest = [self.inf] * self.n_cores
        for q in self.queue:
            if q.inst < oldest[q.core]:
                oldest[q.core] = q.inst
        for c in range(self.n_cores):
            if self.served[c] >= self.n_req:
                continue
            x = self.c_inst[c]
            if self.f(x - oldest[c]) < self.inst_window:
                x = self.f(x + self.inst_step)
            i = self.c_next[c]
            if i < self.n_req and self.inst[c, i] < x:
                x = self.inst[c, i]
            self.c_inst[c] = x

    # ---- 7. power ---------------------------------------------------------

    def power_stage(self, t: int, work_left: bool):
        ch, n = self.ch, self.n
        pending = [False] * ch.n_ranks
        for q in self.queue:
            pending[q.rank] = True
        for r in range(ch.n_ranks):
            idle = not pending[r] and max(self.bank_busy[r]) <= t
            if not idle:
                self.idle_since[r] = t + 1
            idle_for = t - self.idle_since[r]
            if self.in_sr[r] and pending[r]:
                self.in_sr[r] = False
                self.bank_busy[r] = [max(x, t + ch.t_xsr)
                                     for x in self.bank_busy[r]]
                self.ref_next[r] = t + ch.t_xsr + self.t_refi
                if work_left:
                    n["n_sr_exit"] += 1
            elif (self.self_refresh and idle and idle_for >= ch.t_sr
                  and self.debt[r] == 0):
                self.in_sr[r] = True
            if work_left:
                if self.in_sr[r]:
                    n["sr_cycles"] += 1
                elif idle and idle_for >= ch.t_pd:
                    n["pd_cycles"] += 1

    # ---- the cycle loop ---------------------------------------------------

    def run(self) -> dict:
        t = 0
        while t < self.horizon:
            work_left = any(s < self.n_req for s in self.served)
            if not work_left and not any(self.debt):
                break
            due, target = self.refresh_stage(t, work_left)
            self.arrival_stage(t)
            self.command_stage(t, due, target, work_left)
            self.transfer_stage(t, work_left)
            self.retire_stage(t)
            self.progress_stage()
            self.power_stage(t, work_left)
            t += 1
        return self.statistics()

    def statistics(self) -> dict:
        ch, f32 = self.ch, np.float32
        unit = f32(ch.unit_ns)
        served = np.asarray(self.served, np.int32)
        complete = served >= self.n_req
        t_ns = f32(self.horizon) * unit
        finish_ns = np.maximum(np.asarray(self.c_finish, np.int32),
                               1).astype(f32) * unit
        c_inst = np.asarray(self.c_inst, self.f)
        total = self.inst[:, self.n_req - 1]
        ipc = np.where(complete, total / (finish_ns * f32(3.2)),
                       c_inst / (t_ns * f32(3.2))).astype(f32)
        makespan_ns = f32(np.max(np.where(complete, finish_ns, t_ns)))
        makespan_cycles = makespan_ns / unit
        rank_cycles = max(makespan_cycles * f32(ch.n_ranks), f32(1))
        out = {k: np.int32(v) for k, v in self.n.items()}
        out.update(
            served=served, complete=complete,
            ipc=ipc, inst=c_inst, makespan_ns=makespan_ns,
            bandwidth_gbps=f32(served.sum()) * f32(ch.request_bytes)
            / makespan_ns,
            bus_util=f32(self.n["bus_cycles"])
            / max(makespan_cycles * f32(ch.n_buses), f32(1)),
            pd_frac=f32(self.n["pd_cycles"]) / rank_cycles,
            sr_frac=f32(self.n["sr_cycles"]) / rank_cycles,
            horizon_ns=t_ns,
            ref_debt_end=np.int32(sum(self.debt)),
            n_ecc_reread=np.int32(0),
            n_enqueued=np.int32(sum(self.c_next)),
            n_outstanding=np.int32(len(self.queue)))
        return out


def simulate(ch: Channel, traces: dict, core: dict, horizon: int,
             fdtype=np.float32) -> dict:
    """One cell's statistics, under the program's names."""
    return Sim(ch, traces, core, horizon, fdtype).run()


def _simulate(args) -> dict:
    return simulate(*args)


def simulate_many(cells: list[tuple], fdtype=np.float32) -> list[dict]:
    """``simulate(*cell, fdtype)`` for each ``(channel, traces, core,
    horizon)`` of `cells`, in as many fresh worker processes as there
    are cells, up to ``WORKERS`` and the host's cores.  The workers
    import this module and numpy only; all have ended when this
    returns."""
    n = max(1, min(len(cells), WORKERS, os.cpu_count() or 1))
    with concurrent.futures.ProcessPoolExecutor(
            n, mp_context=multiprocessing.get_context("spawn")) as ex:
        return list(ex.map(_simulate, [c + (fdtype,) for c in cells]))
