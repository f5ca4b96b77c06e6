"""One cell's channel, worked out from the benchmark's own configuration
file: the paper's Table 2 timings in fast cycles, each rank's transfer
time on its bus, which ranks share a bus, and the controller's policies.
Plain Python numbers; ``controller.py`` runs them."""
from __future__ import annotations

import dataclasses

#: the value names of each controller-policy axis; the first is the
#: paper's controller, taken where a traffic file leaves an axis out
POLICY_AXES = {
    "scheduler": ("FR_FCFS", "FCFS"),
    "row": ("OPEN_PAGE", "CLOSED_PAGE"),
    "refresh_gran": ("ALL_BANK", "PER_BANK"),
    "write_drain": ("INLINE", "DRAIN_WHEN_FULL", "OPPORTUNISTIC"),
    "self_refresh": ("OFF", "ENABLED"),
    "ref_postpone": ("STRICT", "POSTPONE_8X"),
    "layer_clock": ("UNIFORM", "GATED"),
    "ooo": ("IN_ORDER", "ROW_GROUP", "DIR_BATCH", "ROW_DIR"),
}


@dataclasses.dataclass(frozen=True)
class Channel:
    """Everything the reference needs of one cell but its traces."""
    layers: int
    n_ranks: int
    banks: int
    unit_ns: float
    request_bytes: int
    #: timings in fast cycles
    t_rcd: int
    t_rp: int
    t_cl: int
    t_wr: int
    t_wtr: int
    t_refi: int            # 0: no refresh
    t_rfc: int
    t_pd: int
    t_sr: int
    t_xsr: int
    #: per rank: fast cycles one request occupies its bus
    dur: tuple[int, ...]
    #: per rank: the bus it transfers on
    bus_of_rank: tuple[int, ...]
    n_buses: int
    #: cascaded SLR: rank r may start a transfer only when t % layers
    #: == r % layers
    slotted: bool
    #: axis -> value name, every axis of POLICY_AXES
    policy: dict
    n_req: int


def unit_ns(stack: dict) -> float:
    """One fast cycle in ns: 1 / (layers * base IO clock)."""
    return 1e3 / (stack["base_freq_mhz"] * stack["layers"])


def n_ranks(stack: dict, org: dict) -> int:
    """Baseline: one rank per layer; dedicated or cascaded IO: one rank
    per layer under SLR, the whole stack one rank under MLR."""
    if org["io_model"] == "BASELINE":
        return stack["layers"]
    return 1 if org["rank_org"] == "MLR" else stack["layers"]


def _cascaded_layer_freq(stack: dict, layer: int) -> float:
    """Cascaded IO's layer clocks: the lower half of the stack at L*F, the
    next quarter at L*F/2, and so on down to F at the top."""
    base = stack["base_freq_mhz"]
    f = base * stack["layers"]
    remaining, lo = stack["layers"], 0
    while remaining > 1:
        half = remaining // 2
        if layer < lo + half or f == base:
            return f
        lo += half
        remaining -= half
        f = max(f / 2.0, base)
    return max(f, base)


def channel(stack: dict, org: dict, policy: dict, n_req: int) -> Channel:
    """`stack` is the configuration's ``stack`` block, `org` one of its
    ``organisations``, `policy` a map from policy axis to value name."""
    L = stack["layers"]
    u = unit_ns(stack)

    def cyc(ns: float) -> int:
        return int(round(ns / u))

    R = n_ranks(stack, org)
    beats = stack["request_bytes"] * 8 // stack["io_bits"]
    slr = org["rank_org"] == "SLR"
    io = org["io_model"]
    pol = {axis: policy.get(axis, values[0])
           for axis, values in POLICY_AXES.items()}
    # the baseline's one bus runs at F: a beat takes L fast cycles; MLR
    # gangs every layer's IO at L*F; SLR gives each rank 1/L of the
    # width, and cascading shifts rank r's slot by r cycles
    if io == "BASELINE" or (slr and io == "DEDICATED"):
        dur = [beats * L] * R
    elif not slr:
        dur = [beats] * R
    else:
        dur = [(beats - 1) * L + 1 + r for r in range(R)]
    if pol["layer_clock"] == "GATED" and io == "DEDICATED" and slr:
        fast = stack["base_freq_mhz"] * L
        dur = [d * int(round(fast / _cascaded_layer_freq(stack, r)))
               for r, d in enumerate(dur)]
    private_bus = io != "BASELINE" and slr
    return Channel(
        layers=L, n_ranks=R, banks=stack["banks_per_rank"], unit_ns=u,
        request_bytes=stack["request_bytes"],
        t_rcd=cyc(stack["t_rcd_ns"]), t_rp=cyc(stack["t_rp_ns"]),
        t_cl=cyc(stack["t_cl_ns"]), t_wr=cyc(stack["t_wr_ns"]),
        t_wtr=cyc(stack["t_wtr_ns"]),
        t_refi=cyc(stack["t_refi_ns"]) if stack["refresh"] else 0,
        t_rfc=cyc(stack["t_rfc_ns"]), t_pd=cyc(stack["pd_idle_ns"]),
        t_sr=cyc(stack["sr_idle_ns"]), t_xsr=cyc(stack["t_xsr_ns"]),
        dur=tuple(dur),
        bus_of_rank=tuple(range(R)) if private_bus else (0,) * R,
        n_buses=R if private_bus else 1,
        slotted=io == "CASCADED" and slr and R > 1,
        policy=pol, n_req=n_req)
