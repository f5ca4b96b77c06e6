"""Mixture-of-Experts FFN with expert parallelism.

Two interchangeable dispatch implementations:

* ``dense``      — every expert computed for every token, gated combine.
  O(T·E) compute: the *oracle* for tests and the no-mesh fallback.
* ``shard_map``  — production path.  Experts are sharded over the 'model'
  axis (zero-padded to a multiple of the EP degree; padded experts are
  unroutable).  Activations stay replicated over 'model' (they already are
  between TP blocks), so each EP rank sort-dispatches the token subset routed
  to ITS experts into an (E_local, C, d) capacity buffer, runs the expert
  FFNs as one grouped einsum, scatters weighted results back, and psums
  partial outputs over 'model'.  Communication = one psum of (T, d) — the
  same volume as a Megatron TP FFN — instead of two all_to_alls; the
  replicated-dispatch/time-multiplexed-combine trade mirrors the paper's
  Dedicated-IO (static channel partition) vs Cascaded-IO (shared channel,
  time-sliced) comparison and is benchmarked in benchmarks/collective_schedules.py.

Router: softmax -> top-k -> renormalise (qwen3/granite convention).
Tokens beyond an expert's capacity are dropped (contribute zero), standard
capacity-factor semantics; tests pin capacity_factor high to compare against
the drop-free dense oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import common as cm

DP = ("pod", "data")


def route(x, w_router, cfg: ModelConfig):
    """x (B,S,d) -> (top_w (B,S,k) f32, top_ids (B,S,k) i32, aux_loss)."""
    k = cfg.moe.experts_per_token
    e = cfg.moe.n_experts
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    t = probs.shape[0] * probs.shape[1]
    counts = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(1.0)
    f = counts / (t * k)
    p_mean = probs.mean(axis=(0, 1))
    aux = cfg.moe.aux_loss_weight * e * jnp.sum(f * p_mean)
    return top_w, top_ids, aux


def moe_ffn(x, p, cfg: ModelConfig, pcfg: ParallelConfig):
    """p: {'router': (d, E), 'experts': {w_gate/w_up/w_down: (E, ...)}}."""
    top_w, top_ids, aux = route(x, p["router"], cfg)
    am = jax.sharding.get_abstract_mesh()
    use_sm = (pcfg.moe_impl == "shard_map" and not am.empty
              and "model" in am.axis_names and am.shape["model"] > 1)
    if use_sm:
        out = _moe_shard_map(x, top_w, top_ids, p["experts"], cfg, pcfg, am)
    else:
        out = _moe_dense(x, top_w, top_ids, p["experts"], cfg)
    return out.astype(x.dtype), aux


# ----------------------------------------------------------------------------
# dense oracle
# ----------------------------------------------------------------------------


def _moe_dense(x, top_w, top_ids, experts, cfg: ModelConfig):
    e = cfg.moe.n_experts
    wg = cm.cast(experts["w_gate"], cfg)
    wu = cm.cast(experts["w_up"], cfg)
    wd = cm.cast(experts["w_down"], cfg)
    g = jnp.einsum("bsd,edf->bsef", x, wg)
    u = jnp.einsum("bsd,edf->bsef", x, wu)
    y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, wd)
    gate = jnp.sum(jax.nn.one_hot(top_ids, e, dtype=jnp.float32)
                   * top_w[..., None], axis=2)              # (B,S,E)
    return jnp.einsum("bse,bsed->bsd", gate, y.astype(jnp.float32))


# ----------------------------------------------------------------------------
# shard_map expert parallelism
# ----------------------------------------------------------------------------


def capacity(t_local: int, k: int, e: int, cf: float) -> int:
    c = int(math.ceil(cf * t_local * k / e))
    return int(min(t_local * k, max(c, min(32, t_local * k))))


def _moe_shard_map(x, top_w, top_ids, experts, cfg, pcfg, am):
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.experts_per_token
    ep = int(am.shape["model"])
    e_pad = int(math.ceil(e / ep)) * ep
    e_local = e_pad // ep
    # only Auto axes may appear in the inner shard_map's specs: inside the
    # hierarchical-sync region 'pod' is already Manual (and the batch is
    # already pod-local), so it must be excluded here.
    auto = cm.auto_axis_names(am)
    dp = tuple(a for a in DP if a in auto)
    dp_size = int(math.prod(am.shape[a] for a in dp)) if dp else 1
    if b % dp_size != 0:
        dp, dp_size = (), 1
    t_local = (b // dp_size) * s
    cap = capacity(t_local, k, e, cfg.moe.capacity_factor)

    pad = [(0, e_pad - e)] + [(0, 0), (0, 0)]
    wg = jnp.pad(cm.cast(experts["w_gate"], cfg), pad)
    wu = jnp.pad(cm.cast(experts["w_up"], cfg), pad)
    wd = jnp.pad(cm.cast(experts["w_down"], cfg), pad)

    def body(xb, wb, ib, rank_arr, wg, wu, wd):
        # rank via a P('model')-sharded iota: lax.axis_index on a nested
        # partial-manual axis fails to lower under an outer manual 'pod'
        # (sdy.manual_computation conflict) — the sharded-iota input is the
        # robust equivalent.
        rank = rank_arr[0]
        bl = xb.shape[0]
        t = bl * s
        x2 = xb.reshape(t, d)
        ids = ib.reshape(t * k)
        wts = wb.reshape(t * k)
        tok = jnp.repeat(jnp.arange(t), k)

        local = ids - rank * e_local
        mine = (local >= 0) & (local < e_local)
        key = jnp.where(mine, local, e_local)
        order = jnp.argsort(key, stable=True)
        sk, st, sw = key[order], tok[order], wts[order]
        pos = jnp.arange(t * k) - jnp.searchsorted(sk, sk, side="left")
        keep = (sk < e_local) & (pos < cap)
        slot = jnp.where(keep, sk * cap + pos, e_local * cap)

        vals = jnp.where(keep[:, None], x2[st], 0)
        xbuf = jnp.zeros((e_local * cap + 1, d), x2.dtype).at[slot].set(vals)
        xe = xbuf[:-1].reshape(e_local, cap, d)

        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)

        yf = jnp.concatenate([y.reshape(e_local * cap, d),
                              jnp.zeros((1, d), y.dtype)])
        contrib = yf[slot].astype(jnp.float32) * (sw * keep)[:, None]
        out = jnp.zeros((t, d), jnp.float32).at[st].add(contrib)
        out = jax.lax.psum(out, "model")
        return out.reshape(bl, s, d)

    dp_spec = dp if len(dp) != 1 else dp[0]
    tok_spec = P(dp_spec, None, None) if dp else P(None, None, None)
    ranks = jnp.arange(ep, dtype=jnp.int32)
    return jax.shard_map(
        body, mesh=am,
        in_specs=(tok_spec, tok_spec, tok_spec, P("model"),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=tok_spec,
        axis_names={*dp, "model"},   # never re-manualise an ambient-Manual axis
        check_vma=False,
    )(x, top_w, top_ids, ranks, wg, wu, wd)
