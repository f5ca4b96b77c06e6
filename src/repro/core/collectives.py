"""SMLA-adapted collective schedules (DESIGN.md §2.2).

The paper coordinates multiple DRAM layers behind one shared IO channel:

* **Dedicated-IO** — statically partition the channel; every layer owns a
  dedicated 1/L slice for the whole transfer.  TPU analogue: the single
  fused XLA collective (all-gather / reduce-scatter / all-reduce), where
  every shard's traffic occupies its own share of every link concurrently.
* **Cascaded-IO** — time-multiplex the full channel through neighbours;
  each node first emits its own block, then forwards upstream blocks.  TPU
  analogue: an explicit `lax.ppermute` ring pipeline — hop h carries the
  blocks injected h steps upstream, giving the paper's tiered per-hop
  utilisation and, crucially, exposing *per-hop overlap points* to the
  scheduler (gather of layer l+1 overlaps compute of layer l when the ring
  is unrolled into the layer scan).

All ring primitives below are exact (tests assert equality with the fused
XLA collectives); they run inside `shard_map` with the target axis manual.

`cross_pod_sync` applies these across the 'pod' mesh axis for hierarchical
gradient reduction: within-pod reductions stay in auto (GSPMD) land, the
pod hop is explicit and bucketed (all gradient leaves flattened into one
vector — NCCL-style bucket fusion), with optional int8 compression
(train/compression.py).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# ----------------------------------------------------------------------------
# ring primitives (inside shard_map; `axis` manual)
# ----------------------------------------------------------------------------


def _fwd_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def cascaded_all_gather(x, axis: str):
    """Ring all-gather: returns (n, *x.shape) ordered by source index.

    Hop h forwards the block received at hop h-1 (Cascaded-IO §4.2: send own
    data first, then relay upper layers).  n-1 hops; hop h moves exactly one
    block per node — the paper's time-sliced schedule."""
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)

    def hop(carry, _):
        nxt = lax.ppermute(carry, axis, _fwd_perm(n))
        return nxt, nxt

    _, received = lax.scan(hop, x, None, length=n - 1)
    blocks = jnp.concatenate([x[None], received], axis=0)  # index h: src i-h
    order = (i - jnp.arange(n)) % n                        # want src-ordered
    inv = jnp.zeros((n,), order.dtype).at[order].set(jnp.arange(n))
    return jnp.take(blocks, inv, axis=0)


def cascaded_reduce_scatter(x, axis: str):
    """Ring reduce-scatter over leading dim (must equal axis size).

    x (n, ...) per node; returns block i fully reduced on node i.  The
    partial sum destined for block b starts at node b+1 and accumulates as
    it cascades around the ring — node-local data first, forwarded partials
    after, exactly the Cascaded-IO dataflow with an adder at the mux."""
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    p = jnp.take(x, (i - 1) % n, axis=0)

    def hop(p, s):
        q = lax.ppermute(p, axis, _fwd_perm(n))
        p = q + jnp.take(x, (i - 1 - s) % n, axis=0)
        return p, None

    p, _ = lax.scan(hop, p, jnp.arange(1, n))
    return p


def cascaded_all_reduce(x, axis: str):
    """Ring all-reduce = ring reduce-scatter + ring all-gather (2(n-1) hops,
    each moving 1/n of the data — bandwidth-optimal)."""
    n = lax.axis_size(axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(n, -1)
    mine = cascaded_reduce_scatter(blocks, axis)
    full = cascaded_all_gather(mine, axis).reshape(-1)
    full = full[:flat.shape[0] - pad] if pad else full
    return full.reshape(x.shape)


def dedicated_all_gather(x, axis: str):
    """Fused XLA all-gather (statically partitioned channel)."""
    return lax.all_gather(x, axis, axis=0)


def dedicated_all_reduce(x, axis: str):
    return lax.psum(x, axis)


# ----------------------------------------------------------------------------
# bucketed pytree sync across an axis
# ----------------------------------------------------------------------------


def _flatten_bucket(tree):
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    return flat, leaves


def _unflatten_bucket(tree, leaves, flat):
    out, off = [], 0
    for l in leaves:
        n = math.prod(l.shape) if l.shape else 1
        out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(jax.tree.structure(tree), out)


def tree_sync(tree, axis: str, mode: str = "cascaded", mean: bool = True,
              compress=None):
    """Sum (or mean) a pytree across `axis` inside a partial-manual region.

    PER-LEAF, not bucketed: inside the pod-manual region the leaves remain
    sharded over the (auto) 'data'/'model' axes, and any flatten/concat into
    one bucket would unshard them — measured at 245 GB/device peak for the
    30B MoE before this change (EXPERIMENTS.md §Perf iteration C2).  Ring
    chunking uses the leading dim (the stacked-layer dim, unsharded by the
    param rules) when divisible; scalars/indivisible leaves psum.

    mode: cascaded (ring) | dedicated (fused psum) | cascaded_int8
    (compressed ring; quantisation works on the leading-dim chunks).
    """
    n = lax.axis_size(axis)

    def one(leaf):
        ring_ok = leaf.ndim >= 1 and leaf.shape[0] % n == 0 and n > 1
        if mode == "dedicated" or not ring_ok:
            total = lax.psum(leaf, axis)
        elif mode == "cascaded":
            blocks = leaf.reshape(n, leaf.shape[0] // n, *leaf.shape[1:])
            mine = cascaded_reduce_scatter(blocks, axis)
            total = cascaded_all_gather(mine, axis).reshape(leaf.shape)
        elif mode == "cascaded_int8":
            from repro.train.compression import compressed_ring_all_reduce
            flat = leaf.reshape(-1).astype(jnp.float32)
            total = compressed_ring_all_reduce(flat, axis) \
                .reshape(leaf.shape).astype(leaf.dtype)
        else:
            raise ValueError(mode)
        return (total / n).astype(leaf.dtype) if mean else total

    return jax.tree.map(one, tree)


# ----------------------------------------------------------------------------
# cross-pod hierarchical gradient sync (partial-manual shard_map over 'pod')
# ----------------------------------------------------------------------------


def _pod_batch_spec(kp, leaf) -> P:
    name = str(getattr(kp[-1], "key", kp[-1])) if kp else ""
    if name == "positions":                       # (3, B, S)
        return P(None, "pod")
    return P("pod")                               # batch leading dim


def pod_sync_wrap(grad_fn, mesh, mode: str = "cascaded", compress=None):
    """Wrap grad_fn(params, batch) -> (loss_aux, grads) with hierarchical
    cross-pod reduction.

    Per-pod partial gradients only exist inside a region where 'pod' is a
    manual axis, so the whole gradient computation runs under a
    partial-manual shard_map: 'data'/'model' stay auto (GSPMD inserts the
    within-pod reductions), the 'pod' hop is ours — cascaded ring or
    dedicated fused, optionally compressed.  Single-pod meshes: identity.
    """
    if mesh is None or "pod" not in mesh.axis_names or mesh.shape["pod"] == 1:
        return grad_fn

    def wrapped(params, batch):
        p_specs = jax.tree.map(lambda _: P(), params)
        b_specs = jax.tree_util.tree_map_with_path(_pod_batch_spec, batch)

        def body(p, b):
            (loss, metrics), grads = grad_fn(p, b)
            grads = tree_sync(grads, "pod", mode=mode, mean=True,
                              compress=compress)
            loss = lax.pmean(loss, "pod")
            metrics = jax.tree.map(lambda m: lax.pmean(m, "pod"), metrics)
            return (loss, metrics), grads

        meta = jax.eval_shape(grad_fn, params, batch)
        out_specs = jax.tree.map(lambda _: P(), meta)
        out = jax.shard_map(
            body, mesh=mesh, in_specs=(p_specs, b_specs),
            out_specs=out_specs,
            axis_names={"pod"}, check_vma=False)(params, batch)
        return out

    return wrapped
