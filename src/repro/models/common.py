"""Shared model primitives: norms, init, dtype policy, sharding helpers.

Parameters are stored float32 and cast to the compute dtype (bf16) at use —
the standard JAX mixed-precision policy.  Parameter trees are plain nested
dicts whose flattened key paths match ``configs.base._param_shapes`` exactly
(asserted by tests/test_configs.py).
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

Params = Any  # nested dict pytree of jnp arrays

# ----------------------------------------------------------------------------
# dtype policy
# ----------------------------------------------------------------------------

PARAM_DTYPE = jnp.float32


def compute_dtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def cast(x, cfg):
    return x.astype(compute_dtype(cfg))


# ----------------------------------------------------------------------------
# initialisation
# ----------------------------------------------------------------------------


def init_dense(key, shape, in_axis: int = -2) -> jax.Array:
    """Truncated-normal fan-in init (stddev 1/sqrt(fan_in))."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -3, 3, shape, PARAM_DTYPE)


def init_embed(key, shape) -> jax.Array:
    return 0.02 * jax.random.truncated_normal(key, -3, 3, shape, PARAM_DTYPE)


def init_from_shapes(key, shapes: dict[str, tuple[int, ...]],
                     overrides: dict[str, Callable] | None = None) -> Params:
    """Build a nested param dict from a flat {dotted.path: shape} table."""
    overrides = overrides or {}
    keys = jax.random.split(key, len(shapes))
    tree: dict = {}
    for (path, shape), k in zip(sorted(shapes.items()), keys):
        leaf_name = path.split(".")[-1]
        if path in overrides:
            val = overrides[path](k, shape)
        elif "norm" in leaf_name or leaf_name in ("scale", "ln_x"):
            val = jnp.ones(shape, PARAM_DTYPE)
        elif leaf_name in ("A_log",):
            # mamba2: A in [-1, ..] via -exp(A_log); init A_log ~ log U[1,16]
            u = jax.random.uniform(k, shape, PARAM_DTYPE, 1.0, 16.0)
            val = jnp.log(u)
        elif leaf_name in ("D",):
            val = jnp.ones(shape, PARAM_DTYPE)
        elif leaf_name in ("dt_bias",):
            # softplus^-1 of dt ~ U[1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(k, shape, PARAM_DTYPE,
                                            math.log(1e-3), math.log(1e-1)))
            val = dt + jnp.log(-jnp.expm1(-dt))
        elif leaf_name in ("mu",):
            val = 0.5 * jnp.ones(shape, PARAM_DTYPE)
        elif leaf_name in ("bonus",):
            val = 0.5 * jnp.ones(shape, PARAM_DTYPE)
        elif leaf_name == "tokens" or path.startswith("embed"):
            val = init_embed(k, shape)
        else:
            val = init_dense(keys[0] if False else k, shape)
        _set(tree, path, val)
    return tree


def _set(tree: dict, path: str, val) -> None:
    parts = path.split(".")
    for p in parts[:-1]:
        tree = tree.setdefault(p, {})
    tree[parts[-1]] = val


def get_path(tree: dict, path: str):
    for p in path.split("."):
        tree = tree[p]
    return tree


def flatten_paths(tree) -> dict[str, jax.Array]:
    out = {}
    for kp, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = ".".join(k.key for k in kp)
        out[name] = leaf
    return out


# ----------------------------------------------------------------------------
# norms / activations
# ----------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def sinusoidal_positions(seq: int, dim: int, offset=0) -> jax.Array:
    """(seq, dim) sinusoidal absolute position encoding (whisper-style)."""
    pos = jnp.arange(seq)[:, None] + offset
    half = dim // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = pos * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------------
# sharding helpers
# ----------------------------------------------------------------------------


def auto_axis_names(mesh) -> set[str]:
    """Axes of `mesh` whose type is Auto (shardable by the compiler)."""
    return {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto}


def filter_spec(spec: P, shape: tuple[int, ...]) -> P | None:
    """Restrict a PartitionSpec to the axes of the active mesh, dropping any
    axis that is absent or does not divide the corresponding dim.

    Lets one canonical spec (written for the full ('pod','data','model')
    production mesh) apply unchanged on smaller test meshes or no mesh.
    Returns None when there is no active mesh.
    """
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return None
    # Only constrain over Auto axes: inside a (partial-)manual shard_map
    # region the manual axes (e.g. 'pod' during hierarchical grad sync) must
    # not appear in sharding constraints — with_sharding_constraint raises
    # on any axis that is not Auto in the current abstract mesh.
    names = auto_axis_names(am)
    sizes = dict(am.shape)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in names and sizes[a] > 1)
        prod = math.prod(sizes[a] for a in axes) if axes else 1
        if dim % prod != 0:
            axes = ()  # drop non-divisible shardings (safe fallback)
        out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*out)


def shard(x, spec: P):
    """with_sharding_constraint that adapts to (or skips without) a mesh."""
    fspec = filter_spec(spec, x.shape)
    if fspec is None:
        return x
    return jax.lax.with_sharding_constraint(x, fspec)


def dp_axes():
    """Mesh axes carrying the batch (data-parallel) dimension."""
    return ("pod", "data")


def embed_lookup(table, tokens, cfg):
    """Vocab-table lookup that is communication-minimal AND partitioner-safe.

    The table is FEATURE-sharded (P(None, ('data','model'))), so the gather
    itself is local (vocab replicated).  The output is then resharded to the
    residual layout in two SINGLE-AXIS hops (feature->batch over 'data',
    then feature->seq over 'model'), each a plain all-to-all the SPMD
    partitioner handles.  The alternatives both fail at scale: leaving the
    reshard to propagation triggers 'involuntary full rematerialization'
    (replicates the whole (B,S,d) activation); vocab-sharding the table
    crashes the partitioner inside partial-manual (pod) regions
    (spmd_partitioner_util.cc:504).  See EXPERIMENTS.md §Dry-run notes."""
    x = jnp.take(cast(table, cfg), tokens, axis=0)
    x = shard(x, P(None, None, ("data", "model")))   # local gather output
    x = shard(x, P("data", None, "model"))           # hop 1: batch over data
    return x                                         # caller pins residual
