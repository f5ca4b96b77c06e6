"""Flash-decode Pallas TPU kernel — SMLA-cascaded KV streaming.

One new token attends to a long KV cache.  The cache is tiled into chunks
("layers" in the paper's sense: independent HBM-resident slabs whose reads
would otherwise serialise behind one VMEM staging buffer); the grid's
sequential chunk axis time-multiplexes them through the double-buffered
VMEM stream while partial-softmax statistics (m, l, acc) accumulate in
scratch — fetch of chunk t+1 overlaps the VPU/MXU work on chunk t, the
Cascaded-IO overlap applied to HBM->VMEM.

Grid (B, Hkv, n_chunks); q (G, hd) per (b, kv-head) stays resident; lengths
live in SMEM.  Chunks wholly beyond the valid prefix are skipped (no work
issued) — the tiered utilisation of the paper's upper layers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
                   scale: float, bk: int, n_kv: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(j * bk < length)               # skip fully-invalid chunks
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_prev * alpha + p.sum(axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, bk: int = 256,
                     interpret: bool = False):
    """q (B, Hkv, G, hd); caches (B, Hkv, S, hd); lengths (B,) int32."""
    b, hkv, g, hd = q.shape
    s = k_cache.shape[2]
    bk = min(bk, s)
    n_kv = s // bk
    scale = 1.0 / math.sqrt(hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, j, *_: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, j, *_: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, hd), jnp.float32),
                        pltpu.VMEM((g,), jnp.float32),
                        pltpu.VMEM((g,), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, n_kv=n_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)
