"""WKV6 chunked linear-recurrence Pallas TPU kernel.

Grid (B, H, n_chunks); the chunk axis is sequential ('arbitrary') and the
per-head (hd_k, hd_v) state lives in VMEM scratch across chunks.  Each chunk
computes the intra-chunk pairwise term through an explicit per-channel decay
tensor exp(t_i - s_j) — every exponent <= 0, so it is overflow-free — and
the inter-chunk term against the carried state (the same math as
models/rwkv6.wkv_chunked, which is the cross-check oracle at chunk
granularity; ref.py is the sequential oracle).

VMEM per grid step: chunk x chunk x hd f32 decay tensor (64x64x64 = 1 MiB)
plus four (chunk, hd) operand tiles — sized for a 16 MiB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, st_ref, state, *,
                chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0, 0].astype(jnp.float32)          # (cs, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)          # log decay, < 0
    u = u_ref[0].astype(jnp.float32)             # (hd,)

    scum = jnp.cumsum(w, axis=0)                 # inclusive
    texc = scum - w                              # exclusive

    # intra-chunk: scores[i,j] = sum_d r[i,d] k[j,d] exp(t_i[d] - s_j[d]), j<i
    diff = texc[:, None, :] - scum[None, :, :]   # (cs, cs, hd), <= 0 for j<i
    mask = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    dec = jnp.where(mask[..., None], jnp.exp(diff), 0.0)
    kd = dec * k[None, :, :]                     # (cs, cs, hd)
    scores = jax.lax.dot_general(
        r, kd, (((1,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)      # (cs, cs)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # diagonal bonus
    dsc = jnp.sum(r * u[None, :] * k, axis=1)    # (cs,)
    y = y + dsc[:, None] * v
    # inter-chunk from carried state
    rt = r * jnp.exp(texc)
    y = y + jax.lax.dot_general(rt, state[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # state update
    s_last = scum[-1]                            # (hd,)
    kdl = k * jnp.exp(s_last[None, :] - scum)
    state[...] = (state[...] * jnp.exp(s_last)[:, None]
                  + jax.lax.dot_general(kdl, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state[...]


def wkv6(r, k, v, logw, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/logw (B, H, S, hd); u (H, hd).  S % chunk == 0.
    Returns (y (B, H, S, hd), final_state (B, H, hd, hd) f32)."""
    b, h, s, hd = r.shape
    chunk = min(chunk, s)
    n = s // chunk
    spec4 = pl.BlockSpec((1, 1, chunk, hd), lambda b_, h_, c: (b_, h_, c, 0))
    y, st = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n),
        grid=(b, h, n),
        in_specs=[spec4, spec4, spec4, spec4,
                  pl.BlockSpec((1, hd), lambda b_, h_, c: (h_, 0))],
        out_specs=[spec4,
                   pl.BlockSpec((1, 1, hd, hd),
                                lambda b_, h_, c: (b_, h_, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, hd), r.dtype),
                   jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u)
    return y, st
