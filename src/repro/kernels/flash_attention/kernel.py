"""Flash attention Pallas TPU kernels (forward + backward).

TPU mapping: 4-D grid (batch, q_head, q_block, kv_block); the kv_block axis
is 'arbitrary' (sequential), so the online-softmax accumulators live in VMEM
scratch and persist across kv iterations.  BlockSpecs tile HBM->VMEM:

    q   (1, 1, bq, hd)   revisited for every kv block (stays resident)
    k,v (1, 1, bk, hd)   streamed — Pallas double-buffers the stream, which
                         is exactly the Cascaded-IO dataflow: one shared
                         VMEM 'bus' time-multiplexed across the kv blocks
                         while the MXU consumes the previous block.

Causal masking: blocks strictly above the diagonal are skipped via pl.when
(no MXU work issued), the diagonal block applies the triangular mask.
GQA: kv head index_map h -> h // (Hq//Hkv).

Backward: two kernels (standard split) — dkv iterates q blocks per kv
block; dq iterates kv blocks per q block.  Residuals: (q, k, v, o, lse,
delta) with delta = rowsum(do * o) precomputed in ops.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


NEG_INF = -1e30


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                scale: float, causal: bool, bq: int, bk: int, n_kv: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    run = (~causal) | (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).astype(lse_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, bq: int = 128,
                        bk: int = 128, interpret: bool = False):
    """q (B,Hq,S,hd); k/v (B,Hkv,S,hd) -> (o, lse)."""
    b, hq, s, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    bq = min(bq, s)
    bk = min(bk, s)
    n_q, n_kv = s // bq, s // bk
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_kv=n_kv)
    grid = (b, hq, n_q, n_kv)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b_, h, i, j: (b_, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, s), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bq, hd)), _vmem((bq,)), _vmem((bq,)),
        ],
        compiler_params=_dimsem(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _dimsem(sem):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=sem)


# ----------------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    bq, bk, n_q):
    j = pl.program_id(2)       # kv block
    i = pl.program_id(3)       # q block (sequential)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (~causal) | (i * bq + bq - 1 >= j * bk)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                      # (bq,)
        delta = delta_ref[0, 0]                  # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])            # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, bq, bk, n_kv):
    i = pl.program_id(2)       # q block
    j = pl.program_id(3)       # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (~causal) | (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """Returns (dq, dk, dv) in (B,H,S,hd) layouts (dk/dv summed per kv head
    in ops.py for GQA)."""
    b, hq, s, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    bq = min(bq, s)
    bk = min(bk, s)
    n_q, n_kv = s // bq, s // bk
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # (B,Hq,S)

    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_q=n_q),
        grid=(b, hq, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, j, i: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, j, i: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b_, h, j, i: (b_, h, i)),
            pl.BlockSpec((1, 1, bq), lambda b_, h, j, i: (b_, h, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, j, i: (b_, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, s, hd), jnp.float32),
        ],
        scratch_shapes=[_vmem((bk, hd)), _vmem((bk, hd))],
        compiler_params=_dimsem(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk_per_head, dv_per_head = dkv

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_kv=n_kv),
        grid=(b, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b_, h, i, j: (b_, h, i)),
            pl.BlockSpec((1, 1, bq), lambda b_, h, i, j: (b_, h, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, hq, s, hd), jnp.float32)],
        scratch_shapes=[_vmem((bq, hd))],
        compiler_params=_dimsem(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)[0]

    # GQA: sum per-q-head contributions into kv heads
    dk = dk_per_head.reshape(b, hkv, g, s, hd).sum(axis=2)
    dv = dv_per_head.reshape(b, hkv, g, s, hd).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
