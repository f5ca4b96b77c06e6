"""SMLA cascaded-pipeline matmul — the paper's bottom-layer datapath as a
TPU kernel.

The paper's structure: L stacked DRAM layers each own 1/L of the data and a
full set of internal sense amplifiers, but share one IO bus; Cascaded-IO
time-multiplexes the bus so every layer's data streams through the same
wires while the consumer (the memory controller) never starves.

TPU analogue implemented here: a weight matrix striped across L HBM slabs
(w (L, K/L, N)), consumed by one MXU through ONE shared VMEM staging buffer.
The grid's sequential reduction axis walks layer-by-layer, chunk-by-chunk
(grid index t -> layer t // (K/L/bk), stripe chunk t % ...); Pallas's
automatic double buffering prefetches stripe t+1 while the MXU multiplies
stripe t — the cut-through forwarding of §4.2, with the VMEM buffer playing
the TSV bus.  The accumulator in VMEM scratch is the aggregation point
("bottom layer").

The contrast benchmark (benchmarks/smla_pipe_bench.py) compares:
  * cascaded (this kernel: one shared buffer, time-multiplexed stripes)
  * dedicated (L independent pallas_call matmuls, one per layer slab +
    jnp.sum — private buffers, L partial results: Dedicated-IO)
against the XLA monolithic dot; the lowered-IR slot counts stand in for the
paper's bus-utilisation timeline on this CPU container.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _cascade_kernel(x_ref, w_ref, o_ref, acc, *, n_t: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)            # (bm, bk)
    w = w_ref[0].astype(jnp.float32)              # (bk, bn)
    acc[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finish():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def matmul_cascaded(x, w, *, bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool = False):
    """x (M, K); w (L, K//L, N) -> (M, N) f32.

    Sequential axis order = (layer, stripe chunk): the shared buffer serves
    layer 0's stripes, then layer 1's, ... — the Cascaded-IO slot rotation
    unrolled over a whole transfer."""
    m, k = x.shape
    l, kpl, n = w.shape
    assert l * kpl == k, (l, kpl, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kpl)
    n_k = kpl // bk           # chunks per layer stripe
    n_t = l * n_k             # total sequential steps

    return pl.pallas_call(
        functools.partial(_cascade_kernel, n_t=n_t),
        grid=(m // bm, n // bn, n_t),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, t: (i, t)),          # x walks K
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, t: (t // n_k, t % n_k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)


def matmul_dedicated(x, w, *, bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool = False):
    """Dedicated-IO analogue: one independent kernel per layer slab (private
    staging buffers), partials summed at the end.  Same FLOPs; L live
    partial (M, N) buffers and no cross-layer reuse of the stream."""
    l, kpl, n = w.shape
    parts = []
    for layer in range(l):
        xs = jax.lax.dynamic_slice_in_dim(x, layer * kpl, kpl, axis=1)
        parts.append(matmul_cascaded(xs, w[layer:layer + 1], bm=bm, bn=bn,
                                     bk=bk, interpret=interpret))
    return sum(parts)
