"""Pallas backend for the SMLA cycle engine: the chunked per-cycle
pipeline fused into one kernel over blocks of the stacked cell axis.

The scan backend (`engine._sim_core` under `jax.vmap`) carries its ~35
per-cell state arrays through HBM on every `lax.scan` chunk boundary —
the exact pathology the source paper diagnoses in DRAM (idle internal
bandwidth, all traffic squeezed through one external bus).  The software
analogue of Simultaneous Multi-Layer Access is to keep that state
*on-chip*: this module tiles the cell axis into blocks of
``DEFAULT_BLOCK_CELLS`` cells, and each grid step runs the ENTIRE
chunked simulation for its block inside the kernel body — the state
dict lives in VMEM/registers across the inner fast-cycle loop, and only
the final per-cell metrics are written back to the output refs.

Fidelity by construction: the kernel body calls the very same
`engine._sim_core` (vmapped over the block axis) that the scan backend
jits, so the staged pipeline, the `loop_cond` early-exit contract (no
exit while refresh debt is outstanding), and the per-cell `chunks_run`
freeze under batched `lax.while_loop` are shared code, not a port.
Integer metrics are bit-identical to the scan backend; float metrics may
reassociate across the different program structure, so parity tests pin
them to rtol=1e-6 (`tests/test_backend_parity.py`), the same tolerance
the golden grid uses across platforms.

Cell blocks are independent, so the grid's one dimension is
``"parallel"`` (`dimension_semantics`).  A cell count that does not
divide the block size is padded by replicating the last cell — a
duplicate of a resident cell never extends its block's early-exit point
— and the pad rows are sliced off the outputs.

Fault axes need no kernel changes: the fault/degradation consequences
are lowered to traced *data* in `StackConfig.to_params` (degraded rank
counts, re-timed transfer durations, per-rank refresh derates, the ECC
re-read cadence), and every param threads into the kernel through the
same sorted-key iteration as the policy selectors — the fault x
degradation cross-product reuses this kernel's one compiled executable.
`SimOptions(validate=True)`'s checkify guards run on the *outputs*,
outside the kernel body, so validation works identically on both
backends without a Mosaic lowering for the check primitives.

The backend is interpret-only: pass ``SimOptions(interpret=True)`` to run
the kernel through the Pallas interpreter — same semantics, executed as
ordinary XLA ops, so it validates the kernel logic but not the on-chip
residency win.  The kernel does not lower through Mosaic for the TPU.
Compiled for a described v5e, the chip's compiler stops it at three
places in turn:

1. `spec_of` gives every 1-D per-cell param a block of
   ``DEFAULT_BLOCK_CELLS`` (8); Mosaic wants a rank-1 block that is the
   whole array or a multiple of 128.
2. The chunk's ``lax.scan`` over fast cycles (`engine._sim_core`) has an
   extensive input, and Mosaic's scan lowering does not implement it.
3. Scatters, which the Pallas TPU lowering does not implement.  The
   stages call no ``jax.ops.segment_*`` any more:
   `policies.refresh_demand`, where the first ``segment_sum`` sat, and
   the other per-rank and per-core queue counts are a compare and a sum
   (`policies.counts_by`) or a reshape and a reduction.  The 14
   ``.at[]`` updates of the enqueue, schedule and transfer stages
   still lower to scatters.

Porting the kernel is worth it only if a chip measurement shows the
fused layout can beat the scan backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.smla import engine

#: cells simulated per grid step.  Sized so a block's full state dict
#: (queue arrays x q_size, bank matrices x R*B, per-core vectors — a few
#: tens of KiB per cell at the default shapes) fits VMEM comfortably
#: alongside the trace block; raise for tiny grids, lower for very long
#: traces.
DEFAULT_BLOCK_CELLS = 8


def _pad_cells(tree: dict, pad: int) -> dict:
    """Replicate the last cell `pad` times along the leading axis."""
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])], axis=0),
        tree)


def _kernel(params_refs, traces_refs, out_refs, *, horizon, core, banks,
            chunk):
    """One cell block, start to finish: read the block's params/traces
    from VMEM, run the full chunked simulation as values (state never
    leaves the chip), write only the final metrics.  Pallas hands refs in
    the input/output pytree structure, so the dicts carry through."""
    params = {k: r[...] for k, r in params_refs.items()}
    traces = {k: r[...] for k, r in traces_refs.items()}
    sim = functools.partial(engine._sim_core, horizon=horizon, core=core,
                            banks=banks, chunk=chunk)
    out = jax.vmap(lambda p, t: sim(p, t))(params, traces)
    for k, r in out_refs.items():
        r[...] = out[k]


def sim_cell_blocks(params: dict, traces: dict, *, horizon: int,
                    core: engine.CoreParams, banks: int, chunk: int | None,
                    interpret: bool = False,
                    block_cells: int | None = None) -> dict:
    """Batched simulation (leading cell axis on every leaf) as a Pallas
    grid over cell blocks.  Same contract as the scan path of
    `engine.batched_simulate`; reached via ``SimOptions(backend="pallas")``
    so it shares the compile cache and counter."""
    n_cells = traces["inst"].shape[0]
    blk = min(block_cells or DEFAULT_BLOCK_CELLS, n_cells)
    pad = (-n_cells) % blk
    params = _pad_cells(params, pad)
    traces = _pad_cells(traces, pad)
    n_pad = n_cells + pad
    p_keys = tuple(sorted(params))
    t_keys = tuple(sorted(traces))

    def spec_of(x):
        bshape = (blk,) + x.shape[1:]
        nd = x.ndim
        return pl.BlockSpec(bshape, lambda i, _nd=nd: (i,) + (0,) * (_nd - 1))

    # output structure = one block's metrics, with the block axis widened
    # to the padded cell count; eval_shape keeps this in lockstep with
    # whatever metrics `_sim_core` returns.
    probe = jax.eval_shape(
        jax.vmap(functools.partial(engine._sim_core, horizon=horizon,
                                   core=core, banks=banks, chunk=chunk)),
        {k: jax.ShapeDtypeStruct((blk,) + params[k].shape[1:],
                                 jnp.asarray(params[k]).dtype)
         for k in p_keys},
        {k: jax.ShapeDtypeStruct((blk,) + traces[k].shape[1:],
                                 jnp.asarray(traces[k]).dtype)
         for k in t_keys})
    out_shape = {k: jax.ShapeDtypeStruct((n_pad,) + probe[k].shape[1:],
                                         probe[k].dtype) for k in probe}
    out_specs = {k: spec_of(out_shape[k]) for k in out_shape}

    out = pl.pallas_call(
        functools.partial(_kernel, horizon=horizon, core=core,
                          banks=banks, chunk=chunk),
        grid=(n_pad // blk,),
        in_specs=[{k: spec_of(jnp.asarray(params[k])) for k in p_keys},
                  {k: spec_of(jnp.asarray(traces[k])) for k in t_keys}],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )({k: jnp.asarray(params[k]) for k in p_keys},
      {k: jnp.asarray(traces[k]) for k in t_keys})
    if pad:
        out = jax.tree_util.tree_map(lambda x: x[:n_cells], out)
    return out
