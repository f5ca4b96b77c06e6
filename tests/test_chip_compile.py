"""Compile the batched scan engine for a described TPU v5e chip.

No chip is attached: `jax.experimental.topologies` describes a v5e, and
XLA's TPU compiler builds the program for it.  That refuses what the chip
would refuse (an op it cannot lower, a program too large for its memory)
at no chip time.  Nothing runs, so this says nothing about results or
times.

The shapes are real buckets of the paper's sweeps at full size: one
Fig. 11 bucket (20 single-core cells, ``n_req=600``) and one 16-core
Fig. 12 bucket (4 cells of 4 cores, ``n_req=500``), each at the horizon
its figure derives.

The topology is described only inside the fixture below: loading the TPU
library while a module is imported would make every test worker try to
take it.  The persistent compile cache is off around these compiles, as
an executable built for a described chip cannot be read back without one.
"""
import collections
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.smla import engine, sweep
from repro.core.smla.analytic import default_horizon
from repro.core.smla.engine import SimOptions
from repro.core.smla.traces import WORKLOADS

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _fig11_cells():
    return sweep.paper_grid([(w.name, [w], 0) for w in WORKLOADS],
                            layers=(4,), n_req=600)


def _fig12_c16_cells():
    from benchmarks import paper_fig12
    cells, _ = paper_fig12.grid_cells()
    return [c for c in cells if c.name.startswith("c16/")]


def _compile_first_bucket(topo, cells):
    opts = SimOptions(horizon=default_horizon(cells))
    spec = sweep.SweepSpec(tuple(cells), options=opts)
    bkt = sweep._plan(spec, opts, cells, 1)[0]
    params, traces = sweep._build_arrays(bkt)
    n_cells, n_cores, n_req_max = traces["inst"].shape
    fn = engine._compiled(opts.with_chunk(bkt.chunk_b), spec.core,
                          bkt.banks,
                          (n_cells, n_cores, n_req_max, bkt.r_max), True)

    one_chip = SingleDeviceSharding(topo.devices[0])
    shape_of = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        np.shape(a), np.asarray(a).dtype, sharding=one_chip)
    return fn.lower(jax.tree_util.tree_map(shape_of, params),
                    jax.tree_util.tree_map(shape_of, traces)).compile()


@pytest.mark.parametrize("cells_of", [_fig11_cells, _fig12_c16_cells],
                         ids=["fig11", "fig12_c16"])
def test_scan_bucket_compiles_for_v5e(topo, no_persistent_cache, cells_of):
    compiled = _compile_first_bucket(topo, cells_of())
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem


#: instructions that are no kernel of their own on the chip
_NOT_OPS = {"parameter", "get-tuple-element", "tuple", "constant",
            "bitcast"}


def _scan_body_ops(hlo: str) -> list:
    """``(scope, kinds)`` for each op of the compiled scan body (the
    while-loop body whose ops carry the most stage scopes): the innermost
    stage scope, ``None`` for an op in no stage scope, and the set of the
    op's instruction kinds, those of every computation it calls (a
    fusion's body) included."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None and " = " in line:
            op = re.search(r" = .*? ([a-z][a-z0-9\-]*)\(", line).group(1)
            called = tuple(re.findall(r"(?:calls|to_apply)=%([^,\s]+)",
                                      line))
            name = re.search(r'op_name="([^"]*)"', line)
            found = [c.removeprefix(engine.SCOPE_PREFIX)
                     for c in (name.group(1) if name else "").split("/")
                     if c.startswith(engine.SCOPE_PREFIX)]
            scope = found[-1] if found else None
            cur.append((op, called,
                        scope if scope in engine.STAGE_SCOPES else None))

    def kinds(op, called):
        out = {op}
        for c in called:
            for sub in comps[c]:
                out |= kinds(*sub[:2])
        return out

    bodies = re.findall(r" while\(.*?body=%([^,\s]+)", hlo)
    body = max(bodies, key=lambda b: sum(o[2] is not None for o in comps[b]))
    return [(scope, kinds(op, called)) for op, called, scope in comps[body]
            if op not in _NOT_OPS]


def _scan_body_scopes(hlo: str) -> collections.Counter:
    """How many ops of the compiled scan body each stage scope holds."""
    return collections.Counter(scope for scope, _ in _scan_body_ops(hlo))


def test_stage_scopes_label_the_compiled_scan_body(topo,
                                                   no_persistent_cache):
    """Every stage scope survives XLA's fusion onto ops of the full-size
    Fig. 12 bucket's scan body, and few ops (copies XLA inserts, the
    loop's control) carry none: the stage probe's attribution rests on
    both."""
    scopes = _scan_body_scopes(
        _compile_first_bucket(topo, _fig12_c16_cells()).as_text())
    assert set(engine.STAGE_SCOPES) <= set(scopes), scopes
    assert scopes[None] < 0.3 * sum(scopes.values()), scopes


def test_queue_counts_compile_to_no_scatter(topo, no_persistent_cache):
    """The per-rank and per-core queue reductions of the refresh, retire,
    progress and power stages compile to dense reductions: no op of those
    scopes in the full-size Fig. 12 bucket's scan body is a scatter or a
    fusion holding one.  The chip applies a scatter's updates one by one,
    so each such op cost several times a dense fusion."""
    ops = _scan_body_ops(
        _compile_first_bucket(topo, _fig12_c16_cells()).as_text())
    scattering = [(scope, sorted(kinds)) for scope, kinds in ops
                  if scope in ("refresh", "retire", "progress", "power")
                  and any(k.startswith("scatter") for k in kinds)]
    assert not scattering, scattering
    # the parse does see scatters: the `.at[]` updates of other stages
    assert any(k.startswith("scatter") for _, kinds in ops for k in kinds)
