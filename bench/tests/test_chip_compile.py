"""Compile each cell's first bucket for a described TPU v5e, at the
cell's own size, as the window dispatches it: one ``mp16`` bucket (4
cells x 4 cores x 500 requests) and one ``policy`` bucket (14 cells x 2
cores x 400 requests, 8 ranks).  Nothing runs.

The topology is described inside the fixture, never while a module is
imported; the persistent compile cache is off around these compiles,
since an executable built for a described chip cannot be read back
without one."""
import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench.lib import gen, program, registry
from repro.core.smla import engine, sweep

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


@pytest.mark.parametrize("cell,rows", [("smla4-mp16", 4),
                                       ("smla8-policy", 14)])
def test_first_bucket_compiles_for_v5e(topo, no_persistent_cache, cell,
                                       rows):
    bm = registry.benchmark()
    wl = registry.workload(bm, cell)
    cfg = registry.config(bm, wl["config"])
    traffic = registry.traffic(wl["traffic"])
    g = program.grid(gen.make_job(cfg, traffic, 1, 0), cfg, traffic)
    spec = g.spec
    opts = spec.resolved_options()
    cells = list(spec.cells) if spec.policies is None \
        else sweep.policy_cells(spec.cells, spec.policies)
    bkt = sweep._plan(spec, opts, cells, 1)[0]
    params, traces = sweep._build_arrays(bkt)
    n_cells, n_cores, n_req_max = traces["inst"].shape
    assert n_cells == rows and n_req_max == traffic["n_req"]
    fn = engine._compiled(opts.with_chunk(bkt.chunk_b), spec.core,
                          bkt.banks,
                          (n_cells, n_cores, n_req_max, bkt.r_max), True)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape_of(a):
        return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                    sharding=one_chip)
    compiled = fn.lower(jax.tree_util.tree_map(shape_of, params),
                        jax.tree_util.tree_map(shape_of, traces)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem
