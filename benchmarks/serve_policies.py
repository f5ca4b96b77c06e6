"""MLR vs. SLR serving placement (paper §5 mapped to decode serving):
per-token FLOPs and collective bytes from lowered decode steps on a
(2,2)-device mesh (structure scales to the production mesh; the dry-run
covers 256/512 chips)."""
import os


def run() -> list[str]:
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    import functools
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro import models
    from repro.configs import ParallelConfig, get_config, reduce_config
    from repro.core import partitioning as part
    from repro.launch import hlo_walk
    from repro.serve.engine import ServeConfig, _slr_param_specs

    cfg = reduce_config(get_config("tinyllama-1.1b"))
    pcfg = ParallelConfig(attn_impl="chunked", moe_impl="dense",
                          remat="none")
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    model = models.get_model(cfg)
    rows = ["policy,batch_shards,collective_bytes_per_tok,hlo_collectives"]
    with jax.set_mesh(mesh):
        p_shape = jax.eval_shape(
            functools.partial(model.init, cfg=cfg), jax.random.PRNGKey(0))
        cache_shape = jax.eval_shape(functools.partial(
            model.init_cache, cfg, 8, 64, pcfg))
        for policy in ("mlr", "slr"):
            specs = part.param_specs(p_shape, mesh)
            if policy == "slr":
                specs = _slr_param_specs(specs)
            p_sh = part.shardings(
                jax.tree.map(lambda s, l: part.filter_spec(s, l.shape, mesh),
                             specs, p_shape,
                             is_leaf=lambda s: hasattr(s, "index")), mesh)
            c_specs = part.tree_specs(
                cache_shape, model.cache_specs(cfg, pcfg, False, 2), mesh)
            fn = jax.jit(
                lambda p, t, c: model.decode(p, t, c, cfg, pcfg),
                in_shardings=(p_sh, None, part.shardings(c_specs, mesh)))
            tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
            compiled = fn.lower(p_shape, tok, cache_shape).compile()
            coll = hlo_walk.collective_bytes(compiled.as_text())
            rows.append(f"{policy},{2 if policy == 'mlr' else 4},"
                        f"{coll['total'] / 8:.3e},{coll['n_computations']}")
    rows.append("# MLR: all chips serve every token (latency-optimal); "
                "SLR: model replicated, batch over all axes "
                "(throughput-optimal) — the paper's rank trade-off")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
