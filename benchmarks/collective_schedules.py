"""Cascaded vs. dedicated collective schedules (DESIGN.md §2.2): lowered-IR
comparison of the cross-pod gradient sync on the production multi-pod mesh
— op counts, wire bytes and hop structure, plus wall-clock on host devices.

The cascade shows L-1 collective-permute hops each moving 1/L of the bucket
(the paper's time-sliced slots, tiered per-hop utilisation); dedicated is a
single fused all-reduce."""
import os

import numpy as np


def run() -> list[str]:
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, AxisType
    from repro.core import collectives as C
    from repro.launch import hlo_walk

    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    n = 1 << 17
    x = jnp.arange(4 * n, dtype=jnp.float32).reshape(4, n)

    rows = ["schedule,collective_ops,wire_bytes_per_dev,permute_hops,"
            "wall_us_host"]
    import time
    with jax.set_mesh(mesh):
        for name, fn in [
            ("cascaded", lambda v: C.cascaded_all_reduce(v, "pod")),
            ("dedicated", lambda v: C.dedicated_all_reduce(v, "pod")),
            ("cascaded_int8",
             lambda v: __import__("repro.train.compression",
                                  fromlist=["x"]).compressed_ring_all_reduce(
                                      v, "pod")),
        ]:
            jf = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("pod"),
                                       out_specs=P("pod")))
            compiled = jf.lower(x).compile()
            text = compiled.as_text()
            coll = hlo_walk.collective_bytes(text)
            hops = text.count("collective-permute(") \
                + text.count("collective-permute-start(")
            out = jf(x)
            out.block_until_ready()
            t0 = time.perf_counter()
            out = jf(x)
            out.block_until_ready()
            us = (time.perf_counter() - t0) * 1e6
            rows.append(f"{name},{coll['n_computations']},"
                        f"{coll['total']:.3e},{hops},{us:.0f}")
    rows.append("# same wire volume, different schedule: the ring exposes "
                "per-hop overlap points; int8 ring moves ~3.9x fewer bytes")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
