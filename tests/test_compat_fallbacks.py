"""The two manual-axis behaviours the model code relies on.

Each probe runs in a subprocess (forced 8-device host platform):

* partial-manual shard_map (manual 'pod', Auto rest) with `lax.axis_index`
  and a 'pod' collective in the body compiles and runs.
  `core/collectives.py::pod_sync_wrap`'s hierarchical grad sync is built
  on it.
* `with_sharding_constraint` accepts only axes that are Auto in the
  current abstract mesh, so naming a manual axis raises.
  `models/common.py::filter_spec` therefore drops every axis that is not
  Auto, and `common.shard` stays safe inside (partial-)manual regions.

Probes print verdict lines instead of crashing, so the subprocess exits 0
either way and the assertions happen here.
"""


def _probe(code: str) -> str:
    """conftest.run_subprocess_jax, imported lazily so the module also
    imports outside a pytest run (pytest puts tests/ on sys.path)."""
    from conftest import run_subprocess_jax as run
    return run(code)


PARTIAL_MANUAL_PROBE = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))

def body(x):
    y = x * 2 + jax.lax.axis_index("pod")
    return jax.lax.pmean(y, "pod")

x = jnp.arange(32.0).reshape(8, 4)
try:
    f = jax.shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=P(),
                      axis_names={"pod"}, check_vma=False)
    out = jax.block_until_ready(jax.jit(f)(x))
    print("VERDICT: OK", float(out.sum()))
except Exception as e:
    print("VERDICT: FAIL", type(e).__name__, e)
"""

WSC_MANUAL_PROBE = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.models import common as cm

mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))

def full_manual(x):
    print("FULL-SPEC:", cm.filter_spec(P("data"), x.shape))
    return cm.shard(x * 2, P("data"))

def partial_manual(x):
    print("PARTIAL-SPEC:", cm.filter_spec(P(("pod", "data")), x.shape))
    return cm.shard(x * 2, P(("pod", "data")))

def raw(x):
    return jax.lax.with_sharding_constraint(x * 2, P("data"))

x = jnp.arange(64.0).reshape(8, 8)
with jax.set_mesh(mesh):
    for name, fn, axes in (("full", full_manual, None),
                           ("partial", partial_manual, {"pod"}),
                           ("raw", raw, None)):
        kw = {} if axes is None else {"axis_names": axes}
        try:
            f = jax.shard_map(fn, mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod"), check_vma=False, **kw)
            out = jax.block_until_ready(jax.jit(f)(x))
            ok = bool((out == 2 * x).all())
            print(f"VERDICT {name}: {'OK' if ok else 'WRONG'}")
        except Exception as e:
            print(f"VERDICT {name}: FAIL", type(e).__name__)
"""


def test_partial_manual_guard_matches_jax():
    out = _probe(PARTIAL_MANUAL_PROBE)
    # pmean over 'pod' of (2x + pod index): the pod-1 half adds 1 to each
    # of the 16 elements of its shard, averaged with pod 0's +0
    want = float(2 * sum(range(32)) / 2 + 16 * 0.5)
    assert f"VERDICT: OK {want}" in out, (
        f"partial-manual shard_map (manual 'pod') no longer runs; "
        f"core/collectives.pod_sync_wrap depends on it.  Probe output:\n"
        f"{out}")


def test_sharding_constraint_guard_matches_jax():
    out = _probe(WSC_MANUAL_PROBE)
    # a raw constraint naming a manual axis is refused, which is why
    # filter_spec keeps only Auto axes ...
    assert "VERDICT raw: FAIL" in out, out
    # ... so inside a fully-manual region no axis survives, and inside a
    # partial-manual one only the Auto axes do
    assert "FULL-SPEC: PartitionSpec(None, None)" in out, out
    assert "PARTIAL-SPEC: PartitionSpec('data', None)" in out, out
    assert "VERDICT full: OK" in out, out
    assert "VERDICT partial: OK" in out, out
