"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real (single) device; multi-device tests spawn subprocesses with
--xla_force_host_platform_device_count themselves."""
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden", action="store_true", default=False,
        help="regenerate tests/golden/*.json from the current engine "
             "instead of comparing against it")


@pytest.fixture(autouse=True)
def _reset_smla_compile_count():
    """engine._COMPILE_COUNT is process-global, so absolute values are
    test-order-dependent.  Rebase it per test; compile-budget assertions
    read deltas from zero.  The executable cache is untouched — resetting
    never causes recompiles."""
    from repro.core.smla import engine
    engine.reset_compile_count()
    yield


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def run_subprocess_jax(code: str, n_devices: int = 8, timeout: int = 420,
                       env_overrides: dict | None = None):
    """Run a JAX snippet in a subprocess with forced host device count.
    `env_overrides` sets environment variables (a None value unsets)."""
    env = dict(os.environ)
    for k, v in (env_overrides or {}).items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout
