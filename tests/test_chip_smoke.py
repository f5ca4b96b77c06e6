"""`chip_smoke.py` off the chip: it refuses the CPU, and its checks hold
on a tiny Fig. 12 grid (the full-size run needs the TPU)."""
import os
import subprocess
import sys

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_cpu_at_device_phase():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == "", f"printed on the CPU: {r.stdout!r}"


def test_fig12_phase_checks_pass_on_tiny_grid():
    chip_smoke.fig12_phase(n_mixes=1, n_req=20)
