"""Benchmark of the SMLA sweep on the TPU: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; see ``bench/lib/harness.py``.
JAX's persistent compilation cache is the checkout's ``.jax_cache/``, with
no size limit: the entries are a few MB, and the limit's eviction keeps
access-time files that another writer of the directory may not.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main() -> int:
    from bench.lib import harness
    return harness.main(sys.argv[1:], T_START)


if __name__ == "__main__":
    raise SystemExit(main())
