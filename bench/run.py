"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose first JAX device is a
TPU (there is no CPU fallback). See ``bench/harness.py`` and ``PERF.md``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
# JAX's persistent compilation cache lives inside the checkout, at a fixed
# path, whatever the environment says; the program's own cache set-up takes
# the directory from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_ROOT / ".jax_cache")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], T_START))
