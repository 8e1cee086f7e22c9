"""Shared helpers of the benchmark's CPU tests: the harness is imported
from the repository root, and cells run at a tiny size from the files in
``tests/bench/data`` (a 47-type catalog, a few lanes or tenants)."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(scope="session")
def run_tiny():
    """``run_tiny(cell, seed, seconds, trace=False)`` -> result dict of one
    tiny run, device check skipped."""
    import jax

    from bench import harness

    clock = harness.CompileClock()

    def run(cell_name, seed, seconds, trace=False):
        cell = harness.load_cell(cell_name, DATA / "benchmark.json",
                                 DATA / "traffic")
        return harness.run_cell(cell, seed, seconds, trace,
                                time.perf_counter(), jax.devices(), clock,
                                peaks={"flops_per_s": 1e12,
                                       "bytes_per_s": 1e11})

    return run
