"""One sweep of a serve cell's offered rate, in one process.

    python3 bench/sweep.py --workload serve-b256-scan10s --seed <n> \
        --seconds <s> --scans 20,10,5,2.5

Fills the engine once, then for each scan interval runs the cell's window
with every tenant updating every ``scan`` seconds, and prints one JSON line
per interval: decision latency p50 and p95, requests, ticks and the
reference's checks. It records where the cell's fixed rate sits against the
rate the engine sustains; the benchmark's own runs never use it.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def main(argv) -> int:
    import argparse

    from bench import harness
    from bench.traffic import open_loop

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scans", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devs = harness.require_devices(cell.chips)
    import jax
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    Driver = harness.load_driver(cell.config["engine"])
    base = Driver(cell.config, cell.traffic, args.seed)
    base.setup(args.seconds)
    limits = harness.load_limits()
    for scan in (float(s) for s in args.scans.split(",")):
        traffic = dict(cell.traffic, scan_s=scan)
        d = Driver(cell.config, traffic, args.seed)
        d.engine, d.setup_ticks = base.engine, 0
        d.initial, d.events = open_loop(traffic, int(cell.config["lanes"]),
                                        args.seconds, args.seed)
        t0 = time.perf_counter()
        d.window(args.seconds)
        d.finish()
        for line in d.report():
            print("bench:", line, flush=True)
        attempted, failed = d.attempted_failed()
        print(json.dumps({
            "scan_s": scan, "offered_per_s": len(d.initial) / scan,
            **d.end_to_end(), "attempted": attempted, "failed": failed,
            "ticks": d.window_ticks, "wall_s": time.perf_counter() - t0,
            "checks": harness.judge(d, limits).as_dict()}),
            flush=True)
    return 0


if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
    raise SystemExit(main(sys.argv[1:]))
