"""Benchmark driver — one section per paper table/figure:
  Fig.1  five scenarios (CA vs optimization)       -> scenarios.run()
  Fig.2  demand-scaling sweep + over-provisioning  -> scaling.run()
  SIII   solver approaches + Pallas kernel         -> solver_bench.run()
  (ours) batched multi-tenant fleet solving        -> fleet_bench.run()
  (ours) roofline table from dry-run artifacts     -> roofline.run()
Writes benchmarks/artifacts/results.json.
"""
import json
import os
import sys
import time


def main() -> None:
    t0 = time.time()
    from benchmarks import fleet_bench, roofline, scaling, scenarios, solver_bench
    results = {}
    results["scenarios"] = scenarios.run()
    results["scaling"] = scaling.run()
    results["solver"] = solver_bench.run()
    results["fleet"] = fleet_bench.run()
    results["roofline"] = roofline.run()
    out = os.path.join(os.path.dirname(__file__), "artifacts", "results.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"\n[benchmarks] all sections done in {time.time()-t0:.0f}s -> {out}")


if __name__ == '__main__':
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
