#!/usr/bin/env python
"""``make bench-check``'s workload: a tiny, deterministic, fully-observed
replay whose BENCH JSON is compared against a committed golden snapshot.

This is NOT a performance benchmark — it is the regression sentinel's
canary: small enough to run on every CI push (seconds, not minutes), but
exercising the real batched replay engine, telemetry, the metrics
registry and the health monitor, and emitting every metric class
``tools/bench_compare.py`` knows how to compare:

* ``steady_state`` — tick latency percentiles and the compile/execute
  split from the telemetry recorder (timing class: noisy, compared under
  the loose timing tolerance, skipped entirely cross-platform);
* ``objective`` — cost integral, churn, SLO ticks from the replay metrics
  (objective class: deterministic, compared tightly even cross-platform);
* ``health`` — breach counters and KKT certification stats from the
  attached ``HealthMonitor``.

The provenance block carries the config digest + seed list, so a golden
produced by a different configuration refuses to compare instead of
producing nonsense deltas.

Run:    PYTHONPATH=src python benchmarks/check_bench.py [--json PATH]
Golden: PYTHONPATH=src python benchmarks/check_bench.py --golden
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO, "benchmarks", "artifacts",
                           "BENCH_check.json")
GOLDEN_OUT = os.path.join(REPO, "benchmarks", "golden", "BENCH_check.json")

# the whole experiment definition — digested into provenance so the
# sentinel refuses to compare two runs of DIFFERENT experiments
CONFIG = {
    "bench": "check_bench",
    "catalog_stride": 40,
    "base_demand": [8.0, 16.0, 4.0, 100.0],
    "tenants": [
        {"kind": "diurnal", "scale": 1.0, "amplitude": 0.3},
        {"kind": "ramp", "scale": 0.6},
        {"kind": "constant", "scale": 0.8},
    ],
    "T": 8,
    "n_starts": 2,
    "replay_mode": "batched",
    "controller": "myopic",
    "deadline_ms": 10000.0,
}
SEEDS = [0, 1, 2]


def run() -> dict:
    """Run the canary replay and assemble the BENCH doc (sans provenance)."""
    from repro.core import Catalog, make_cloud_catalog
    from repro.fleet import TenantSpec, make_trace, replay_fleet
    from repro.obs import (HealthMonitor, MetricRegistry, ReplayReport,
                           collect_metrics, telemetry)

    catalog = Catalog(make_cloud_catalog().instances[::CONFIG["catalog_stride"]])
    base = np.asarray(CONFIG["base_demand"], np.float64)
    specs = []
    for seed, tn in zip(SEEDS, CONFIG["tenants"]):
        kw = {k: v for k, v in tn.items() if k not in ("kind", "scale")}
        specs.append(TenantSpec(
            name=f"{tn['kind']}{seed}", n_starts=CONFIG["n_starts"],
            trace=make_trace(tn["kind"], base * tn["scale"], CONFIG["T"],
                             seed=seed, **kw)))
    registry = MetricRegistry()
    monitor = HealthMonitor(deadline_ms=CONFIG["deadline_ms"],
                            registry=registry)
    with telemetry() as rec, collect_metrics(registry=registry):
        res = replay_fleet(catalog, specs,
                           replay_mode=CONFIG["replay_mode"],
                           controller=CONFIG["controller"],
                           run_ca_baseline=True, health=monitor)
    report = ReplayReport.from_recorder(rec)
    health = monitor.report().to_dict()
    health.pop("events")            # events carry no comparable numbers
    health.pop("deadline_miss_ticks")   # wall-clock dependent: not golden
    m = res.metrics
    return {
        "steady_state": {
            "tick_ms": report.tick_ms,
            "compile_ms": report.compile_ms,
            "execute_ms": report.execute_ms,
        },
        "objective": {
            "cost_integral": m.total_cost_integral,
            "total_churn": m.total_churn,
            "slo_violation_ticks": m.total_slo_violation_ticks,
            "max_churn_violation": m.max_churn_violation,
            "ca_cost_integral": m.baseline_cost_integral,
            "savings_vs_ca_pct": m.cost_savings_vs_baseline_pct,
        },
        "health": health,
        "metrics_snapshot": {
            # exporter smoke: the registry must be serializable; only the
            # deterministic counter set is embedded for comparison
            "n_metrics": len(registry.snapshot()["histograms"])
            + len(registry.snapshot()["counters"])
            + len(registry.snapshot()["gauges"]),
        },
        "config": CONFIG,
    }


def main(argv) -> int:
    out = DEFAULT_OUT
    if "--golden" in argv:
        out = GOLDEN_OUT
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            raise SystemExit("--json requires a path argument")
        out = argv[i + 1]

    from repro.obs import provenance_block

    doc = run()
    doc["provenance"] = provenance_block(argv, config=CONFIG, seeds=SEEDS)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"[check_bench] wrote {out}")
    print(f"[check_bench] objective: {doc['objective']}")
    print(f"[check_bench] tick_ms: {doc['steady_state']['tick_ms']}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    sys.exit(main(sys.argv[1:]))
