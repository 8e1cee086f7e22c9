"""Scenario benchmark: the priced-term objective IR's three consumers
(docs/scenarios.md) replayed against the Cluster-Autoscaler baseline.

For each trace kind (diurnal, flash_crowd) the benchmark replays one fleet
three ways, sweeping each scenario's price knob to trace out a cost/SLO
FRONTIER — the point of pricing the tradeoff in $ instead of hand-tuned
penalty weights:

* slo      — ``with_slo_pricing``: sweep the contractual SLO-credit price.
             At price 0 the term is absent (the seed objective); raising it
             buys SLO ticks down with capacity the base cost alone would
             not justify.
* priority — ``with_priority_classes``: a critical/standard/batch class
             mix, sweeping the eviction price. Batch tenants' capacity is
             repriced toward its true expected cost, so their allocations
             (and the fleet frontier) shift while critical tenants hold.
* spot     — ``make_spot_fleet``: the catalog is widened with discounted
             spot twins, interruption risk is priced via the ``spot_risk``
             term, and a seeded ``spot_interruption`` overlay zeroes
             interrupted pools per tick. Sweeping the interruption rate
             trades spot savings against interruption-driven churn/SLO.

Every cell reports cost integral, SLO-violation ticks, churn, and savings
vs the SAME Cluster-Autoscaler baseline (pools sized from each trace's
peak demand; the CA side never sees terms or spot twins — it is the
operator status quo the scenarios are priced against). All replays use the
batched engine (one solve per shape bucket per tick), which the tests pin
to the sequential reference with terms active.

Run:  PYTHONPATH=src python benchmarks/scenario_bench.py
          [--quick] [--json PATH]

Writes machine-readable results (default benchmarks/BENCH_scenarios.json)
with a provenance block, like the other benchmarks, so the scenario
frontiers are tracked across PRs.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from repro.core import Catalog, make_cloud_catalog
from repro.fleet import (TenantSpec, make_spot_fleet, make_trace,
                         replay_fleet, with_priority_classes,
                         with_slo_pricing)
from repro.obs import provenance_block

DEFAULT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_scenarios.json")
# production-scale demand (same rationale as horizon_bench: allocations
# land at tens of nodes, so swings move whole nodes)
BASE = np.array([8.0, 16.0, 4.0, 100.0]) * 25
NOISE = 0.08

# the class mix assigned round-robin to the fleet: one protected tenant
# per three keeps the eviction pressure (protected peak-demand share)
# strictly inside (0, 1) for any fleet size >= 2
PRIORITY_MIX = ("critical", "standard", "batch")


def _fleet(catalog: Catalog, trace_kind: str, B: int, T: int):
    """B tenants on one shared catalog, staggered scales/seeds — the same
    fleet construction as horizon_bench so frontiers are comparable."""
    specs = []
    for s in range(B):
        kwargs = dict(seed=s, noise=NOISE)
        if trace_kind == "diurnal":
            kwargs.update(amplitude=0.45, phase=3.0 * s)
        elif trace_kind == "flash_crowd":
            kwargs.update(burst_scale=2.5, decay=5.0)
        specs.append(TenantSpec(
            name=f"{trace_kind}{s}",
            trace=make_trace(trace_kind, BASE * (0.7 + 0.2 * (s % 3)), T,
                             **kwargs),
            n_starts=2, delta_max=6.0))
    return specs


def _cell(metrics, t_replay: float) -> dict:
    """One frontier point: the replayed fleet vs its CA baseline."""
    out = dict(
        cost=metrics.total_cost_integral,
        slo_ticks=metrics.total_slo_violation_ticks,
        churn=metrics.total_churn,
        max_churn_violation=metrics.max_churn_violation,
        t_replay=t_replay,
    )
    if metrics.baseline is not None:
        out["ca_cost"] = metrics.baseline_cost_integral
        out["ca_slo_ticks"] = sum(t.slo_violation_ticks
                                  for t in metrics.baseline)
        out["savings_vs_ca_pct"] = metrics.cost_savings_vs_baseline_pct
    return out


def _replay_cell(catalog, specs, **kw) -> dict:
    t0 = time.time()
    res = replay_fleet(catalog, specs, replay_mode="batched",
                       run_ca_baseline=True, **kw)
    return _cell(res.metrics, time.time() - t0)


def _print_cell(label: str, c: dict) -> None:
    print(f"  {label:>24s} cost ${c['cost']:10.2f}  slo {c['slo_ticks']:3d} "
          f"(ca {c['ca_slo_ticks']:3d})  churn {c['churn']:7.1f}  "
          f"vs CA {c['savings_vs_ca_pct']:+6.1f}%")


def run(B: int = 3, T: int = 24,
        trace_kinds=("diurnal", "flash_crowd"),
        slo_prices=(0.0, 0.5, 2.0, 8.0),
        eviction_prices=(0.0, 0.15, 0.6),
        spot_rates=(0.02, 0.08, 0.2)):
    """The full sweep; returns the JSON-ready results dict. Each scenario's
    knob list is swept per trace kind; the knob-0 cells (price 0 / rate at
    its mildest) anchor the frontier at (or near) the unpriced seed
    objective."""
    catalog = Catalog(make_cloud_catalog().instances[::40])
    out = dict(config=dict(B=B, T=T, trace_kinds=list(trace_kinds),
                           slo_prices=list(slo_prices),
                           eviction_prices=list(eviction_prices),
                           spot_rates=list(spot_rates),
                           catalog_n=catalog.n),
               scenarios={})
    print("=" * 100)
    print(f"Scenario benchmark: B={B} tenants, T={T} ticks, "
          f"catalog n={catalog.n}")
    print("=" * 100)
    for kind in trace_kinds:
        specs = _fleet(catalog, kind, B, T)
        print(f"\n[{kind}]")
        cells = dict(slo=[], priority=[], spot=[])

        for price in slo_prices:
            scen = with_slo_pricing(specs, price=price) if price else specs
            c = _replay_cell(catalog, scen)
            c["price"] = price
            cells["slo"].append(c)
            _print_cell(f"slo price={price:g}", c)

        priorities = [PRIORITY_MIX[i % len(PRIORITY_MIX)] for i in range(B)]
        for ep in eviction_prices:
            scen = (with_priority_classes(specs, priorities, catalog=catalog,
                                          eviction_price=ep)
                    if ep else specs)
            c = _replay_cell(catalog, scen)
            c["eviction_price"] = ep
            cells["priority"].append(c)
            _print_cell(f"priority evict={ep:g}", c)

        for rate in spot_rates:
            spot_cat, scen = make_spot_fleet(catalog, specs,
                                             interruption_rate=rate,
                                             seed=7)
            c = _replay_cell(spot_cat, scen)
            c["interruption_rate"] = rate
            cells["spot"].append(c)
            _print_cell(f"spot rate={rate:g}", c)
        # on-demand-only reference for the spot frontier: the same fleet
        # denied the spot market entirely (the twins' savings ceiling)
        c = _replay_cell(catalog, specs)
        c["interruption_rate"] = None
        cells["spot_on_demand_ref"] = c
        _print_cell("spot (on-demand ref)", c)

        out["scenarios"][kind] = cells

    # acceptance summary: every scenario frontier must include at least one
    # cell that saves cost vs CA, and the slo frontier must be monotone
    # enough that SOME priced cell has no more SLO ticks than the unpriced
    # one (pricing shortage cannot make SLO worse at the frontier's end)
    checks = {}
    for kind, cells in out["scenarios"].items():
        slo0 = cells["slo"][0]
        checks[kind] = dict(
            all_scenarios_save_vs_ca=all(
                any(c["savings_vs_ca_pct"] > 0 for c in cells[s])
                for s in ("slo", "priority", "spot")),
            slo_pricing_not_worse=min(
                c["slo_ticks"] for c in cells["slo"]) <= slo0["slo_ticks"],
        )
    out["checks"] = checks
    ok = all(all(v.values()) for v in checks.values())
    print(f"\n[checks] {'PASS' if ok else 'FAIL'}: "
          + json.dumps(checks, sort_keys=True))
    return out


def main(argv):
    """CLI: --quick trims the sweep (2 tenants, 12 ticks, 2 knob values per
    scenario); --json PATH overrides the output file."""
    quick = "--quick" in argv
    json_path = DEFAULT_JSON
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            raise SystemExit("--json requires a path argument")
        json_path = argv[i + 1]
    if quick:
        out = run(B=2, T=12, slo_prices=(0.0, 2.0),
                  eviction_prices=(0.0, 0.6), spot_rates=(0.02, 0.2))
    else:
        out = run()
    out["config"]["quick"] = quick
    # trace seeds are tenant indices (make_fleet's spec loop); the config
    # digest makes bench_compare refuse quick-vs-full comparisons
    out["provenance"] = provenance_block(
        argv, config=out["config"], seeds=list(range(out["config"]["B"])))
    with open(json_path, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"\n[json] wrote {json_path}")


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    main(sys.argv[1:])
