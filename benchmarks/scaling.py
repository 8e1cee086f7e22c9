"""Paper Fig. 2 — demand-scaling sweep: cost curves (top) and
over-provisioning (bottom) as resource demands grow. The paper's claim:
CA cost grows ~linearly while the optimizer's curve is much flatter, and CA
over-provisions pathologically on asymmetric workloads."""
from __future__ import annotations

import numpy as np

from repro.core import (build_scenarios, evaluate, make_cloud_catalog,
                        optimize, scaled_scenario,
                        simulate_cluster_autoscaler)

FACTORS = (1.0, 2.0, 4.0, 8.0, 16.0)


def run(base_scenario: str = "s4_memory", n_seeds: int = 3, n_starts: int = 4):
    cat = make_cloud_catalog()
    base = {s.name: s for s in build_scenarios(cat)}[base_scenario]
    rows = []
    print("=" * 96)
    print(f"Fig.2 — scaling sweep on {base_scenario} (demand x factor)")
    print("=" * 96)
    for f in FACTORS:
        s = scaled_scenario(base, f)
        res = optimize(cat, s, n_starts=n_starts)
        ca_costs, ca_overs = [], []
        for sd in range(n_seeds):
            ca = simulate_cluster_autoscaler(cat, s.pools, s.demand, seed=sd)
            m = evaluate(cat, ca.counts, s.demand)
            ca_costs.append(m.total_cost)
            ca_overs.append(m.overprovision_pct)
        row = dict(factor=f, opt_cost=res.metrics.total_cost,
                   ca_cost=float(np.median(ca_costs)),
                   opt_over=res.metrics.overprovision_pct,
                   ca_over=float(np.median(ca_overs)))
        rows.append(row)
        print(f"x{f:5.1f}  opt=${row['opt_cost']:8.3f}  CA=${row['ca_cost']:8.3f}  "
              f"ratio={row['ca_cost']/max(row['opt_cost'],1e-9):5.2f}  "
              f"over: opt={row['opt_over']:8.1f}%  CA={row['ca_over']:9.1f}%")
    # slope comparison (cost per unit demand factor, linear fit)
    fs = np.array([r["factor"] for r in rows])
    opt_slope = float(np.polyfit(fs, [r["opt_cost"] for r in rows], 1)[0])
    ca_slope = float(np.polyfit(fs, [r["ca_cost"] for r in rows], 1)[0])
    print("-" * 96)
    print(f"cost-vs-demand slope: opt={opt_slope:.4f} $/hr/x   CA={ca_slope:.4f} "
          f"$/hr/x   (flatter = better; paper: optimizer much flatter)")
    return {"rows": rows, "opt_slope": opt_slope, "ca_slope": ca_slope}


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
