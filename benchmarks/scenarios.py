"""Paper Fig. 1 + Appendix A: the five scenarios, CA (5-seed median, as in
§IV.A.4) vs convex optimization. Prints the comparison table and per-dim
utilization radar data; returns records for run.py."""
from __future__ import annotations

import time

import numpy as np

from repro.core import (build_scenarios, evaluate, make_cloud_catalog,
                        optimize, per_dim_utilization,
                        simulate_cluster_autoscaler)

PAPER_SAVINGS = {"s1_greenfield": 0.0, "s2_scaling": 42.5,
                 "s3_enterprise": 80.5, "s4_memory": 87.2,
                 "s5_constrained": 71.1}


def run(n_seeds: int = 5, n_starts: int = 6, radar: bool = True):
    cat = make_cloud_catalog()
    records = []
    print("=" * 108)
    print("Fig.1 — Cost comparison: Kubernetes Cluster Autoscaler vs convex "
          "optimization (5-seed CA median)")
    print("=" * 108)
    saves = []
    for s in build_scenarios(cat):
        t0 = time.time()
        res = optimize(cat, s, n_starts=n_starts)
        ca_runs = [simulate_cluster_autoscaler(cat, s.pools, s.demand, seed=sd)
                   for sd in range(n_seeds)]
        ca_m = [evaluate(cat, r.counts, s.demand) for r in ca_runs]
        ca_cost = float(np.median([m.total_cost for m in ca_m]))
        ca_over = float(np.median([m.overprovision_pct for m in ca_m]))
        save = 100 * (ca_cost - res.metrics.total_cost) / max(ca_cost, 1e-9)
        saves.append(save)
        om = res.metrics
        rec = dict(name=s.name, opt_cost=om.total_cost, ca_cost=ca_cost,
                   savings_pct=save, paper_savings_pct=PAPER_SAVINGS[s.name],
                   opt_util=om.utilization_pct,
                   opt_over=om.overprovision_pct, ca_over=ca_over,
                   opt_diversity=om.instance_diversity,
                   opt_providers=om.provider_fragmentation,
                   satisfied=om.satisfied, wall_s=time.time() - t0)
        records.append(rec)
        print(f"{s.name:16s} opt=${om.total_cost:7.3f}  CA=${ca_cost:7.3f}  "
              f"save={save:5.1f}% (paper {PAPER_SAVINGS[s.name]:5.1f}%)  "
              f"util={om.utilization_pct:5.1f}%  over={om.overprovision_pct:8.1f}% "
              f"(CA {ca_over:9.1f}%)  div={om.instance_diversity} "
              f"prov={om.provider_fragmentation}  [{rec['wall_s']:.1f}s]")
        if radar:
            u = per_dim_utilization(cat, res.counts, s.demand)
            ca_best = ca_runs[int(np.argmin([m.total_cost for m in ca_m]))]
            u_ca = per_dim_utilization(cat, ca_best.counts, s.demand)
            dims = ("cpu", "mem", "net", "storage")
            print("    radar (util/dim)  opt: "
                  + " ".join(f"{d}={x:.2f}" for d, x in zip(dims, u))
                  + "  | CA: "
                  + " ".join(f"{d}={x:.2f}" for d, x in zip(dims, u_ca)))
    avg = float(np.mean(saves))
    print("-" * 108)
    print(f"average savings: {avg:.1f}%   (paper: 56.3%)")
    return {"scenarios": records, "avg_savings_pct": avg}


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
