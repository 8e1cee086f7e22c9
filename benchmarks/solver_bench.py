"""§III approaches benchmark: relaxation quality, KKT residuals, rounding vs
branch-and-bound, multistart spread, Pareto grid — plus the Pallas
alloc_objective kernel vs the jnp path (us/call on the solver hot loop)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SolverConfig, branch_and_bound, build_scenarios,
                        grid_search, kkt_report, make_cloud_catalog,
                        multistart_solve, problem_from_scenario,
                        round_and_polish, solve_relaxation)
import repro.core.objective as obj


def run(n_starts: int = 6):
    cat = make_cloud_catalog()
    scens = build_scenarios(cat)
    out = {}
    print("=" * 100)
    print("Solver benchmark (paper §III approaches)")
    print("=" * 100)

    rows = []
    for s in scens[:3]:
        prob = problem_from_scenario(cat, s)
        t0 = time.time()
        ms = multistart_solve(prob, n_starts=n_starts)
        t_ms = time.time() - t0
        spread = float(jnp.max(ms.all_fun) - jnp.min(ms.all_fun))
        rep = kkt_report(prob, ms.best.x)
        f_round = float(ms.fun_int)
        t0 = time.time()
        bnb = branch_and_bound(prob, np.asarray(ms.best.x), max_nodes=12)
        t_bnb = time.time() - t0
        f_bnb = min(bnb.fun, f_round)
        rows.append(dict(name=s.name, relax_fun=float(ms.best.fun),
                         round_fun=f_round, bnb_fun=f_bnb,
                         bnb_gain_pct=100 * (f_round - f_bnb) / max(abs(f_round), 1e-9),
                         kkt_stationarity=float(rep.stationarity),
                         kkt_comp=float(rep.comp_slack),
                         multistart_spread=spread,
                         t_multistart_s=t_ms, t_bnb_s=t_bnb,
                         bnb_nodes=bnb.nodes_explored))
        r = rows[-1]
        print(f"{r['name']:16s} relax={r['relax_fun']:7.4f} round={r['round_fun']:7.4f} "
              f"bnb={r['bnb_fun']:7.4f} (gain {r['bnb_gain_pct']:4.1f}%) "
              f"KKT(stat={r['kkt_stationarity']:.3g}, comp={r['kkt_comp']:.3g}) "
              f"spread={r['multistart_spread']:.3g} "
              f"[ms {r['t_multistart_s']:.1f}s, bnb {r['t_bnb_s']:.1f}s/"
              f"{r['bnb_nodes']}n]")
    out["approaches"] = rows

    # ---- Pallas kernel vs jnp objective+grad (solver hot loop) -------------
    prob = problem_from_scenario(cat, scens[0])
    from repro.kernels import resolve_interpret
    from repro.kernels.alloc_objective.ops import batched_value_and_grad
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.uniform(0, 3, (128, prob.n)), jnp.float32)

    def jnp_path(X):
        f = jax.vmap(lambda x: obj.objective(prob, x))(X)
        g = jax.vmap(lambda x: obj.grad_objective(prob, x))(X)
        return f, g

    jnp_path_j = jax.jit(jnp_path)
    f1, g1 = jnp_path_j(X)
    f2, g2 = batched_value_and_grad(prob, X)
    err = float(jnp.max(jnp.abs(g1 - g2)))

    def timeit(fn, reps=20):
        fn(X)[0].block_until_ready()
        t0 = time.time()
        for _ in range(reps):
            fn(X)[0].block_until_ready()
        return (time.time() - t0) / reps * 1e6

    us_jnp = timeit(jnp_path_j)
    us_pal = timeit(lambda X: batched_value_and_grad(prob, X))
    print("-" * 100)
    mode = "interpreted" if resolve_interpret() else "compiled"
    print(f"alloc_objective (S=128, n={prob.n}): jnp={us_jnp:.0f}us/call  "
          f"pallas({mode})={us_pal:.0f}us/call  max|dgrad|={err:.2e}")
    print("  (the kernel compiles on TPU; on other backends it runs in the "
          "Pallas interpreter, which only validates correctness)")
    out["kernel"] = {"us_jnp": us_jnp, "us_pallas": us_pal,
                     "pallas_mode": mode, "grad_err": err}

    # ---- Pareto / parameter tuning (paper §III.D) ---------------------------
    pts = grid_search(problem_from_scenario(cat, scens[2]),
                      alphas=(0.005, 0.02, 0.1), gammas=(0.001, 0.005, 0.02))
    frontier = [p for p in pts if p.on_frontier]
    print(f"Pareto grid: {len(pts)} points, {len(frontier)} on the "
          f"cost-fragmentation frontier")
    for p in frontier[:5]:
        print(f"  alpha={p.params['alpha']:<6g} gamma={p.params['gamma']:<6g} "
              f"cost=${p.cost:.3f} frag={p.fragmentation} div={p.diversity}")
    out["pareto_frontier_size"] = len(frontier)
    return out


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
