"""Solver tests: convergence, feasibility, KKT residuals, multistart."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import repro.core.objective as obj
from repro.core import (SolverConfig, kkt_report, multistart_solve,
                        solve_relaxation)
from repro.core.solver import phase1_point
from repro.testing import make_toy_problem

CFG = SolverConfig(max_iters=300, barrier_rounds=3)


def test_phase1_reaches_feasibility(toy_problem):
    x = phase1_point(toy_problem, jnp.zeros(toy_problem.n))
    lo, hi = obj.constraint_residuals(toy_problem, x)
    assert float(jnp.min(lo)) >= -1e-2
    assert float(jnp.min(hi)) >= -1e-2


def test_solution_feasible(toy_problem):
    res = solve_relaxation(toy_problem, jnp.zeros(toy_problem.n), CFG)
    assert bool(res.feasible)


def test_solver_descends(toy_problem):
    x0 = jnp.full(toy_problem.n, 3.0)
    x0p = phase1_point(toy_problem, x0)
    f0 = float(obj.objective(toy_problem, x0p))
    res = solve_relaxation(toy_problem, x0, CFG)
    assert float(res.fun) <= f0 + 1e-5


def test_convex_instance_start_independence():
    """alpha=0 (convex): different starts reach the same objective value."""
    prob = make_toy_problem(alpha=0.0, gamma=0.0)
    funs = []
    for s in [0.0, 1.0, 5.0]:
        res = solve_relaxation(prob, jnp.full(prob.n, s), CFG)
        funs.append(float(res.fun))
    assert max(funs) - min(funs) <= 5e-2 * max(abs(min(funs)), 1.0)


def test_kkt_residuals_small_on_convex():
    prob = make_toy_problem(alpha=0.0, gamma=0.0)
    res = solve_relaxation(prob, jnp.zeros(prob.n), CFG)
    # final barrier temperature of CFG: t0 * kappa^(rounds-1) = 100
    t_final = CFG.barrier_t0 * CFG.barrier_kappa ** (CFG.barrier_rounds - 1)
    rep = kkt_report(prob, res.x, barrier_t=jnp.asarray(t_final))
    scale = float(jnp.max(jnp.abs(prob.c))) + 1.0
    assert float(rep.primal_lo) <= 1e-2
    assert float(rep.primal_hi) <= 1e-2
    assert float(rep.dual) <= 1e-6            # nonneg by construction
    # interior-point duals make stationarity ~ solver tolerance
    assert float(rep.stationarity) <= 0.15 * scale
    # complementary slackness decays as 1/t
    assert float(rep.comp_slack) <= 10.0 / t_final + 0.1


def test_multistart_picks_best(toy_problem):
    ms = multistart_solve(toy_problem, n_starts=6, cfg=CFG)
    merit = np.where(np.asarray(ms.all_feasible), np.asarray(ms.all_fun), np.inf)
    assert float(ms.best.fun) <= np.min(merit) + 1e-5


@settings(max_examples=10)
@given(seed=st.integers(0, 1000))
def test_solver_feasible_property(seed):
    prob = make_toy_problem(seed=seed)
    res = solve_relaxation(prob, jnp.zeros(prob.n), CFG)
    # solver must end feasible (phase-1 + projections guarantee reachable)
    assert bool(res.feasible)
    assert np.all(np.isfinite(np.asarray(res.x)))
