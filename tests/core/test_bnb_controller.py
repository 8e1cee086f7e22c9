"""Branch-and-bound, controller, Pareto, and KKT-on-scenario tests."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.objective as obj
from repro.core import (InfrastructureOptimizationController, branch_and_bound,
                        build_scenarios, grid_search, kkt_report, optimize,
                        pareto_mask, problem_from_scenario, sensitivity,
                        solve_relaxation, SolverConfig)

from repro.testing import make_toy_problem


def test_bnb_never_worse_than_rounding(toy_problem):
    cfg = SolverConfig(max_iters=200, barrier_rounds=2)
    res = solve_relaxation(toy_problem, jnp.zeros(toy_problem.n), cfg)
    from repro.core import round_and_polish
    f_round = float(obj.objective(toy_problem,
                                  round_and_polish(toy_problem, res.x)))
    bnb = branch_and_bound(toy_problem, np.asarray(res.x), max_nodes=16, cfg=cfg)
    assert bnb.fun <= f_round + 1e-5
    assert np.allclose(bnb.x, np.round(bnb.x))
    assert bool(obj.is_feasible(toy_problem, jnp.asarray(bnb.x, jnp.float32), 1e-3))


def test_bnb_explores_and_reports(toy_problem):
    bnb = branch_and_bound(toy_problem, max_nodes=8)
    assert bnb.nodes_explored >= 1
    assert bnb.gap >= 0.0


@pytest.mark.slow
def test_controller_churn_bounded():
    from repro.core import Catalog, make_cloud_catalog
    cat = Catalog(make_cloud_catalog().instances[::40])
    ctl = InfrastructureOptimizationController(catalog=cat, delta_max=5.0,
                                               n_starts=2)
    d = np.array([8, 16, 4, 100], np.float64)
    first = ctl.step(d)
    assert first.metrics.satisfied
    # small demand bump: churn stays ~bounded (rounding may add slack of a
    # few units to preserve feasibility, which dominates the bound check)
    second = ctl.step(d * 1.1)
    assert second.metrics.satisfied
    assert second.churn <= 5.0 + 8.0  # delta + rounding slack



@pytest.mark.parametrize("allowed_idx", [None, np.array([3, 7])])
def test_controller_commit_covers_f32_near_miss_in_float64(allowed_idx):
    """Counts that cover the demand only to within f32 error are committed
    with one more unit, of a type the tenant may use; counts really short
    of the demand are committed as they are, and reported short."""
    from repro.core import Catalog, make_cloud_catalog
    cat = Catalog(make_cloud_catalog().instances[::40])
    K = cat.matrices()[0].astype(np.float64)
    counts = np.zeros(cat.n)
    counts[5] = 3.0
    provided = K @ counts
    ctl = InfrastructureOptimizationController(catalog=cat,
                                               allowed_idx=allowed_idx)
    step = ctl.apply_counts(provided * (1 + 5e-7), counts, replanned=True)
    assert step.metrics.satisfied
    added = np.flatnonzero(step.counts - counts)
    assert step.counts.sum() == 4.0 and len(added) == 1
    if allowed_idx is not None:
        assert added[0] in allowed_idx
    np.testing.assert_array_equal(ctl.x_current, step.counts)
    short = ctl.apply_counts(provided * 2.0, counts, replanned=False)
    assert not short.metrics.satisfied
    np.testing.assert_array_equal(short.counts, counts)

@pytest.mark.slow
def test_controller_failure_replan():
    from repro.core import Catalog, make_cloud_catalog
    cat = Catalog(make_cloud_catalog().instances[::40])
    ctl = InfrastructureOptimizationController(catalog=cat, delta_max=4.0,
                                               n_starts=2)
    d = np.array([16, 32, 8, 200], np.float64)
    ctl.step(d)
    # half the fleet dies
    failed = np.ceil(ctl.x_current * 0.5)
    st = ctl.replan_on_failure(failed, d)
    assert st.metrics.satisfied


def test_pareto_mask_handcrafted():
    pts = np.array([[1.0, 5.0], [2.0, 2.0], [3.0, 3.0], [5.0, 1.0]])
    mask = pareto_mask(pts)
    assert mask.tolist() == [True, True, False, True]


def test_grid_search_and_sensitivity(toy_problem):
    pts = grid_search(toy_problem, alphas=(0.01, 0.1), gammas=(0.001, 0.01))
    assert len(pts) == 4
    assert any(p.on_frontier for p in pts)
    from repro.core import PenaltyParams
    sens = sensitivity(toy_problem, PenaltyParams.create())
    assert set(sens) == {"alpha", "beta1", "beta2", "beta3", "gamma"}
    assert all(np.isfinite(v) for v in sens.values())


def test_kkt_on_scenario(small_catalog):
    from repro.core import Scenario
    s = build_scenarios(small_catalog)[0] if False else None
    # build a scenario directly on the small catalog
    demand = np.array([8, 16, 4, 100], np.float64)
    scen = Scenario(name="t", title="t", demand=demand, allowed_idx=None,
                    pools=[], existing=np.zeros(small_catalog.n))
    prob = problem_from_scenario(small_catalog, scen)
    res = solve_relaxation(prob, jnp.zeros(prob.n),
                           SolverConfig(max_iters=300, barrier_rounds=3))
    rep = kkt_report(prob, res.x)
    assert float(rep.primal_lo) <= 1e-2
    assert float(rep.dual) <= 1e-6
