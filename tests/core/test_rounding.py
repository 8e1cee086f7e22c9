"""Greedy rounding (paper III.B) properties: integrality, feasibility,
monotone coverage; scale-down never breaks feasibility."""
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import repro.core.objective as obj
from repro.core import greedy_round, round_and_polish, scale_down, solve_relaxation, SolverConfig
from repro.core.rounding import cover_in_float64
from repro.testing import make_toy_problem


def _covers(prob, x):
    Kx = np.asarray(prob.K) @ np.asarray(x)
    return np.all(Kx >= np.asarray(prob.d - prob.mu) - 1e-5)


def test_rounding_integral_and_feasible(toy_problem):
    res = solve_relaxation(toy_problem, jnp.zeros(toy_problem.n),
                           SolverConfig(max_iters=200, barrier_rounds=2))
    x = np.asarray(greedy_round(toy_problem, res.x))
    assert np.allclose(x, np.round(x))
    assert _covers(toy_problem, x)


def test_round_and_polish_not_worse(toy_problem):
    res = solve_relaxation(toy_problem, jnp.zeros(toy_problem.n),
                           SolverConfig(max_iters=200, barrier_rounds=2))
    xa = greedy_round(toy_problem, res.x)
    xb = round_and_polish(toy_problem, res.x)
    fa = float(obj.objective(toy_problem, xa))
    fb = float(obj.objective(toy_problem, xb))
    assert fb <= fa + 1e-4
    assert _covers(toy_problem, np.asarray(xb))


def test_scale_down_keeps_feasibility(toy_problem):
    x = jnp.full(toy_problem.n, 6.0)  # heavily over-provisioned
    xd = scale_down(toy_problem, x)
    assert _covers(toy_problem, np.asarray(xd))
    assert float(jnp.sum(xd)) <= float(jnp.sum(x))


@settings(max_examples=20)
@given(seed=st.integers(0, 10_000))
def test_rounding_properties(seed):
    prob = make_toy_problem(seed=seed)
    rng = np.random.default_rng(seed + 13)
    x_star = jnp.asarray(rng.uniform(0, 3, prob.n), jnp.float32)
    x = np.asarray(greedy_round(prob, x_star))
    # integral
    assert np.allclose(x, np.round(x))
    # never below floor of input (clipped)
    floor = np.floor(np.clip(np.asarray(x_star), np.asarray(prob.lb),
                             np.asarray(prob.ub))) * np.asarray(prob.mask)
    assert np.all(x >= floor - 1e-6)
    # covers demand (toy problems always have full coverage available)
    assert _covers(prob, x)


@settings(max_examples=15)
@given(seed=st.integers(0, 10_000))
def test_scale_down_properties(seed):
    prob = make_toy_problem(seed=seed)
    x = jnp.asarray(np.full(prob.n, 5.0), jnp.float32)
    xd = np.asarray(scale_down(prob, x))
    assert _covers(prob, xd)
    assert np.allclose(xd, np.round(xd))
    # removal is monotone: no count increased
    assert np.all(xd <= 5.0 + 1e-6)



def test_cover_in_float64_closes_only_f32_sized_gaps():
    """Two units of a type holding just under half the demand come within
    5e-7 of it (relative), inside the f32 error of K x where rounding
    stops, yet fall 5e-4 short in float64 raw units, where
    ``repro.core.metrics`` checks coverage to 1e-6. The
    float64 top-up adds the best unit per dollar among the addable types; a
    covering allocation, or one really short, is left alone."""
    K = np.array([[1e3 * (0.5 - 2.5e-7), 1e3]])
    c, d = np.array([1.0, 3.0]), np.array([1e3])
    both = np.array([True, True])
    near = cover_in_float64(K, c, both, [2.0, 0.0], d)
    assert near.tolist() == [3.0, 0.0] and float(K[0] @ near) >= d[0]
    assert cover_in_float64(K, c, both, [3.0, 0.0], d).tolist() == [3.0, 0.0]
    assert cover_in_float64(K, c, both, [1.0, 0.0], d).tolist() == [1.0, 0.0]
    only_b = cover_in_float64(K, c, np.array([False, True]), [2.0, 0.0], d)
    assert only_b.tolist() == [2.0, 1.0]
