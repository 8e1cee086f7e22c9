"""Incremental adoption (paper III.E): L1-ball projection properties and the
bounded-churn solve."""
import pytest
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

from repro.core import project_l1_ball, project_incremental, solve_incremental
from repro.testing import make_toy_problem


@pytest.mark.slow
@settings(max_examples=50)
@given(seed=st.integers(0, 10_000), radius=st.floats(0.1, 20.0), dim=st.integers(2, 40))
def test_l1_projection_properties(seed, radius, dim):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(0, 5, dim), jnp.float32)
    w = project_l1_ball(v, jnp.asarray(radius, jnp.float32))
    # inside the ball
    assert float(jnp.sum(jnp.abs(w))) <= radius * (1 + 1e-4) + 1e-5
    # idempotent
    w2 = project_l1_ball(w, jnp.asarray(radius, jnp.float32))
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w), atol=1e-5)
    # no-op when already inside
    if float(jnp.sum(jnp.abs(v))) <= radius:
        np.testing.assert_allclose(np.asarray(w), np.asarray(v), atol=1e-6)


@settings(max_examples=25)
@given(seed=st.integers(0, 10_000))
def test_l1_projection_is_closest_point(seed):
    """Projection must beat random candidates inside the ball on distance."""
    rng = np.random.default_rng(seed)
    dim, radius = 10, 3.0
    v = jnp.asarray(rng.normal(0, 4, dim), jnp.float32)
    w = np.asarray(project_l1_ball(v, jnp.asarray(radius, jnp.float32)))
    dist_w = np.linalg.norm(np.asarray(v) - w)
    for _ in range(20):
        z = rng.normal(0, 2, dim)
        norm = np.abs(z).sum()
        if norm > radius:
            z = z * (radius / norm)
        assert dist_w <= np.linalg.norm(np.asarray(v) - z) + 1e-4


def test_project_incremental_respects_both_sets(toy_problem):
    x_cur = jnp.full(toy_problem.n, 2.0)
    x = jnp.asarray(np.linspace(-3, 9, toy_problem.n), jnp.float32)
    delta = jnp.asarray(4.0)
    z = project_incremental(toy_problem, x, x_cur, delta)
    assert float(jnp.min(z)) >= -1e-6                       # box
    assert float(jnp.sum(jnp.abs(z - x_cur))) <= 4.0 + 1e-3  # churn bound


def test_solve_incremental_bounded_churn():
    prob = make_toy_problem(seed=3)
    x_cur = jnp.full(prob.n, 1.0)
    for delta in (0.5, 2.0, 8.0):
        x = solve_incremental(prob, x_cur, delta)
        churn = float(jnp.sum(jnp.abs(x - x_cur)))
        assert churn <= delta + 1e-3
