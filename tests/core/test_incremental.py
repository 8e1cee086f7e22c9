"""Incremental adoption (paper III.E): L1-ball projection properties and the
bounded-churn solve."""
import jax
import pytest
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

from repro.core import project_l1_ball, project_incremental, solve_incremental
from repro.core.incremental import _l1_threshold
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


def _duchi_reference(v, radius):
    """Duchi et al. (2008) in float64: sort, cumsum, rho; ``(w, theta)``,
    theta None when ``v`` is already inside the ball."""
    v = np.asarray(v, np.float64)
    a = np.abs(v)
    if a.sum() <= radius:
        return v, None
    u = np.sort(a)[::-1]
    css = np.cumsum(u)
    ks = np.arange(1, a.size + 1)
    cond = u * ks > css - radius
    rho = ks[cond].max() if cond.any() else 1
    theta = (css[rho - 1] - radius) / rho
    return np.sign(v) * np.maximum(a - theta, 0.0), theta


def _shaped_vector(shape, n, seed):
    """The four magnitude shapes the projection meets: dense normal,
    heavy-tailed Cauchy, 1%-sparse spikes and 10%-sparse exponentials."""
    rng = np.random.default_rng(seed)
    if shape == "normal":
        return rng.normal(size=n)
    if shape == "cauchy":
        return rng.standard_cauchy(size=n)
    share, scale = {"spikes": (0.01, 10.0), "sparse_exp": (0.1, 3.0)}[shape]
    idx = rng.choice(n, max(1, int(n * share)), replace=False)
    v = np.zeros(n)
    v[idx] = (rng.normal(0.0, scale, idx.size) if shape == "spikes" else
              rng.exponential(scale, idx.size) * rng.choice([-1, 1], idx.size))
    return v


_SHAPES = ("normal", "cauchy", "spikes", "sparse_exp")
_project = jax.jit(project_l1_ball)
_threshold = jax.jit(_l1_threshold)


@pytest.mark.parametrize("shape", _SHAPES)
@pytest.mark.parametrize("n", [2, 40, 1880])
def test_l1_projection_matches_duchi_reference(n, shape):
    for seed in range(5):
        v = np.float32(_shaped_vector(shape, n, seed))
        for radius in (0.5, 8.0):
            w = np.asarray(_project(jnp.asarray(v), jnp.float32(radius)))
            ref, theta = _duchi_reference(v, radius)
            scale = max(abs(theta), radius) if theta is not None else 0.0
            np.testing.assert_allclose(w, ref, rtol=1e-5, atol=1e-5 * scale)
            if theta is not None:
                got, _ = _threshold(jnp.abs(jnp.asarray(v)),
                                    jnp.float32(radius))
                np.testing.assert_allclose(float(got), theta, rtol=1e-5)


@pytest.mark.parametrize("v, radius, expected", [
    ([3.0, -1.0, 2.0], 0.0, [0.0, 0.0, 0.0]),           # radius 0
    ([0.0, 0.0, 0.0], 1.0, [0.0, 0.0, 0.0]),            # v = 0
    ([0.0, 0.0, 0.0], 0.0, [0.0, 0.0, 0.0]),            # v = 0, radius 0
    ([2.0, -2.0, 1.0, 1.0], 1.0, [0.5, -0.5, 0.0, 0.0]),  # tie at theta
    ([0.0, -5.0, 0.0], 2.0, [0.0, -2.0, 0.0]),          # one non-zero
    ([3.0, -3.0, 3.0, 3.0], 4.0, [1.0, -1.0, 1.0, 1.0]),  # all equal
    ([1.0, -2.0, 0.5], 100.0, [1.0, -2.0, 0.5]),        # inside the ball
    ([1.0, -2.0, 0.5], 3.5, [1.0, -2.0, 0.5]),          # on the sphere
])
def test_l1_projection_edge_cases(v, radius, expected):
    w = np.asarray(_project(jnp.asarray(v, jnp.float32),
                            jnp.float32(radius)))
    np.testing.assert_allclose(w, expected, atol=1e-6)
    np.testing.assert_allclose(w, _duchi_reference(v, radius)[0], atol=1e-6)
    if np.abs(v).sum() <= radius:
        _, rounds = _threshold(jnp.abs(jnp.asarray(v, jnp.float32)),
                               jnp.float32(radius))
        assert int(rounds) == 0


@pytest.mark.parametrize("shape", _SHAPES)
def test_l1_threshold_rounds_bounded(shape):
    """The fixed point needs a handful of rounds at the catalog's width,
    far below its n-round worst case."""
    for seed in range(20):
        a = jnp.abs(jnp.asarray(_shaped_vector(shape, 1880, seed),
                                jnp.float32))
        for radius in (1.0, 8.0, 100.0):
            _, rounds = _threshold(a, jnp.float32(radius))
            assert int(rounds) <= 16
            assert (int(rounds) == 0) == bool(jnp.sum(a) <= radius)


def test_l1_projection_vmap_matches_rows_bitwise():
    """12 Armijo candidates x 8 lanes, mixed shapes and radii (some rows
    inside the ball): the batched call equals the row-by-row call bit for
    bit, though rows need different numbers of rounds."""
    n = 1880
    rows = np.stack([_shaped_vector(_SHAPES[i % 4], n, i) * (0.5 + i % 3)
                     for i in range(96)]).astype(np.float32)
    radii = np.float32(np.resize([0.5, 8.0, 40.0, 1e4], 96))
    v = jnp.asarray(rows).reshape(12, 8, n)
    r = jnp.asarray(radii).reshape(12, 8)
    batched = np.asarray(jax.jit(jax.vmap(jax.vmap(project_l1_ball)))(v, r))
    theta_b, rounds_b = jax.jit(jax.vmap(jax.vmap(_l1_threshold)))(
        jnp.abs(v), r)
    assert len(set(np.asarray(rounds_b).ravel().tolist())) > 1
    for i in range(12):
        for j in range(8):
            row = np.asarray(_project(v[i, j], r[i, j]))
            assert np.array_equal(batched[i, j], row)
            theta, rounds = _threshold(jnp.abs(v[i, j]), r[i, j])
            assert np.array_equal(np.asarray(theta_b[i, j]),
                                  np.asarray(theta))
            assert int(rounds_b[i, j]) == int(rounds)
