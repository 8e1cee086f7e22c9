"""Time-expanded program invariants.

Property-style tests run through the deterministic ``repro.testing`` shim
when the image lacks hypothesis."""
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.objective as obj
from repro.horizon import (churn_bound_grad, churn_bound_penalty,
                           coupling_grad, coupling_penalty, expand_problems,
                           horizon_objective, horizon_objective_terms,
                           tick_problem)
from repro.testing import make_toy_problem


def _window(seed, H, n=10, m=3):
    """H same-shape per-tick problems with different demands (what a real
    lookahead window looks like: one catalog, drifting demand)."""
    return [make_toy_problem(seed=seed + h, n=n, m=m) for h in range(H)]


@settings(max_examples=8)
@given(seed=st.integers(0, 10_000), H=st.integers(1, 6))
def test_zero_coupling_decouples_into_per_tick_objectives(seed, H):
    """Satellite acceptance: with coupling_w == 0 the time-expanded
    objective equals the SUM of per-tick core.objective.objective values —
    the program decouples exactly."""
    probs = _window(seed, H)
    hp = expand_problems(probs, coupling_w=0.0)
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(0.0, 5.0, size=(H, probs[0].n)), jnp.float32)
    total = float(horizon_objective(hp, X))
    per_tick = sum(float(obj.objective(pb, X[h]))
                   for h, pb in enumerate(probs))
    np.testing.assert_allclose(total, per_tick, rtol=1e-6)


@settings(max_examples=6)
@given(seed=st.integers(0, 10_000), H=st.integers(2, 5))
def test_coupling_grad_matches_autodiff(seed, H):
    """The hand-written smoothed-|.| coupling gradient must agree with
    jax.grad of the penalty."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(H, 7)), jnp.float32)
    w, eps = jnp.asarray(0.3, jnp.float32), jnp.asarray(1e-4, jnp.float32)
    g_auto = jax.grad(lambda x: coupling_penalty(x, w, eps))(X)
    np.testing.assert_allclose(np.asarray(coupling_grad(X, w, eps)),
                               np.asarray(g_auto), rtol=1e-4, atol=1e-6)


@settings(max_examples=6)
@given(seed=st.integers(0, 10_000), H=st.integers(2, 5))
def test_churn_bound_grad_matches_autodiff(seed, H):
    """The soft churn-bound hinge gradient must agree with jax.grad."""
    rng = np.random.default_rng(seed)
    # large moves so some transitions exceed the bound and some don't
    X = jnp.asarray(rng.normal(scale=3.0, size=(H, 7)), jnp.float32)
    delta, w, eps = (jnp.asarray(4.0, jnp.float32),
                     jnp.asarray(5.0, jnp.float32),
                     jnp.asarray(1e-4, jnp.float32))
    g_auto = jax.grad(lambda x: churn_bound_penalty(x, delta, w, eps))(X)
    np.testing.assert_allclose(np.asarray(churn_bound_grad(X, delta, w, eps)),
                               np.asarray(g_auto), rtol=1e-3, atol=1e-4)


def test_churn_bound_inactive_within_budget():
    """Transitions within delta_max contribute nothing (hinge inactive)."""
    X = jnp.asarray([[0.0] * 5, [0.5] * 5], jnp.float32)   # churn 2.5 < 4
    assert float(churn_bound_penalty(X, 4.0, 10.0, 1e-6)) < 1e-4
    assert float(jnp.abs(churn_bound_grad(X, 4.0, 10.0, 1e-6)).max()) == 0.0


def test_coupling_vanishes_on_constant_plan():
    # s(0) = 0 exactly (the smoothing floor is subtracted)
    X = jnp.ones((4, 6)) * 3.0
    assert float(coupling_penalty(X, 1.0, 1e-6)) == 0.0


def test_expand_problems_padding_is_exact():
    """Padding a window up to bucket dims (as the batched fleet replay does)
    must not change the objective of an embedded plan."""
    probs = _window(7, 3, n=10, m=3)
    hp = expand_problems(probs, coupling_w=0.2)
    hp_pad = expand_problems(probs, coupling_w=0.2, n_max=16, m_max=4,
                             p_max=4)
    rng = np.random.default_rng(0)
    X = rng.uniform(0.0, 4.0, size=(3, 10)).astype(np.float32)
    X_pad = np.zeros((3, 16), np.float32)
    X_pad[:, :10] = X
    np.testing.assert_allclose(
        float(horizon_objective(hp, jnp.asarray(X))),
        float(horizon_objective(hp_pad, jnp.asarray(X_pad))), rtol=1e-6)


def test_tick_problem_round_trip():
    probs = _window(3, 4)
    hp = expand_problems(probs)
    for h, pb in enumerate(probs):
        back = tick_problem(hp, h)
        np.testing.assert_array_equal(np.asarray(back.K), np.asarray(pb.K))
        np.testing.assert_array_equal(np.asarray(back.d), np.asarray(pb.d))


def test_objective_terms_split():
    probs = _window(11, 3)
    hp = expand_problems(probs, coupling_w=0.5)
    X = jnp.ones((3, probs[0].n))
    terms = horizon_objective_terms(hp, X)
    assert terms["per_tick"].shape == (3,)
    np.testing.assert_allclose(
        float(jnp.sum(terms["per_tick"]) + terms["coupling"]),
        float(horizon_objective(hp, X)), rtol=1e-6)


@settings(max_examples=6)
@given(seed=st.integers(0, 10_000), H=st.integers(2, 5))
def test_commit_coupling_grad_matches_autodiff(seed, H):
    """The committed transition's churn-price gradient (only row 0 moves;
    x_current is a constant) must agree with jax.grad."""
    from repro.horizon import commit_coupling_grad, commit_coupling_penalty
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(H, 7)), jnp.float32)
    xc = jnp.asarray(rng.normal(size=(7,)), jnp.float32)
    w, eps = jnp.asarray(0.3, jnp.float32), jnp.asarray(1e-4, jnp.float32)
    g_auto = jax.grad(lambda x: commit_coupling_penalty(x, xc, w, eps))(X)
    np.testing.assert_allclose(
        np.asarray(commit_coupling_grad(X, xc, w, eps)),
        np.asarray(g_auto), rtol=1e-4, atol=1e-6)


def test_commit_coupling_vanishes_when_committed_row_holds():
    """No committed movement -> no price (s(0) = 0 exactly), regardless of
    what the planned rows do."""
    from repro.horizon import commit_coupling_grad, commit_coupling_penalty
    xc = jnp.asarray([2.0, 3.0, 1.0])
    X = jnp.stack([xc, xc * 4.0, xc * 0.5])
    assert float(commit_coupling_penalty(X, xc, 1.0, 1e-6)) == 0.0
    g = commit_coupling_grad(X, xc, 1.0, 1e-6)
    assert float(jnp.abs(g[0]).max()) == 0.0
    assert float(jnp.abs(g[1:]).max()) == 0.0      # planned rows untouched
