"""Adaptive (BB/Armijo) vs fixed-step horizon solver: convergence contract.

The tentpole's speedup claim, pinned as tests so regressions fail loudly:

* at the SAME iteration budget the adaptive engine's horizon merit is never
  worse than the fixed-step engine's (property-swept across random catalogs
  and H ∈ {4, 8, 16} through the ``repro.testing`` shim's ``sampled_from``);
* on at least the median draw the adaptive engine reaches the fixed-step
  engine's FINAL merit in at most HALF the iterations;
* iterations-to-tolerance are recorded and bounded: a warm-started re-solve
  (the MPC steady state — the plan barely moves tick to tick) must
  early-stop far under the budget instead of burning all of it.

Merit here is the full relaxed time-expanded objective the solver actually
minimizes (per-tick eq.(1) + coupling + churn bound + planned band
penalty), evaluated by the SAME ``_horizon_merit_fns`` triple both engines
share — so the comparison cannot drift from the implementation.
"""
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import pytest
import jax.numpy as jnp
import numpy as np

from repro.horizon import HorizonSolverConfig, expand_problems, solve_horizon_info
from repro.horizon.solver import _horizon_merit_fns
from repro.testing import make_toy_problem

BUDGET = 300           # fixed-step budget per draw (and the adaptive cap)
DELTA = 6.0


def _window(seed: int, H: int):
    """An H-tick lookahead of same-shape toy problems with drifting demand
    (what a real forecaster window looks like: one catalog, demand moving)."""
    return [make_toy_problem(seed=seed + 3 * h,
                             demand_scale=1.0 + 0.08 * h) for h in range(H)]


def _merit(hp, x_cur, X) -> float:
    value, _, _ = _horizon_merit_fns(hp, x_cur, jnp.asarray(DELTA, jnp.float32),
                                     HorizonSolverConfig().penalty_w,
                                     HorizonSolverConfig().delta_penalty_w)
    return float(value(X))


def _solve(hp, x_cur, **cfg_kw):
    return solve_horizon_info(hp, x_cur, DELTA,
                              cfg=HorizonSolverConfig(**cfg_kw))


@pytest.mark.slow
@settings(max_examples=6)
@given(seed=st.integers(0, 10_000), H=st.sampled_from((4, 8, 16)))
def test_adaptive_no_worse_than_fixed_at_same_budget(seed, H):
    """Same budget, same merit function, same warm start: the adaptive
    engine must end at a merit <= the fixed-step engine's."""
    probs = _window(seed, H)
    hp = expand_problems(probs)
    x_cur = jnp.full(probs[0].n, 1.0, jnp.float32)
    ra = _solve(hp, x_cur, solver="adaptive", steps=BUDGET)
    rf = _solve(hp, x_cur, solver="fixed", steps=BUDGET)
    ma, mf = _merit(hp, x_cur, ra.plan), _merit(hp, x_cur, rf.plan)
    assert ma <= mf * 1.001 + 1e-4, (ma, mf)
    assert int(ra.iters) <= BUDGET


@pytest.mark.slow
def test_adaptive_half_budget_beats_fixed_final_on_median_draw():
    """ISSUE acceptance: the adaptive engine reaches the fixed-step
    engine's FINAL merit in <= half the iterations on at least the median
    draw, for every H in the sweep. (The Armijo ladder makes each accepted
    adaptive iterate monotone in merit, so comparing the half-budget
    iterate against the fixed final merit IS the iterations-to-merit
    question.)"""
    for H in (4, 8, 16):
        wins = []
        records = []
        for seed in (0, 11, 23, 37, 41):
            probs = _window(seed, H)
            hp = expand_problems(probs)
            x_cur = jnp.full(probs[0].n, 1.0, jnp.float32)
            rf = _solve(hp, x_cur, solver="fixed", steps=BUDGET)
            ra = _solve(hp, x_cur, solver="adaptive", steps=BUDGET // 2)
            ma, mf = _merit(hp, x_cur, ra.plan), _merit(hp, x_cur, rf.plan)
            wins.append(ma <= mf * 1.001 + 1e-4)
            records.append((seed, int(ra.iters), round(ma, 3), round(mf, 3)))
        # median draw or better: at least half the draws must win
        assert sum(wins) * 2 >= len(wins), (H, records)


def test_warm_started_resolve_early_stops():
    """Iterations-to-tolerance, recorded: repeatedly re-solving from the
    previous solution (the MPC steady state — each restart is a tick whose
    plan barely moves) must reach a fixpoint where the engine early-stops
    far under the budget, instead of burning the full budget every tick the
    way the fixed engine does. (The first restarts may still find real
    progress — a fresh BB step escapes plateaus — so the bound is on the
    settled state, monotonicity on every restart.)"""
    for H in (4, 8):
        probs = _window(5, H)
        hp = expand_problems(probs)
        x_cur = jnp.full(probs[0].n, 1.0, jnp.float32)
        res = _solve(hp, x_cur, solver="adaptive", steps=600)
        merit_prev = _merit(hp, x_cur, res.plan)
        for _ in range(3):
            res = solve_horizon_info(hp, x_cur, DELTA, x_init=res.plan,
                                     cfg=HorizonSolverConfig(steps=600))
            merit = _merit(hp, x_cur, res.plan)
            assert merit <= merit_prev * 1.001 + 1e-4   # never spoils
            merit_prev = merit
        assert int(res.iters) <= 150, (H, int(res.iters))


def test_iters_reporting_contract():
    """The reported iteration count is the engine's actual effort: the
    fixed engine always bills its full budget, the adaptive engine never
    exceeds it, and a zero-budget adaptive solve reports zero."""
    probs = _window(2, 4)
    hp = expand_problems(probs)
    x_cur = jnp.full(probs[0].n, 1.0, jnp.float32)
    rf = _solve(hp, x_cur, solver="fixed", steps=40)
    assert int(rf.iters) == 40
    ra = _solve(hp, x_cur, solver="adaptive", steps=40)
    assert 0 < int(ra.iters) <= 40
    r0 = _solve(hp, x_cur, solver="adaptive", steps=0)
    assert int(r0.iters) == 0
