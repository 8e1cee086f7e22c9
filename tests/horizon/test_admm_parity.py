"""ADMM horizon engine: parity and certificate battery.

The consensus-ADMM engine (``repro.horizon.admm``) is a second, structurally
different solver for the SAME time-expanded program the adaptive engine
minimizes monolithically. That redundancy is the test asset: every property
here pins ADMM against an independent implementation path, so a bug in
either engine breaks an equivalence instead of shifting a benchmark number.

The battery, in order of strictness:

* equal-budget objective parity — at matched per-tick compute
  (``admm_iters * inner_steps == steps``) the two engines land within a
  bounded relative merit gap of each other on random windows across
  H ∈ {4, 8, 16}.  (Measured: ADMM is typically a few percent BETTER;
  the bound only needs to catch divergence/sign bugs, which blow past it
  by an order of magnitude.)
* committed-tick agreement — after ``round_committed`` the plans agree to
  integer rounding granularity (measured: exactly; asserted: L-inf <= 1).
* residual certificates — the ``ADMMTrace`` primal/dual residual
  trajectories actually decrease to tolerance and agree with the final
  ``ADMMDiag`` certificate.
* batched ≡ sequential — the vmapped fleet step reproduces sequential
  per-lane solves BIT-exactly on a ragged mixed-catalog fleet (the
  branch-free z-update exists precisely to keep this contract; a
  line-searched z-update breaks it in the last ulps).
* H=1 reduction — a one-tick window has no coupling to split on, so the
  admm config must reproduce ``solve_incremental`` bit-for-bit.
* replay reachability — ``solver="admm"`` must be drivable end-to-end from
  ``replay_fleet`` in BOTH replay engines (pins the config-plumbed-but-
  unreachable bug class) and must surface ``ADMMTrace`` captures there.
"""
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Catalog, make_cloud_catalog, solve_incremental
from repro.fleet import TenantSpec, replay_fleet
from repro.fleet.traces import diurnal_trace, ramp_trace
from repro.horizon import (ADMMDiag, ADMMTrace, HorizonProblem,
                           HorizonSolverConfig, admm_residual_history,
                           expand_problems, round_committed,
                           solve_horizon_fleet_step, solve_horizon_info)
from repro.horizon.problem import tick_problem
from repro.horizon.solver import _horizon_merit_fns
from repro.obs import admm_trace_summary
from repro.testing import make_toy_problem

# Equal per-tick compute: the adaptive engine gets `steps` iterations on the
# monolithic (H, n) program; ADMM spends admm_iters outer sweeps of
# inner_steps prox iterations on every tick block (vmapped), so the per-tick
# budgets match at admm_iters * inner_steps == steps.
ADAPTIVE = HorizonSolverConfig(solver="adaptive", steps=600)
ADMM = HorizonSolverConfig(solver="admm", admm_iters=30, inner_steps=20)
assert ADMM.admm_iters * ADMM.inner_steps == ADAPTIVE.steps

SEEDS = (0, 1, 2)
DELTA = 8.0


def _window(seed: int, H: int):
    """A demand-varied lookahead window of random per-tick catalogs."""
    return expand_problems([make_toy_problem(seed=seed + 3 * h,
                                             demand_scale=1.0 + 0.05 * h)
                            for h in range(H)])


def _solve_pair(seed: int, H: int, trace: bool = False):
    hp = _window(seed, H)
    xc = jnp.full(hp.problem.c.shape[1], 1.0, jnp.float32)
    ra = solve_horizon_info(hp, xc, DELTA, cfg=ADAPTIVE)
    rm = solve_horizon_info(hp, xc, DELTA, cfg=ADMM, capture_trace=trace)
    return hp, xc, ra, rm


@st.composite
def _window_cases(draw):
    """Composite strategy: a random-catalog window spec (seed, H) — seeds
    span the measured toy-catalog pool, H the satellite's {4, 8, 16}."""
    return draw(st.integers(0, 2)), draw(st.sampled_from((4, 8, 16)))


@settings(max_examples=4)
@given(case=_window_cases())
def test_equal_budget_objective_parity(case):
    """At matched compute, ADMM's window merit lands within a bounded
    relative gap of the adaptive engine's (measured ~[-0.08, -0.02]: the
    splitting is typically BETTER; the bound catches divergence, which
    overshoots it tenfold)."""
    seed, H = case
    hp, xc, ra, rm = _solve_pair(seed, H)
    merit, _, _ = _horizon_merit_fns(hp, xc,
                                     jnp.asarray(DELTA, jnp.float32),
                                     ADAPTIVE.penalty_w,
                                     ADAPTIVE.delta_penalty_w)
    Ja, Jm = float(merit(ra.plan)), float(merit(rm.plan))
    rel = (Jm - Ja) / (1.0 + abs(Ja))
    assert abs(rel) <= 0.15, (H, seed, Ja, Jm, rel)


@pytest.mark.parametrize("H", [4, 8, 16])
def test_committed_ints_match_to_rounding_granularity(H):
    """The committed (rounded) tick agrees across engines within rounding
    granularity — measured exactly equal; one unit of slack tolerated for
    knife-edge rounding ties."""
    for seed in SEEDS:
        hp, _, ra, rm = _solve_pair(seed, H)
        p0 = tick_problem(hp, 0)
        ia = round_committed(p0, ra.plan[0], True)
        im = round_committed(p0, rm.plan[0], True)
        assert int(jnp.max(jnp.abs(ia - im))) <= 1, (H, seed, ia, im)


@pytest.mark.parametrize("H", [4, 8, 16])
def test_residuals_decrease_and_match_diag(H):
    """The ADMMTrace residual trajectories must actually certify
    convergence: both residuals end well below where they start (measured
    >= 20x drop; asserted 4x), and the trace's final row IS the ADMMDiag
    certificate the untraced path gauges."""
    for seed in SEEDS:
        _, _, _, rm = _solve_pair(seed, H, trace=True)
        assert isinstance(rm.trace, ADMMTrace)
        assert isinstance(rm.diag, ADMMDiag)
        primal, dual = admm_residual_history(rm.trace)
        assert primal.shape[0] == int(rm.diag.admm_iters)
        assert primal[-1] <= 0.25 * primal[0], (H, seed, primal)
        assert dual[-1] <= 0.25 * dual[0], (H, seed, dual)
        assert np.isclose(primal[-1], float(rm.diag.primal_res), atol=1e-6)
        assert np.isclose(dual[-1], float(rm.diag.dual_res), atol=1e-6)
        s = admm_trace_summary(rm.trace)
        assert s["admm_iters"] == int(rm.diag.admm_iters)
        assert s["inner_total"] > 0


@pytest.mark.parametrize("delta_max", [1e3, 6.0])
def test_batched_matches_sequential_on_ragged_fleet(delta_max):
    """The vmapped fleet step reproduces sequential per-lane ADMM solves
    BIT-exactly (plans AND rounded commits) on a mixed-catalog fleet with a
    frozen (ragged-trace) lane — with both a slack and a binding churn
    bound. This is the contract the branch-free z-update buys: any
    data-dependent accept/reject in the consensus update would bifurcate on
    batched-vs-sequential ulp noise and break exact equality."""
    lane_seeds = [[5, 9, 2, 7], [13, 4, 19, 8], [1, 3, 18, 27]]
    lanes = [expand_problems([make_toy_problem(seed=s) for s in ss])
             for ss in lane_seeds]
    n = lanes[0].problem.c.shape[1]
    xc = jnp.stack([jnp.full(n, float(i), jnp.float32) for i in range(3)])
    active = np.array([True, False, True])
    batched = HorizonProblem(
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                               *(l.problem for l in lanes)),
        lanes[0].coupling_w, lanes[0].coupling_eps)
    fr = solve_horizon_fleet_step(batched, xc, delta_max, active=active,
                                  cfg=ADMM)
    assert isinstance(fr.diag, ADMMDiag)
    for i, l in enumerate(lanes):
        if not active[i]:
            np.testing.assert_array_equal(np.asarray(fr.x_int[i]),
                                          np.asarray(xc[i]))
            assert int(fr.iters[i]) == 0
            continue
        sq = solve_horizon_info(l, xc[i], delta_max, cfg=ADMM)
        np.testing.assert_array_equal(np.asarray(fr.plan[i]),
                                      np.asarray(sq.plan))
        xi = round_committed(tick_problem(l, 0), sq.plan[0], True)
        np.testing.assert_array_equal(np.asarray(fr.x_int[i]),
                                      np.asarray(xi))


def test_h1_reduces_to_solve_incremental():
    """A one-tick window has nothing to split: solver='admm' at H=1 must be
    solve_incremental bit-for-bit (same merit triple, same engine), with no
    residual certificate to report."""
    for seed in (5, 13):
        prob = make_toy_problem(seed=seed)
        hp = expand_problems([prob])
        xc = jnp.full(prob.n, 1.0, jnp.float32)
        r = solve_horizon_info(hp, xc, 6.0, cfg=ADMM)
        x_myo = solve_incremental(prob, xc, 6.0)
        np.testing.assert_array_equal(np.asarray(r.plan[0]),
                                      np.asarray(x_myo))
        assert r.diag is None


@pytest.mark.slow
def test_admm_reachable_from_replay_fleet_both_engines():
    """Pins the config-plumbed-but-unreachable bug class: an MPC replay
    configured with solver='admm' must actually run the ADMM engine in BOTH
    replay engines — proven by the ADMMTrace captures coming back — and the
    two engines must still agree on every committed integer allocation."""
    cat = Catalog(make_cloud_catalog().instances[::40])
    base = np.array([8.0, 16.0, 4.0, 100.0])
    specs = [
        TenantSpec(name="a", trace=diurnal_trace(base, 4, amplitude=0.3,
                                                 noise=0.0), n_starts=2),
        TenantSpec(name="b", trace=ramp_trace(base * 0.5, 3, end_scale=1.5,
                                              noise=0.0), n_starts=2,
                   delta_max=4.0),
    ]
    kw = dict(run_ca_baseline=False, controller="mpc", horizon=3,
              forecaster="last_value", solver_config=ADMM,
              capture_solver_trace=True)
    seq = replay_fleet(cat, specs, replay_mode="sequential", **kw)
    bat = replay_fleet(cat, specs, replay_mode="batched", **kw)
    for out in (seq, bat):
        assert out.solver_traces is not None
        warm = [tr for traces in out.solver_traces for tr in traces]
        assert warm, "no warm-tick solver traces captured"
        assert all(isinstance(tr, ADMMTrace) for tr in warm), (
            "replay ran a different engine than solver_config asked for")
        # every captured trace certifies a converging solve
        for tr in warm:
            primal, dual = admm_residual_history(tr)
            assert primal.shape[0] >= 1
            assert primal[-1] <= primal[0] + 1e-6
    for rs, rb in zip(seq.tenants, bat.tenants):
        for ss, sb in zip(rs.steps, rb.steps):
            np.testing.assert_array_equal(ss.counts, sb.counts)
