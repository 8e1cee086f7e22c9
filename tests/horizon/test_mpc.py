"""Receding-horizon controller + fleet integration.

The anchor (ISSUE acceptance): MPC with H=1 and the last_value forecaster
must reproduce the myopic controller's per-tick INTEGER allocations exactly
— every lookahead behavior is then an explicit deviation from that anchored
baseline, not an artifact of a different solver.

Property-style tests run through the deterministic ``repro.testing`` shim
when the image lacks hypothesis."""
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import numpy as np
import pytest

from repro.core import Catalog, make_cloud_catalog
from repro.core.controller import InfrastructureOptimizationController
from repro.fleet import TenantSpec, replay_fleet
from repro.fleet.traces import (constant_trace, diurnal_trace,
                                flash_crowd_trace, ramp_trace)
from repro.horizon import ModelPredictiveController, make_forecaster

BASE = np.array([8.0, 16.0, 4.0, 100.0])


@pytest.fixture(scope="module")
def tiny_catalog():
    return Catalog(make_cloud_catalog().instances[::40])


def test_h1_last_value_mpc_reproduces_myopic(tiny_catalog):
    """Tentpole acceptance: H=1 + last_value ≡ the myopic controller,
    per-tick integer allocations compared EXACTLY through replay_fleet."""
    specs = [
        TenantSpec(name="a", trace=diurnal_trace(BASE, 4, amplitude=0.3,
                                                 noise=0.0), n_starts=2),
        TenantSpec(name="b", trace=ramp_trace(BASE * 0.5, 3, end_scale=1.5,
                                              noise=0.0), n_starts=2,
                   delta_max=4.0),
    ]
    myo = replay_fleet(tiny_catalog, specs, run_ca_baseline=False)
    mpc = replay_fleet(tiny_catalog, specs, run_ca_baseline=False,
                       controller="mpc", horizon=1, forecaster="last_value")
    assert mpc.metrics.controller == "mpc"
    for rm, rp in zip(myo.tenants, mpc.tenants):
        for sm, sp in zip(rm.steps, rp.steps):
            np.testing.assert_array_equal(sm.counts, sp.counts)
            assert sm.churn == sp.churn
            assert sm.replanned == sp.replanned
        assert rm.metrics.cost_integral == rp.metrics.cost_integral


@pytest.mark.slow
@settings(max_examples=3)
@given(cat_pick=st.integers(0, 2), trace_seed=st.integers(0, 50))
def test_h1_equivalence_across_random_catalogs(cat_pick, trace_seed):
    """Satellite property test: the H=1 ≡ myopic equivalence is structural,
    not tuned to one catalog — it holds across random catalog slices and
    random traces (strides drawn from a fixed set so compile shapes repeat
    across examples)."""
    stride = (38, 40, 44)[cat_pick]
    cat = Catalog(make_cloud_catalog().instances[::stride])
    trace = diurnal_trace(BASE * (0.6 + 0.1 * (trace_seed % 4)), 3,
                          amplitude=0.35, seed=trace_seed)
    myo = InfrastructureOptimizationController(catalog=cat, n_starts=2)
    mpc = ModelPredictiveController(catalog=cat, n_starts=2, horizon=1,
                                    forecaster=make_forecaster("last_value"))
    for d in trace:
        np.testing.assert_array_equal(myo.step(d).counts, mpc.step(d).counts)


@pytest.mark.slow
def test_batched_mpc_matches_sequential(tiny_catalog):
    """Tentpole acceptance: the batched MPC engine (one vmapped
    solve_horizon_fleet_step per shape bucket per tick) must yield per-tenant
    integer allocations identical to the sequential MPC loop on CPU —
    ragged horizons and a per-tenant catalog included."""
    cat_other = Catalog(make_cloud_catalog().instances[::50])
    specs = [
        TenantSpec(name="a", trace=diurnal_trace(BASE, 4, amplitude=0.3,
                                                 noise=0.0), n_starts=2),
        TenantSpec(name="b", trace=ramp_trace(BASE * 0.5, 2, end_scale=1.5,
                                              noise=0.0), n_starts=2,
                   catalog=cat_other, delta_max=4.0),
        TenantSpec(name="c", trace=constant_trace(BASE, 3), n_starts=2),
    ]
    kw = dict(run_ca_baseline=False, controller="mpc", horizon=3,
              forecaster="holt_winters", forecaster_kwargs=dict(period=24))
    seq = replay_fleet(tiny_catalog, specs, replay_mode="sequential", **kw)
    bat = replay_fleet(tiny_catalog, specs, replay_mode="batched", **kw)
    for rs, rb in zip(seq.tenants, bat.tenants):
        assert len(rs.steps) == len(rb.steps) == rs.spec.trace.shape[0]
        for ss, sb in zip(rs.steps, rb.steps):
            np.testing.assert_array_equal(ss.counts, sb.counts)
            assert ss.churn == sb.churn
            assert ss.replanned == sb.replanned
        assert rs.metrics == rb.metrics
    assert (seq.metrics.total_cost_integral == bat.metrics.total_cost_integral)


def test_mpc_lookahead_serves_demand(tiny_catalog):
    """An H>1 oracle-driven MPC replay on a flash crowd must keep serving
    demand every tick (the hard tick-0 problem is unchanged; lookahead only
    reshapes WHERE the plan is headed)."""
    spec = TenantSpec(name="fc", trace=flash_crowd_trace(BASE, 5,
                                                         burst_scale=2.5,
                                                         noise=0.0, seed=3),
                      n_starts=2, delta_max=16.0)
    out = replay_fleet(tiny_catalog, [spec], run_ca_baseline=False,
                       controller="mpc", horizon=4, forecaster="oracle")
    assert all(s.metrics.satisfied for s in out.tenants[0].steps)
    assert out.tenants[0].metrics.slo_violation_ticks == 0


def test_oracle_regret_plumbing(tiny_catalog):
    """run_oracle_baseline attaches the oracle twin: oracle-vs-oracle regret
    is exactly zero, the summary renders it, and the flag is rejected for
    the myopic controller (regret is an MPC notion)."""
    spec = TenantSpec(name="t", trace=diurnal_trace(BASE, 3, amplitude=0.2,
                                                    noise=0.0), n_starts=2)
    out = replay_fleet(tiny_catalog, [spec], run_ca_baseline=False,
                       controller="mpc", horizon=2, forecaster="oracle",
                       run_oracle_baseline=True)
    assert out.metrics.oracle is not None
    assert out.metrics.regret_vs_oracle == 0.0
    assert "regret vs oracle" in out.metrics.summary()
    with pytest.raises(ValueError):
        replay_fleet(tiny_catalog, [spec], controller="myopic",
                     run_oracle_baseline=True)


def test_mpc_plan_state(tiny_catalog):
    """The controller keeps its (H, n) relaxed plan as rolling state."""
    ctl = ModelPredictiveController(catalog=tiny_catalog, n_starts=2,
                                    horizon=3,
                                    forecaster=make_forecaster("ewma"))
    trace = diurnal_trace(BASE, 3, amplitude=0.2, noise=0.0)
    for d in trace:
        ctl.step(d)
    assert ctl.plan.shape == (3, tiny_catalog.n)
    assert len(ctl.history) == 3
    # the committed tick is always within the hard churn bound + rounding
    shifted = ctl.shifted_plan()
    np.testing.assert_array_equal(shifted[0], ctl.x_current)


@pytest.mark.slow
def test_solver_config_plumbs_through_replay(tiny_catalog):
    """Satellite acceptance: ``replay_fleet(controller="mpc",
    solver_config=...)`` must reach every warm tick's solve in BOTH engines
    — the recorded per-tick ``solver_iters`` respects the configured budget
    (adaptive) and equals it exactly (fixed), which a module-constant
    600-step solver could not produce. The PR 3 ``solver_steps``-unreachable
    bug class, pinned for the horizon path."""
    from repro.horizon import HorizonSolverConfig

    spec = TenantSpec(name="t", trace=diurnal_trace(BASE, 4, amplitude=0.3,
                                                    noise=0.0), n_starts=2)
    for mode in ("sequential", "batched"):
        cfg = HorizonSolverConfig(steps=7)
        out = replay_fleet(tiny_catalog, [spec], run_ca_baseline=False,
                           replay_mode=mode, controller="mpc", horizon=3,
                           solver_config=cfg)
        warm = out.tenants[0].steps[1:]
        assert all(0 < s.solver_iters <= 7 for s in warm), \
            [(mode, s.solver_iters) for s in warm]
        assert out.tenants[0].steps[0].solver_iters == 0     # cold tick
        fixed = replay_fleet(tiny_catalog, [spec], run_ca_baseline=False,
                             replay_mode=mode, controller="mpc", horizon=3,
                             solver_config=HorizonSolverConfig(
                                 solver="fixed", steps=11))
        assert all(s.solver_iters == 11 for s in fixed.tenants[0].steps[1:])


@pytest.mark.slow
def test_solver_iters_match_across_engines(tiny_catalog):
    """Iteration-count contract across engines: the FIRST warm tick's
    inputs (integer cold counts, tiled warm start) are bit-identical in
    both engines, so its adaptive trajectory — and hence its recorded
    ``solver_iters`` — must match exactly. Later ticks warm-start from the
    previous RELAXED plan, which the two engines carry with last-ulp
    differences (vmap batches the matmuls differently), so their
    early-stopping points may drift a little while the committed integer
    allocations stay identical (asserted elsewhere) — bound the drift, and
    require every tick to respect the budget."""
    specs = [
        TenantSpec(name="a", trace=diurnal_trace(BASE, 4, amplitude=0.3,
                                                 noise=0.0), n_starts=2),
        TenantSpec(name="b", trace=ramp_trace(BASE * 0.5, 3, end_scale=1.5,
                                              noise=0.0), n_starts=2,
                   delta_max=4.0),
    ]
    kw = dict(run_ca_baseline=False, controller="mpc", horizon=3,
              forecaster="last_value")
    seq = replay_fleet(tiny_catalog, specs, replay_mode="sequential", **kw)
    bat = replay_fleet(tiny_catalog, specs, replay_mode="batched", **kw)
    for rs, rb in zip(seq.tenants, bat.tenants):
        it_s = [s.solver_iters for s in rs.steps]
        it_b = [s.solver_iters for s in rb.steps]
        assert it_s[0] == it_b[0] == 0                # cold tick records 0
        assert it_s[1] == it_b[1] > 0                 # identical inputs
        for a, b in zip(it_s[1:], it_b[1:]):          # bounded ulp drift
            assert 0 < a <= 600 and 0 < b <= 600
            assert abs(a - b) <= max(10, 0.5 * max(a, b)), (it_s, it_b)


@pytest.mark.slow
def test_window_cold_start_batched_matches_sequential(tiny_catalog):
    """cold_start="window" must preserve the engine equivalence: the
    batched replay re-ranks the SAME multistart candidates by the same
    whole-window scores, so per-tenant integer allocations stay identical
    to the sequential loop."""
    specs = [
        TenantSpec(name="a", trace=flash_crowd_trace(BASE, 4, burst_scale=2.0,
                                                     noise=0.0, seed=1),
                   n_starts=3),
        TenantSpec(name="b", trace=ramp_trace(BASE * 0.6, 3, end_scale=1.8,
                                              noise=0.0), n_starts=3),
    ]
    kw = dict(run_ca_baseline=False, controller="mpc", horizon=3,
              forecaster="oracle", cold_start="window")
    seq = replay_fleet(tiny_catalog, specs, replay_mode="sequential", **kw)
    bat = replay_fleet(tiny_catalog, specs, replay_mode="batched", **kw)
    for rs, rb in zip(seq.tenants, bat.tenants):
        for ss, sb in zip(rs.steps, rb.steps):
            np.testing.assert_array_equal(ss.counts, sb.counts)
        assert rs.metrics == rb.metrics


def test_window_cold_start_h1_is_myopic(tiny_catalog):
    """At H=1 the whole-window score IS the tick-0 merit, so
    cold_start="window" must not perturb the H=1 ≡ myopic anchor."""
    spec = TenantSpec(name="t", trace=diurnal_trace(BASE, 3, amplitude=0.3,
                                                    noise=0.0), n_starts=2)
    myo = replay_fleet(tiny_catalog, [spec], run_ca_baseline=False)
    mpc = replay_fleet(tiny_catalog, [spec], run_ca_baseline=False,
                       controller="mpc", horizon=1, cold_start="window")
    for sm, sp in zip(myo.tenants[0].steps, mpc.tenants[0].steps):
        np.testing.assert_array_equal(sm.counts, sp.counts)


def test_window_cold_start_scores_whole_window(tiny_catalog):
    """The window selection must actually consult the future: scoring is
    Σ_h f_h(candidate), so a candidate that is cheapest for tick 0 only
    loses to one that serves the whole ramp (verified on the controller's
    own multistart candidates via the public scoring helpers)."""
    from repro.horizon import (ModelPredictiveController, make_forecaster,
                               select_window_candidate,
                               window_candidate_scores)
    from repro.core.multistart import multistart_solve
    import repro.core.objective as obj

    trace = ramp_trace(BASE * 0.6, 6, end_scale=2.5, noise=0.0)
    ctl = ModelPredictiveController(
        catalog=tiny_catalog, n_starts=4, horizon=4, cold_start="window",
        forecaster=make_forecaster("oracle", trace=trace))
    demands = ctl.window_demands(trace[0])
    probs = ctl.window_problems(demands)
    ms = multistart_solve(probs[0], n_starts=4)
    cands = np.asarray(ms.x_int_all, np.float64)
    scores = window_candidate_scores(probs, cands)
    j = select_window_candidate(scores, np.asarray(ms.feas_int_all))
    # the helper's scores really are the sum of per-tick objectives
    for s, cand in zip(scores, cands):
        manual = sum(float(obj.objective(pb, np.asarray(cand, np.float32)))
                     for pb in probs)
        np.testing.assert_allclose(s, manual, rtol=1e-5)
    # and the controller's cold tick commits exactly that winner
    step = ctl.step(trace[0])
    np.testing.assert_array_equal(step.counts, cands[j])
