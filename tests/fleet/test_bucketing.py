"""Shape-bucketed stacking: bucketing + scatter-back must be a
permutation-exact round trip, and bucketed solves must reproduce the
unbucketed (globally padded) solve_fleet results.

Property-style tests run through the deterministic ``repro.testing`` shim
when the image lacks hypothesis.
"""
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis not installed — deterministic shim
    from repro.testing import given, settings, strategies as st

import pytest
import numpy as np

from repro.core import SolverConfig
from repro.fleet import (bucket_dims, bucket_problems, ceil_pow2,
                         padding_stats, scatter_from_buckets, solve_fleet,
                         solve_fleet_bucketed, stack_problems, tenant_problem)
from repro.fleet.batching import unstack_solution
from repro.testing import make_toy_problem

CFG = SolverConfig(max_iters=100, barrier_rounds=2)


def _ragged(B, seed0=0):
    return [make_toy_problem(seed=seed0 + s, n=6 + 7 * (s % 4),
                             m=2 + s % 3, p=2 + s % 2) for s in range(B)]


# ---------------------------------------------------------------------------
# bucket geometry
# ---------------------------------------------------------------------------

@settings(max_examples=20)
@given(v=st.integers(1, 5000), floor=st.integers(1, 16))
def test_ceil_pow2_properties(v, floor):
    r = ceil_pow2(v, floor)
    assert r >= v and r >= floor
    # r is floor * 2^k and halving it (when possible) drops below v
    assert r == floor or r // 2 < max(v, floor)


@settings(max_examples=10)
@given(n=st.integers(1, 300), m=st.integers(1, 12), p=st.integers(1, 12))
def test_bucket_dims_dominate_true_dims(n, m, p):
    bn, bm, bp = bucket_dims(n, m, p)
    assert bn >= n and bm >= m and bp >= p
    # padding per axis is bounded: less than 2x above the floor
    assert bn < 2 * max(n, 8) and bm < 2 * max(m, 2) and bp < 2 * max(p, 2)


# ---------------------------------------------------------------------------
# permutation-exact round trip
# ---------------------------------------------------------------------------

@settings(max_examples=6)
@given(B=st.integers(2, 12), seed0=st.integers(0, 100))
def test_bucket_scatter_roundtrip_is_permutation_exact(B, seed0):
    probs = _ragged(B, seed0)
    bucketed = bucket_problems(probs)
    # tenant_idx is a permutation of range(B)
    flat = np.concatenate([np.asarray(i) for i in bucketed.tenant_idx])
    assert sorted(flat.tolist()) == list(range(B))
    # every bucket member slices back to its ORIGINAL problem bit-for-bit
    for batch, idx in zip(bucketed.batches, bucketed.tenant_idx):
        for i, b in enumerate(idx):
            orig = probs[int(b)]
            back = tenant_problem(batch, i)
            np.testing.assert_array_equal(np.asarray(back.K),
                                          np.asarray(orig.K))
            np.testing.assert_array_equal(np.asarray(back.c),
                                          np.asarray(orig.c))
            np.testing.assert_array_equal(np.asarray(back.d),
                                          np.asarray(orig.d))
    # scatter restores bucket-ordered payloads to original order exactly
    payload = [[f"tenant-{int(b)}" for b in idx]
               for idx in bucketed.tenant_idx]
    out = scatter_from_buckets(bucketed, payload)
    assert out == [f"tenant-{b}" for b in range(B)]
    # ... and per-tenant solution vectors survive embed -> unstack per bucket
    for batch, idx in zip(bucketed.batches, bucketed.tenant_idx):
        xs = [np.arange(probs[int(b)].n, dtype=np.float32) for b in idx]
        from repro.fleet import embed_solutions
        back = unstack_solution(batch, embed_solutions(batch, xs))
        for a, c in zip(xs, back):
            np.testing.assert_array_equal(a, c)


@settings(max_examples=6)
@given(B=st.integers(3, 16), seed0=st.integers(0, 50))
def test_padding_stats_accounting(B, seed0):
    probs = _ragged(B, seed0)
    g = padding_stats(probs)
    bk = padding_stats(probs, bucket_problems(probs))
    assert g["true_cells"] == bk["true_cells"] > 0
    assert 0.0 <= g["waste_frac"] < 1.0 and 0.0 <= bk["waste_frac"] < 1.0
    assert g["padded_cells"] >= g["true_cells"]
    assert bk["padded_cells"] >= bk["true_cells"]


def test_bucketing_cuts_padding_on_skewed_fleet():
    """The motivating case: one big tenant + many small ones. Global padding
    inflates every small tenant to the big tenant's shape; bucketing keeps
    the small tenants in their own small bucket."""
    probs = [make_toy_problem(seed=0, n=96, m=4)] + [
        make_toy_problem(seed=s, n=10, m=3) for s in range(1, 9)]
    g = padding_stats(probs)
    bk = padding_stats(probs, bucket_problems(probs))
    assert bk["padded_cells"] < 0.5 * g["padded_cells"]
    assert bk["waste_frac"] < g["waste_frac"]


# ---------------------------------------------------------------------------
# solve equivalence: bucketed == unbucketed
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bucketed_solve_matches_unbucketed():
    """Bucketed stacking must not change WHAT is solved: per-tenant integer
    solutions/objectives identical to the single globally-padded batch
    (start points are drawn per tenant at true shape, so both layouts see
    the same subproblems)."""
    probs = _ragged(7)
    flat = solve_fleet(stack_problems(probs), n_starts=2, cfg=CFG,
                       hot_loop="vmap")
    buck = solve_fleet_bucketed(probs, n_starts=2, cfg=CFG, hot_loop="vmap")
    np.testing.assert_array_equal(np.asarray(buck.fun_int),
                                  np.asarray(flat.fun_int))
    np.testing.assert_array_equal(np.asarray(buck.x_int),
                                  np.asarray(flat.x_int))
    # relaxed trajectories may part ways in the last ulps under different
    # padded reduction shapes; the BB/Armijo engine's accept/reject line
    # search amplifies those ulps more than the old fixed ladder did, so
    # the relaxed values get solver tolerance while the INTEGER results
    # above stay the exact-equality gate
    np.testing.assert_allclose(np.asarray(buck.fun), np.asarray(flat.fun),
                               rtol=5e-3)
    assert bool(np.all(np.asarray(buck.feasible)))


@pytest.mark.slow
@settings(max_examples=3)
@given(seed0=st.integers(0, 30))
def test_bucketed_solve_property_sweep(seed0):
    """Property sweep over random ragged fleets: bucketed integer objectives
    match unbucketed stacking, and every tenant ends feasible."""
    probs = _ragged(5, seed0)
    flat = solve_fleet(stack_problems(probs), n_starts=2, cfg=CFG,
                       hot_loop="vmap")
    buck = solve_fleet_bucketed(probs, n_starts=2, cfg=CFG, hot_loop="vmap")
    np.testing.assert_array_equal(np.asarray(buck.fun_int),
                                  np.asarray(flat.fun_int))
    assert bool(np.all(np.asarray(buck.feasible)))
