"""Anytime-mode contract for the shared PGD engine (ISSUE tentpole).

Three guarantees, all test-enforced here:

1. **Off means off, bit-exactly** — ``anytime=None`` (or a config without
   a deadline) branches at Python level into the exact pre-anytime
   compiled program, and a chunked run whose budget never expires matches
   the monolithic solve bit-for-bit.
2. **Best-so-far is the merit-argmin prefix** — a truncated solve's
   returned iterate achieves exactly the minimum merit over the
   untruncated trajectory's first ``iters`` rows (plus the warm start):
   the driver returns the best thing it SAW, never a worse later iterate.
3. **Graceful floor** — an immediately-expired budget still returns the
   projected (feasible) warm start after one chunk, flagged
   ``deadline_hit``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AnytimeConfig, objective_value, solve_incremental_info
from repro.core.pgd import run_anytime, PGDConfig
from repro.testing import make_toy_problem


def _warm_setup(seed=0):
    """A toy warm tick: problem, current allocation, a deliberately poor
    warm start (so the solve has real work to do)."""
    prob = make_toy_problem(seed=seed)
    n = prob.c.shape[0]
    x_cur = jnp.asarray(np.full(n, 2.0), jnp.float32)
    delta = jnp.asarray(50.0, jnp.float32)
    return prob, x_cur, delta


def _fake_clock(step_ms: float):
    state = {"t": 0.0}

    def clock():
        state["t"] += step_ms / 1e3
        return state["t"]

    return clock


def test_disabled_config_is_bit_identical_to_no_config():
    prob, x_cur, delta = _warm_setup()
    x_off, it_off = solve_incremental_info(prob, x_cur, delta)
    x_none, it_none = solve_incremental_info(
        prob, x_cur, delta, anytime=AnytimeConfig(deadline_ms=None))
    np.testing.assert_array_equal(np.asarray(x_off), np.asarray(x_none))
    assert int(it_off) == int(it_none)


def test_generous_deadline_matches_monolithic_solve_bit_exactly():
    """A chunked run that never expires walks the exact iteration sequence
    of the monolithic program (shared ``_pgd_iteration`` body), so its
    answer — and iteration count — are bit-identical."""
    prob, x_cur, delta = _warm_setup()
    x_off, it_off = solve_incremental_info(prob, x_cur, delta)
    x_any, it_any, report = solve_incremental_info(
        prob, x_cur, delta,
        anytime=AnytimeConfig(deadline_ms=1e9, chunk_iters=37))
    assert not report.deadline_hit
    assert int(it_any) == int(it_off)
    np.testing.assert_array_equal(np.asarray(x_off), np.asarray(x_any))


def test_truncated_best_so_far_is_merit_argmin_prefix():
    """Contract 2: truncate at several budgets with a deterministic clock
    and check the returned iterate's merit equals the min over the traced
    untruncated trajectory's first ``iters`` merits (including the warm
    start's own merit — a solve that never improved must return it)."""
    prob, x_cur, delta = _warm_setup()
    # untruncated traced run: merit[i] is the merit AFTER iteration i+1
    _, _, trace = solve_incremental_info(prob, x_cur, delta,
                                         capture_trace=True)
    merit = np.asarray(trace.merit, np.float64)
    # the warm start's merit: objective at the projected x_cur == the
    # chunk driver's f_best initialization (x_cur is already box-feasible
    # and inside its own churn ball, so projection is identity here)
    f0 = float(objective_value(prob, x_cur))
    for budget_ms, chunk in [(2.0, 4), (6.0, 8), (20.0, 16)]:
        x_best, iters, report = solve_incremental_info(
            prob, x_cur, delta,
            anytime=AnytimeConfig(deadline_ms=budget_ms, chunk_iters=chunk,
                                  clock=_fake_clock(1.0)))
        k = int(iters)
        assert report.deadline_hit
        assert 0 < k < 600       # actually truncated
        expect = min([f0] + list(merit[:k]))
        got = float(objective_value(prob, jnp.asarray(x_best)))
        np.testing.assert_allclose(got, expect, rtol=1e-6)


def test_zero_budget_returns_feasible_projected_warm_start():
    """Contract 3: a budget that expires on the first clock reading still
    runs one chunk and returns a best-so-far no worse than the projected
    warm start, flagged as a deadline hit."""
    from repro.core import is_feasible, round_and_polish

    prob, x_cur, delta = _warm_setup()
    x_best, iters, report = solve_incremental_info(
        prob, x_cur, delta,
        anytime=AnytimeConfig(deadline_ms=0.5, chunk_iters=4,
                              clock=_fake_clock(10.0)))
    assert report.deadline_hit
    assert int(iters) <= 4
    f0 = float(objective_value(prob, x_cur))
    assert float(objective_value(prob, jnp.asarray(x_best))) <= f0 + 1e-6
    x_int = round_and_polish(prob, jnp.asarray(x_best))
    assert bool(is_feasible(prob, x_int, 1e-3))


def test_tighter_budgets_never_return_better_merit():
    """Monotone degradation: with one deterministic clock, a larger budget
    sees a superset of the trajectory, so its best-so-far merit is <= any
    tighter budget's (the serve bench's graceful-degradation check)."""
    prob, x_cur, delta = _warm_setup()
    merits = []
    for budget_ms in (1.0, 4.0, 16.0, 64.0):
        x_best, _, _ = solve_incremental_info(
            prob, x_cur, delta,
            anytime=AnytimeConfig(deadline_ms=budget_ms, chunk_iters=8,
                                  clock=_fake_clock(0.5)))
        merits.append(float(objective_value(prob, jnp.asarray(x_best))))
    assert all(b <= a + 1e-6 for a, b in zip(merits, merits[1:]))


def test_anytime_and_capture_trace_are_mutually_exclusive():
    prob, x_cur, delta = _warm_setup()
    with pytest.raises(ValueError, match="mutually exclusive"):
        solve_incremental_info(
            prob, x_cur, delta, capture_trace=True,
            anytime=AnytimeConfig(deadline_ms=5.0))


def test_run_anytime_requires_a_deadline():
    with pytest.raises(ValueError):
        run_anytime(lambda: None, lambda s, e: s, PGDConfig(),
                    AnytimeConfig(deadline_ms=None))


@pytest.mark.parametrize("budget_ms", [0.5, 3.0, 20.0])
def test_report_elapsed_within_budget_plus_one_step(budget_ms):
    """Every chunk is fenced before the clock is read, so a drive overruns
    its budget by at most its longest step (the init or one chunk)."""
    prob, x_cur, delta = _warm_setup()
    _, _, report = solve_incremental_info(
        prob, x_cur, delta,
        anytime=AnytimeConfig(deadline_ms=budget_ms, chunk_iters=8,
                              clock=_fake_clock(1.0)))
    assert report.budget_ms == budget_ms
    assert report.max_step_ms > 0.0
    assert report.elapsed_ms <= report.budget_ms + report.max_step_ms
