"""Incremental adoption (paper §III.E): ||x - x_current||_1 <= delta_max.

Implemented as an exact Euclidean projection onto the L1 ball centered at
``x_current``, composed with the box projection by a short alternating
(Dykstra-like) loop. Used by the controller to bound per-step cluster churn —
the paper's "bounded perturbation" methodology.

The projection is a soft threshold at Duchi et al.'s (2008) theta, found
without a sort by Michelot's (1986) active-set fixed point: theta is the mean
excess over the radius of the magnitudes still active, and every round drops
the magnitudes at or below it. Each round is one compare-and-reduce pass, and
the active set only shrinks, so the loop ends within n rounds on exactly
Duchi's top-rho set (Condat 2016, §2).

``solve_incremental`` (the warm tick of both the myopic controller and —
under vmap — the batched fleet engine ``solve_fleet_step``) runs the shared
Barzilai-Borwein + Armijo projected-gradient engine (``core.pgd``) on the
objective over this feasible set — the ``repro.core.terms`` registry sum,
so attached scenario terms (SLO pricing, priority eviction, spot risk)
price the warm tick automatically: ``steps`` is an iteration BUDGET,
not an exact count — the solve early-stops once an accepted step moves no
coordinate by more than the tolerance. The H=1 time-expanded program in
``repro.horizon.solver`` reduces op-for-op to this function (same engine,
same merit, same projection), which anchors the MPC ≡ myopic equivalence.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .pgd import (AnytimeConfig, PGDConfig, pgd_chunk_init, pgd_chunk_run,
                  pgd_minimize, pgd_minimize_traced, run_anytime)
from .problem import AllocationProblem
import repro.core.objective as obj


def _l1_threshold(a: jnp.ndarray, radius: jnp.ndarray):
    """Soft threshold of magnitudes ``a >= 0`` onto the L1 ball of ``radius``.

    Returns ``(theta, rounds)``. Michelot's fixed point: with the active set
    ``A = {a > theta}``, set ``theta = (sum_A a - radius) / |A|`` until
    ``|A|`` stops shrinking. It starts from the larger of two lower bounds on
    Duchi's theta: that mean over ``{a > 0}`` (Michelot's first round) and
    ``max(a) - radius``, which skips most rounds on a few large entries.
    Theta only grows (in exact arithmetic by itself, under rounding by the
    ``maximum``), so each round's set lies inside the last, ``|A|`` falls
    every round but the last, and the loop ends within ``len(a)`` rounds on
    Duchi's top-rho set. An empty set (a radius at or near 0) keeps the last
    theta, which zeroes every entry. ``a`` inside the ball takes 0 rounds,
    so under ``vmap`` it does not hold the loop open."""
    def mean_excess(active):
        k = jnp.sum(active, dtype=jnp.int32)
        s = jnp.sum(jnp.where(active, a, 0.0))
        return (s - radius) / jnp.maximum(k, 1).astype(a.dtype), k

    def body(state):
        theta, k, _, rounds = state
        theta_new, k_new = mean_excess(a > theta)
        return (jnp.maximum(theta, theta_new), k_new, k_new == k, rounds + 1)

    theta0 = jnp.maximum(mean_excess(a > 0)[0], jnp.max(a) - radius)
    init = (theta0, jnp.asarray(-1, jnp.int32), jnp.sum(a) <= radius,
            jnp.asarray(0, jnp.int32))
    theta, _, _, rounds = jax.lax.while_loop(lambda st: ~st[2], body, init)
    return theta, rounds


def project_l1_ball(v: jnp.ndarray, radius: jnp.ndarray) -> jnp.ndarray:
    """Euclidean projection of v onto {z : ||z||_1 <= radius}: soft
    thresholding at Duchi et al.'s theta, found by the sort-free fixed point
    of :func:`_l1_threshold`; ``v`` inside the ball comes back unchanged."""
    abs_v = jnp.abs(v)
    inside = jnp.sum(abs_v) <= radius
    theta, _ = _l1_threshold(abs_v, radius)
    w = jnp.sign(v) * jnp.maximum(abs_v - theta, 0.0)
    return jnp.where(inside, v, w)


def project_incremental(
    prob: AllocationProblem,
    x: jnp.ndarray,
    x_current: jnp.ndarray,
    delta_max: jnp.ndarray,
    n_alternations: int = 8,
) -> jnp.ndarray:
    """Project onto box ∩ {||x - x_current||_1 <= delta_max} by alternating
    exact projections. Both sets are convex; alternation converges to the
    intersection (we take the last box-feasible iterate)."""

    def body(i, z):
        z = x_current + project_l1_ball(z - x_current, delta_max)
        return obj.project(prob, z)

    return jax.lax.fori_loop(0, n_alternations, body, obj.project(prob, x))


def _incremental_merit_fns(prob, x_current, delta_max):
    """The warm tick's ``(value, grad, project)`` triple — the eq.(1)
    objective (terms-registry sum) over box ∩ L1 churn ball. One builder
    shared by the monolithic, traced, and chunked-anytime engines so all
    three run the exact same merit graph."""
    F = partial(obj.objective, prob)
    G = partial(obj.grad_objective, prob)

    def proj(x):
        return project_incremental(prob, x, x_current, delta_max)

    return F, G, proj


@partial(jax.jit, static_argnames=("cfg",))
def _solve_incremental_impl(prob, x_current, delta_max, x0, cfg: PGDConfig):
    F, G, proj = _incremental_merit_fns(prob, x_current, delta_max)
    return pgd_minimize(F, G, proj, x0, cfg)


def incremental_anytime_init(prob, x_current, delta_max, x0,
                             cfg: PGDConfig):
    """Unjitted chunk-state init for the warm tick's anytime mode (same
    merit triple as ``_solve_incremental_impl``). Exposed unjitted so the
    fleet engine can vmap it inside its own jitted impl."""
    F, G, proj = _incremental_merit_fns(prob, x_current, delta_max)
    return pgd_chunk_init(F, G, proj, x0, cfg)


def incremental_anytime_chunk(prob, x_current, delta_max, state, it_end,
                              cfg: PGDConfig):
    """Unjitted chunk advance for the warm tick's anytime mode — run the
    shared engine until the traced cap ``it_end`` or convergence."""
    F, G, proj = _incremental_merit_fns(prob, x_current, delta_max)
    return pgd_chunk_run(F, G, proj, state, it_end, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _anytime_init_impl(prob, x_current, delta_max, x0, cfg: PGDConfig):
    return incremental_anytime_init(prob, x_current, delta_max, x0, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _anytime_chunk_impl(prob, x_current, delta_max, state, it_end,
                        cfg: PGDConfig):
    return incremental_anytime_chunk(prob, x_current, delta_max, state,
                                     it_end, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _solve_incremental_traced_impl(prob, x_current, delta_max, x0,
                                   cfg: PGDConfig):
    """The traced twin of ``_solve_incremental_impl``: same merit triple,
    same engine, plus the fixed-size per-iteration PGDTrace capture."""
    F, G, proj = _incremental_merit_fns(prob, x_current, delta_max)
    return pgd_minimize_traced(F, G, proj, x0, cfg)


def solve_incremental(
    prob: AllocationProblem,
    x_current: jnp.ndarray,
    delta_max,
    x_init=None,
    steps: int = 600,
    cfg: PGDConfig | None = None,
) -> jnp.ndarray:
    """Adaptive PGD on f with the incremental-adoption feasible set, warm-
    started from the current allocation (the natural production warm start).

    Runs the shared BB/Armijo engine (``core.pgd.pgd_minimize``): ``steps``
    is the iteration budget (``PGDConfig.max_iters``); pass ``cfg`` to
    control the full ladder/tolerance instead. Returns the relaxed solution
    only — use :func:`solve_incremental_info` when the caller also wants the
    iteration count (benchmark instrumentation)."""
    return solve_incremental_info(prob, x_current, delta_max, x_init=x_init,
                                  steps=steps, cfg=cfg)[0]


def solve_incremental_info(
    prob: AllocationProblem,
    x_current: jnp.ndarray,
    delta_max,
    x_init=None,
    steps: int = 600,
    cfg: PGDConfig | None = None,
    capture_trace: bool = False,
    anytime: AnytimeConfig | None = None,
):
    """:func:`solve_incremental` variant returning ``(x, iters)`` — the
    relaxed solution plus the PGD iterations actually taken (the early-
    stopping win the adaptive engine buys over the old fixed-step loop).

    With ``capture_trace=True`` it returns ``(x, iters, trace)`` instead,
    where ``trace`` is the engine's per-iteration ``core.pgd.PGDTrace``
    (fixed-size ``(steps,)`` arrays — vmap-safe, so the batched fleet tick
    can surface one trace per lane; see ``repro.obs.solver_trace``). The
    solution and iteration count match the untraced call: the trace is
    extra loop state, not extra math.

    With an *enabled* ``anytime`` config (``deadline_ms`` set) the solve
    runs chunked against ``anytime.clock`` and returns ``(x_best, iters,
    AnytimeReport)`` — the best-so-far feasible iterate by merit when the
    budget expires (see ``core.pgd.AnytimeConfig``). ``anytime=None`` or a
    disabled config takes the untruncated path above, byte-for-byte the
    same compiled program as before the anytime mode existed. Anytime and
    ``capture_trace`` are mutually exclusive."""
    delta_max = jnp.asarray(delta_max, jnp.float32)
    x0 = x_current if x_init is None else x_init
    if cfg is None:
        cfg = PGDConfig(max_iters=int(steps))
    if anytime is not None and anytime.enabled:
        if capture_trace:
            raise ValueError("anytime deadlines and capture_trace are "
                             "mutually exclusive (truncated traces would "
                             "be misleading); drop one")
        xc = jnp.asarray(x_current)
        x0j = jnp.asarray(x0)
        state, report = run_anytime(
            lambda: _anytime_init_impl(prob, xc, delta_max, x0j, cfg),
            lambda s, e: _anytime_chunk_impl(prob, xc, delta_max, s, e, cfg),
            cfg, anytime)
        return state.x_best, state.it, report
    if capture_trace:
        x, _, iters, tr = _solve_incremental_traced_impl(
            prob, jnp.asarray(x_current), delta_max, jnp.asarray(x0), cfg)
        return x, iters, tr
    x, _, iters = _solve_incremental_impl(prob, jnp.asarray(x_current),
                                          delta_max, jnp.asarray(x0), cfg)
    return x, iters
