"""Projected-gradient / interior-point solver for the relaxed problem.

The paper solves the relaxation with interior-point methods (via CVXPY).
Here the solver is a first-class JAX citizen: fully ``jit``-able and
``vmap``-able (multi-start batches thousands of solves), built from
``lax.while_loop`` so it runs as a single compiled program on TPU.

Structure per solve:
  outer loop (barrier continuation, R rounds):  t <- kappa * t
    inner loop (projected gradient):            x <- P(x - eta * grad F_t(x))
      with Armijo backtracking over a fixed geometric step ladder (vmap-safe).

If the problem has no strictly feasible interior (common in the paper's own
scenarios where integral covers overshoot d+g), the barrier is replaced by a
smooth quadratic penalty — chosen automatically per-solve from the phase-1
point, exactly the fallback the paper's implementation notes describe
("basic rounding strategy when the solver produces ... infeasible solutions").
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

import repro.core.objective as obj
from .pgd import PGDConfig, pgd_minimize
from .problem import AllocationProblem


class SolverConfig(NamedTuple):
    """Hashable solver knobs (static under jit): barrier continuation
    schedule, PGD iteration budget, and the Armijo backtracking ladder."""

    max_iters: int = 400           # inner PGD iterations per barrier round
    barrier_rounds: int = 4        # outer continuation rounds
    barrier_t0: float = 1.0        # initial barrier temperature
    barrier_kappa: float = 10.0    # t multiplier per round
    penalty_w: float = 1e3         # quadratic penalty weight (fallback mode)
    step0: float = 1.0             # top of the step ladder
    n_backtracks: int = 12         # ladder length
    backtrack: float = 0.5         # ladder ratio
    armijo_c: float = 1e-4
    tol: float = 1e-6              # stop when projected-gradient step is tiny


class SolveResult(NamedTuple):
    """One relaxed solve: final iterate, objective, merit, effort, and
    whether the barrier (vs quadratic-penalty) path was taken."""

    x: jnp.ndarray
    fun: jnp.ndarray            # objective f(x) (WITHOUT barrier/penalty)
    composite: jnp.ndarray      # final merit value
    iters: jnp.ndarray
    feasible: jnp.ndarray
    used_barrier: jnp.ndarray


def phase1_point(prob: AllocationProblem, x0: jnp.ndarray, steps: int = 200,
                 margin_frac: float = 0.02) -> jnp.ndarray:
    """Drive constraint violation to ~0 by PGD on the violation alone.
    Targets a small margin INSIDE the [d-mu, d+g] band so the result is
    strictly interior (enabling barrier mode) whenever the band has width.
    Returns a feasible (or least-infeasible) point for warm starts."""
    band = prob.mu + prob.g
    margin = margin_frac * band      # zero-width band -> zero margin

    def body(i, x):
        Kx = obj.constraint_matvec(prob.K, x)
        lo_v = jnp.maximum((prob.d - prob.mu + margin) - Kx, 0.0)
        hi_v = jnp.maximum(Kx - (prob.d + prob.g - margin), 0.0)
        grad = (-2.0 * obj.constraint_matvec(prob.K.T, lo_v)
                + 2.0 * obj.constraint_matvec(prob.K.T, hi_v))
        # Lipschitz-ish step from row norms; cheap and robust.
        L = 2.0 * jnp.sum(prob.K * prob.K) + 1e-6
        return obj.project(prob, x - (1.0 / L) * grad)

    return jax.lax.fori_loop(0, steps, body, obj.project(prob, x0))


def _pgd(prob, x0, barrier_t, penalty_w, use_barrier, cfg: SolverConfig):
    """Inner projected-gradient loop, routed through the shared BB/Armijo
    engine (``core.pgd.pgd_minimize``): merit = eq.(1) objective + barrier
    or quadratic penalty, projection = box ∩ mask."""

    F = partial(obj.composite, prob, barrier_t=barrier_t, penalty_w=penalty_w,
                use_barrier=use_barrier)
    G = partial(obj.composite_grad, prob, barrier_t=barrier_t,
                penalty_w=penalty_w, use_barrier=use_barrier)
    # ftol=0.0: the barrier solver keeps its high-accuracy behavior — the
    # flat-streak stop only fires on literal zero-progress cycling (the
    # relaxation feeds KKT certificates and BnB bounds, so trading merit
    # digits for iterations is the warm-tick engines' business, not ours)
    pcfg = PGDConfig(max_iters=cfg.max_iters, step0=cfg.step0,
                     n_backtracks=cfg.n_backtracks, backtrack=cfg.backtrack,
                     armijo_c=cfg.armijo_c, tol=cfg.tol, ftol=0.0)
    return pgd_minimize(F, G, partial(obj.project, prob), x0, pcfg)


@partial(jax.jit, static_argnames=("cfg",))
def solve_relaxation(
    prob: AllocationProblem,
    x0: jnp.ndarray,
    cfg: SolverConfig = SolverConfig(),
) -> SolveResult:
    """Solve the continuous relaxation from a single start point."""
    x = phase1_point(prob, x0)
    lo, hi = obj.constraint_residuals(prob, x)
    strict = (jnp.min(lo) > 1e-3) & (jnp.min(hi) > 1e-3)

    def round_body(r, carry):
        x, total_it = carry
        t = cfg.barrier_t0 * (cfg.barrier_kappa ** r.astype(jnp.float32))
        x, _, it = _pgd(prob, x, jnp.asarray(t), jnp.asarray(cfg.penalty_w),
                        strict, cfg)
        return (x, total_it + it)

    x, iters = jax.lax.fori_loop(0, cfg.barrier_rounds, round_body,
                                 (x, jnp.asarray(0)))
    # feasibility restoration: a no-op when feasible (phase-1 gradient is 0
    # at margin 0), otherwise walks penalty-mode residual violation to ~0.
    x = phase1_point(prob, x, steps=100, margin_frac=0.0)
    fx = obj.objective(prob, x)
    comp = obj.composite(prob, x, jnp.asarray(cfg.barrier_t0),
                         jnp.asarray(cfg.penalty_w), strict)
    return SolveResult(
        x=x, fun=fx, composite=comp, iters=iters,
        feasible=obj.is_feasible(prob, x, tol=1e-3),
        used_barrier=strict,
    )
