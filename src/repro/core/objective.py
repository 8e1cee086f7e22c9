"""The eq. (1) objective, its analytic gradient (eq. 6), and the constraint
machinery (log-barrier / quadratic penalty) used by the solver.

Term math lives in the ``repro.core.terms`` registry: the four paper terms
plus any scenario terms attached on ``prob.terms`` (SLO pricing, priority
eviction, spot risk).  The functions here are registry sums — base terms in
the seed trace order, then attached terms — so a problem with ``terms=()``
compiles to exactly the seed graph (jaxpr-identity is test-pinned).

Pure jnp — every function here is jit- and vmap-safe. The fused Pallas kernel
in ``repro.kernels.alloc_objective`` implements the batched (multi-start)
objective+gradient and is validated against THESE functions, which act as the
oracle.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import terms as _terms
from .problem import AllocationProblem

# ---------------------------------------------------------------------------
# Objective terms (paper eq. 1 + attached scenario terms)
# ---------------------------------------------------------------------------


def objective_terms(prob: AllocationProblem, x: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Return each named term of f(x) — eq. (1) terms plus every attached
    scenario term, one matvec pair shared across all of them. x: (n,)."""
    Kx = prob.K @ x                       # (m,)
    Ex = prob.E @ x                       # (p,)
    return _terms.term_values(prob, x, Kx, Ex)


def objective(prob: AllocationProblem, x: jnp.ndarray) -> jnp.ndarray:
    """f(x): the full objective (registry sum of objective_terms)."""
    return _terms.sum_terms(objective_terms(prob, x))


def grad_objective(prob: AllocationProblem, x: jnp.ndarray) -> jnp.ndarray:
    """Analytic gradient: registry sum of per-term gradients.  For the base
    terms this is the stationarity expression (eq. 6/8):

      grad = c + a*b1*E^T e^{-b1 Ex} - g*b2*E^T 1/(1+b2 Ex)
               - 2*b3*K^T diag(s)(d - Kx)
    """
    Kx = prob.K @ x
    Ex = prob.E @ x
    return _terms.sum_terms(_terms.term_grads(prob, x, Kx, Ex))


def value_and_grad(prob: AllocationProblem, x: jnp.ndarray):
    """(f(x), ∇f(x)) — the oracle the Pallas kernel is validated against.
    Fused: ONE ``K@x``/``E@x`` pair feeds both the value and gradient
    registry sums (the seed version recomputed the matvecs per side)."""
    Kx = prob.K @ x
    Ex = prob.E @ x
    val = _terms.sum_terms(_terms.term_values(prob, x, Kx, Ex))
    grad = _terms.sum_terms(_terms.term_grads(prob, x, Kx, Ex))
    return val, grad


# ---------------------------------------------------------------------------
# Constraint handling (paper eq. 2): d - mu <= Kx <= d + g
# ---------------------------------------------------------------------------

# Every contraction against K that evaluates the constraint (or its barrier
# and penalty gradients) runs at full f32 precision. TPU's default rounds f32
# operands of a batched matmul to bf16; K x then errs by ~0.4% and rounding
# accepts allocations that leave demand uncovered in float64. On CPU the
# setting changes no bit.
CONSTRAINT_PRECISION = jax.lax.Precision.HIGHEST


def constraint_matvec(K: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``K @ v`` at :data:`CONSTRAINT_PRECISION` (pass ``K.T`` for the
    gradient side)."""
    return jnp.matmul(K, v, precision=CONSTRAINT_PRECISION)


def constraint_residuals(prob: AllocationProblem, x: jnp.ndarray):
    """Positive residual == satisfied. Returns (lower (m,), upper (m,))."""
    Kx = constraint_matvec(prob.K, x)
    return Kx - (prob.d - prob.mu), (prob.d + prob.g) - Kx


def constraint_violation(prob: AllocationProblem, x: jnp.ndarray) -> jnp.ndarray:
    """Squared violation of the two-sided band (0 iff band-feasible)."""
    lo, hi = constraint_residuals(prob, x)
    return jnp.sum(jnp.maximum(-lo, 0.0) ** 2) + jnp.sum(jnp.maximum(-hi, 0.0) ** 2)


def is_feasible(prob: AllocationProblem, x: jnp.ndarray, tol: float = 1e-4):
    """Band + box feasibility within ``tol`` (the rounding acceptance test)."""
    lo, hi = constraint_residuals(prob, x)
    box = jnp.all(x >= prob.lb - tol) & jnp.all(x <= prob.ub + tol)
    return jnp.all(lo >= -tol) & jnp.all(hi >= -tol) & box


def barrier(prob: AllocationProblem, x: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Log-barrier for the two-sided Kx constraint. Returns +inf outside the
    strict interior (handled by the line search rejecting such points)."""
    lo, hi = constraint_residuals(prob, x)
    safe = (lo > 0).all() & (hi > 0).all()
    val = -(1.0 / t) * (jnp.sum(jnp.log(jnp.where(lo > 0, lo, 1.0)))
                        + jnp.sum(jnp.log(jnp.where(hi > 0, hi, 1.0))))
    return jnp.where(safe, val, jnp.inf)


def barrier_grad(prob: AllocationProblem, x: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """∇ of the log-barrier (residuals clamped away from 0 for safety)."""
    lo, hi = constraint_residuals(prob, x)
    lo = jnp.maximum(lo, 1e-9)
    hi = jnp.maximum(hi, 1e-9)
    return (-(1.0 / t) * constraint_matvec(prob.K.T, 1.0 / lo)
            + (1.0 / t) * constraint_matvec(prob.K.T, 1.0 / hi))


def penalty(prob: AllocationProblem, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Smooth quadratic exact-ish penalty used when no strict interior exists."""
    return w * constraint_violation(prob, x)


def penalty_grad(prob: AllocationProblem, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """∇ of the quadratic penalty (the barrier's fallback, paper impl. notes)."""
    lo, hi = constraint_residuals(prob, x)
    # d(sum max(-lo,0)^2)/dx = 2 K^T max(-lo,0) * d(-lo)/dKx ...
    g_lo = constraint_matvec(prob.K.T, jnp.maximum(-lo, 0.0))
    g_hi = constraint_matvec(prob.K.T, jnp.maximum(-hi, 0.0))
    return w * (-2.0 * g_lo + 2.0 * g_hi)


# ---------------------------------------------------------------------------
# Composite objective used by the solver
# ---------------------------------------------------------------------------


def composite(
    prob: AllocationProblem,
    x: jnp.ndarray,
    barrier_t: jnp.ndarray,
    penalty_w: jnp.ndarray,
    use_barrier: jnp.ndarray,
) -> jnp.ndarray:
    """f(x) + (barrier | penalty). ``use_barrier`` is a traced bool."""
    f = objective(prob, x)
    b = barrier(prob, x, barrier_t)
    q = penalty(prob, x, penalty_w)
    return f + jnp.where(use_barrier, b, q)


def composite_grad(
    prob: AllocationProblem,
    x: jnp.ndarray,
    barrier_t: jnp.ndarray,
    penalty_w: jnp.ndarray,
    use_barrier: jnp.ndarray,
) -> jnp.ndarray:
    """∇ of :func:`composite` — the solver's per-iteration gradient."""
    gf = grad_objective(prob, x)
    gb = barrier_grad(prob, x, barrier_t)
    gq = penalty_grad(prob, x, penalty_w)
    return gf + jnp.where(use_barrier, gb, gq)


# ---------------------------------------------------------------------------
# Projection
# ---------------------------------------------------------------------------


def project(prob: AllocationProblem, x: jnp.ndarray) -> jnp.ndarray:
    """Project onto the box [lb, ub] intersected with the mask support."""
    return jnp.clip(x, prob.lb, prob.ub) * prob.mask
