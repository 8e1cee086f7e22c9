"""Greedy rounding strategy — paper §III.B, implemented verbatim:

  1. x_hat = floor(x*)
  2. delta = d - K x_hat
  3. while delta has positive components:
       pick i maximizing  sum_{r: delta_r>0} K_ri * delta_r / c_i
       x_hat_i += 1; recompute delta

jit-able via ``lax.while_loop``; the iteration count is bounded by the number
of unit increments needed, capped at ``max_adds``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import objective as obj
from .metrics import COVER_TOL
from .problem import AllocationProblem


@partial(jax.jit, static_argnames=("max_adds",))
def greedy_round(prob: AllocationProblem, x_star: jnp.ndarray,
                 max_adds: int = 4096) -> jnp.ndarray:
    """Round a fractional solution to a feasible integer allocation."""
    x0 = jnp.floor(jnp.clip(x_star, prob.lb, prob.ub)) * prob.mask
    # deficits measured against the hard lower bound d - mu (primal feas.)
    target = prob.d - prob.mu

    def deficit(x):
        return target - obj.constraint_matvec(prob.K, x)

    def cond(state):
        x, it = state
        return jnp.any(deficit(x) > 1e-6) & (it < max_adds)

    def body(state):
        x, it = state
        delta = deficit(x)
        pos = jnp.maximum(delta, 0.0)
        score = (prob.K.T @ pos) / jnp.maximum(prob.c, 1e-9)       # (n,)
        # never pick masked-out or at-upper-bound types
        ok = (prob.mask > 0) & (x < prob.ub)
        score = jnp.where(ok, score, -jnp.inf)
        i = jnp.argmax(score)
        return x.at[i].add(1.0), it + 1

    x, _ = jax.lax.while_loop(cond, body, (x0, jnp.asarray(0)))
    return x


def round_and_polish(prob: AllocationProblem, x_star: jnp.ndarray,
                     max_adds: int = 4096) -> jnp.ndarray:
    """Paper's greedy rounding plus two beyond-paper polish passes:
      * also try the ceil() candidate (keeps all fractional types instead of
        dropping them at floor()),
      * scale-down pass: drop units whose removal stays feasible,
        most-expensive first (mirrors CA's scale-down).
    Picks the feasible candidate with the lower objective."""
    a = scale_down(prob, greedy_round(prob, x_star, max_adds=max_adds))
    ceil_start = jnp.ceil(jnp.clip(x_star, prob.lb, prob.ub)) * prob.mask
    # tiny fractions should not force a whole node: drop < 0.05 before ceil
    ceil_start = jnp.where(x_star - jnp.floor(x_star) < 0.05,
                           jnp.floor(x_star), ceil_start)
    b = scale_down(prob, greedy_round(prob, ceil_start, max_adds=max_adds))
    fa, fb = obj.objective(prob, a), obj.objective(prob, b)
    feas_a = obj.is_feasible(prob, a, 1e-3)
    feas_b = obj.is_feasible(prob, b, 1e-3)
    pick_a = jnp.where(feas_a == feas_b, fa <= fb, feas_a)
    return jnp.where(pick_a, a, b)


@partial(jax.jit, static_argnames=("max_removes",))
def scale_down(prob: AllocationProblem, x: jnp.ndarray,
               max_removes: int = 4096) -> jnp.ndarray:
    """Drop units whose removal keeps Kx >= d - mu, most-expensive first —
    the polish mirroring CA's utilization-gated scale-down."""
    target = prob.d - prob.mu

    def removable(x):
        """cost of each type whose decrement keeps K x >= target."""
        Kx = obj.constraint_matvec(prob.K, x)
        slack_ok = jnp.all(Kx[:, None] - prob.K >= target[:, None] - 1e-6, axis=0)
        can = slack_ok & (x >= 1.0) & (x - 1.0 >= prob.lb)
        return jnp.where(can, prob.c, -jnp.inf)

    def cond(state):
        x, it = state
        return jnp.any(jnp.isfinite(removable(x)) & (removable(x) > 0)) & (it < max_removes)

    def body(state):
        x, it = state
        i = jnp.argmax(removable(x))
        return x.at[i].add(-1.0), it + 1

    x, _ = jax.lax.while_loop(cond, body, (x, jnp.asarray(0)))
    return x


# a shortfall below this share of a resource's demand is f32 error of K x
F32_GAP = 1e-5


def cover_in_float64(K: np.ndarray, c: np.ndarray, addable: np.ndarray,
                     counts: np.ndarray, demand: np.ndarray) -> np.ndarray:
    """Finish greedy rounding in float64 raw units, on the host.

    :func:`greedy_round` and :func:`scale_down` run in f32 on K scaled by
    1/d and accept a deficit of 1e-6 of demand, about the f32 error of
    K x; :func:`repro.core.metrics.evaluate` checks coverage in float64 raw
    units and accepts :data:`~repro.core.metrics.COVER_TOL` absolute. An
    allocation short by less than :data:`F32_GAP` of a resource's demand,
    which is f32 error and not a real shortfall, gets the greedy pick (most
    deficit covered per dollar among the ``addable`` types) one unit at a
    time until that check holds. A covering allocation, or one short by
    more, is returned unchanged."""
    K = np.asarray(K, np.float64)
    demand = np.asarray(demand, np.float64)
    x = np.array(counts, np.float64)
    deficit = demand - K @ x
    if (np.all(deficit <= COVER_TOL)
            or np.any(deficit > F32_GAP * np.abs(demand) + COVER_TOL)):
        return x
    while np.any(deficit > COVER_TOL):
        score = np.where(addable, K.T @ np.maximum(deficit, 0.0)
                         / np.maximum(c, 1e-9), -np.inf)
        j = int(np.argmax(score))
        if not score[j] > 0.0:
            break
        x[j] += 1.0
        deficit = demand - K @ x
    return x
