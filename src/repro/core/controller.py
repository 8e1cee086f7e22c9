"""Infrastructure Optimization Controller (paper §I.C bullet 3 + §III.E).

Maintains a cluster allocation against a time-varying demand stream, replanning
each tick under the incremental-adoption constraint ||x - x_cur||_1 <= delta.
This is the production control loop: bounded churn, warm-started solves,
failure-driven replans (used by repro.distributed.elastic for TPU fleets).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from .api import problem_from_demand
from .catalog import Catalog
from .incremental import solve_incremental_info
from .pgd import AnytimeConfig
from .metrics import AllocationMetrics, evaluate
from .multistart import multistart_solve
from .problem import AllocationProblem, PenaltyParams
from .rounding import cover_in_float64, round_and_polish


@dataclass
class ControllerStep:
    """One recorded tick: the demand seen, the allocation deployed, its
    snapshot metrics, the L1 churn paid, and whether it was a full replan.

    ``churn_violation`` is the excess of ``churn`` over the controller's
    ``delta_max`` on a warm (non-replanned) tick: rounding may exceed the
    relaxed solve's churn bound slightly when demand jumps — the
    feasibility-first tradeoff (shortage beats churn). Zero on replans,
    which deliberately ignore the bound. Surfaced fleet-wide by
    ``FleetReplayMetrics.summary()`` so churn comparisons between
    controllers are honest about bound overruns.

    ``solver_iters`` records the PGD iterations the solve behind this tick
    actually took (0 where the engine did not report one, e.g. cold-start
    multistart ticks) — the adaptive-vs-fixed speedup evidence
    ``benchmarks/horizon_bench.py`` aggregates per cell.

    ``deadline_hit`` marks ticks whose solve was truncated by an enforced
    anytime deadline (``core.pgd.AnytimeConfig``) — the allocation is the
    solve's best-so-far feasible iterate, not its converged answer. Always
    False without an anytime budget."""

    demand: np.ndarray
    counts: np.ndarray
    metrics: AllocationMetrics
    churn: float                 # ||x_t - x_{t-1}||_1
    replanned: bool
    churn_violation: float = 0.0  # max(0, churn - delta_max) on warm ticks
    solver_iters: int = 0         # inner PGD iterations spent on this tick
    deadline_hit: bool = False    # anytime budget truncated this tick's solve


@dataclass
class InfrastructureOptimizationController:
    """Stateful per-cluster control loop: cold multistart solve on the first
    tick, then warm-started incremental solves under the L1 churn bound
    ``delta_max``. The batched fleet replay drives the same state via
    :meth:`apply_counts` (see docs/fleet.md, replay modes)."""

    catalog: Catalog
    delta_max: float = 8.0                       # max L1 churn per tick
    params: Optional[PenaltyParams] = None
    n_starts: int = 4
    allowed_idx: Optional[np.ndarray] = None
    normalize: bool = True                       # demand-normalized solver units
    x_current: np.ndarray = None                 # set on first step
    history: List[ControllerStep] = field(default_factory=list)
    # scenario surface (repro.core.terms / docs/scenarios.md): ``terms`` is a
    # static tuple of scenario-term specs attached to EVERY tick's problem;
    # ``spot_idx``/``spot_availability`` drive the per-tick spot overlay —
    # availability row t (clamped to the last row) zeroes the interrupted
    # spot types' capacity for the tick the controller is about to solve.
    terms: tuple = ()
    spot_idx: Optional[np.ndarray] = None        # (S,) catalog spot-twin idx
    spot_availability: Optional[np.ndarray] = None   # (T', S) in {0, 1}
    # opt-in solver observability: when True, every warm solve also captures
    # the engine's per-iteration convergence rows (core.pgd.PGDTrace, one
    # entry per warm tick on ``solver_traces``). The traced program computes
    # the same solution — see repro.obs.solver_trace.
    capture_solver_trace: bool = False
    solver_traces: List = field(default_factory=list)
    # enforced anytime budget (core.pgd.AnytimeConfig): when set with a
    # deadline, every warm solve runs chunked against the injectable clock
    # and deploys its best-so-far feasible iterate at expiry. None (or a
    # config without a deadline) keeps the untruncated engine — the exact
    # pre-anytime compiled program.
    anytime: Optional[AnytimeConfig] = None

    # not a dataclass field: last warm solve's PGD iteration count, consumed
    # by step() when recording the tick (0 until a warm solve has run)
    _last_solver_iters = 0
    # not a dataclass field: whether the last warm solve's anytime budget
    # expired before convergence (False without an anytime deadline)
    _last_deadline_hit = False
    # not a dataclass field: the last solve's RELAXED solution (set by both
    # cold and warm solves, and by the batched fleet engine). Health
    # monitoring (repro.obs.health) certifies THIS point through kkt_report
    # — integer counts are a rounding of it, not a stationary point.
    last_x_rel: Optional[np.ndarray] = None

    def make_problem(self, demand: np.ndarray) -> AllocationProblem:
        """Build this tick's AllocationProblem — the same construction as the
        one-shot api.optimize pipeline, so a constant-demand replay reproduces
        the single-shot result. Also used by the batched fleet replay engine,
        which stacks these per-tenant problems into one padded batch.

        The current tick index is ``len(self.history)`` (the step being
        built has not been applied yet) — identical in the sequential and
        batched engines, so the spot overlay stays bit-exact across them.
        The MPC controller builds its whole lookahead window through this
        method before advancing history, so a tick's availability applies
        to all window rows: interruptions are observed, not forecast, and
        an observed outage is assumed to persist over the horizon."""
        return problem_from_demand(self.catalog, demand, params=self.params,
                                   allowed_idx=self.allowed_idx,
                                   normalize=self.normalize,
                                   terms=self.terms,
                                   unavailable_idx=self._unavailable_idx())

    def _unavailable_idx(self) -> Optional[np.ndarray]:
        """Spot types interrupted at the tick about to be recorded (None
        without a spot overlay)."""
        if self.spot_idx is None or self.spot_availability is None:
            return None
        avail = np.asarray(self.spot_availability)
        t = min(len(self.history), len(avail) - 1)
        spot = np.asarray(self.spot_idx, np.int64)
        return spot[avail[t] <= 0.0]

    def _addable(self) -> np.ndarray:
        """(n,) bool: the types this tick's problem may add units of."""
        ok = np.ones(self.catalog.n, bool)
        if self.allowed_idx is not None:
            ok[:] = False
            ok[np.asarray(self.allowed_idx, np.int64)] = True
        unavailable = self._unavailable_idx()
        if unavailable is not None:
            ok[unavailable] = False
        return ok

    # back-compat alias (pre-docs name)
    _problem = make_problem

    def cold_start_counts(self, prob: AllocationProblem) -> np.ndarray:
        """First-tick allocation: full multistart solve, no churn bound; take
        the best rounded start (matches api.optimize without BnB)."""
        ms = multistart_solve(prob, n_starts=self.n_starts)
        self.last_x_rel = np.asarray(ms.best.x, np.float64)
        return np.asarray(ms.x_int, np.float64)

    def incremental_counts(self, prob: AllocationProblem,
                           x_init: Optional[np.ndarray] = None) -> np.ndarray:
        """Warm-tick allocation: incremental solve from the current counts
        under the L1 churn bound, then greedy rounding. ``x_init`` optionally
        overrides the warm start (e.g. the previous tick's relaxed solution,
        plumbed through by the batched replay engine). The adaptive solve's
        iteration count is kept on ``_last_solver_iters`` for
        :meth:`apply_counts` bookkeeping; with ``capture_solver_trace`` the
        engine's convergence rows are appended to ``solver_traces``."""
        x_init = None if x_init is None else jnp.asarray(x_init, jnp.float32)
        self._last_deadline_hit = False
        if self.anytime is not None and self.anytime.enabled:
            if self.capture_solver_trace:
                raise ValueError("anytime deadlines and "
                                 "capture_solver_trace are mutually "
                                 "exclusive; drop one")
            x_rel, iters, report = solve_incremental_info(
                prob, jnp.asarray(self.x_current, jnp.float32),
                jnp.asarray(self.delta_max, jnp.float32), x_init=x_init,
                anytime=self.anytime)
            self._last_deadline_hit = bool(report.deadline_hit)
        elif self.capture_solver_trace:
            x_rel, iters, trace = solve_incremental_info(
                prob, jnp.asarray(self.x_current, jnp.float32),
                jnp.asarray(self.delta_max, jnp.float32),
                x_init=x_init, capture_trace=True)
            self.solver_traces.append(
                type(trace)(*(np.asarray(f) for f in trace)))
        else:
            x_rel, iters = solve_incremental_info(
                prob, jnp.asarray(self.x_current, jnp.float32),
                jnp.asarray(self.delta_max, jnp.float32), x_init=x_init)
        self._last_solver_iters = int(iters)
        self.last_x_rel = np.asarray(x_rel, np.float64)
        # rounding may exceed the churn bound slightly when demand jumps;
        # that's the feasibility-first tradeoff (shortage beats churn).
        return np.asarray(round_and_polish(prob, x_rel), np.float64)

    def apply_counts(self, demand: np.ndarray, counts: np.ndarray,
                     replanned: bool, solver_iters: int = 0,
                     deadline_hit: bool = False) -> ControllerStep:
        """Record an allocation computed for this tick (by :meth:`step`, or
        externally by the batched fleet engine): compute churn and metrics,
        advance ``x_current``, append to history. Counts that f32 rounding
        left within its own error short of demand are first topped up in
        float64 (:func:`repro.core.rounding.cover_in_float64`), so every
        engine commits the same covering allocation. ``solver_iters`` optionally
        records the inner PGD iterations the solve took (see
        ``ControllerStep.solver_iters``); ``deadline_hit`` whether an
        anytime budget truncated it."""
        demand = np.asarray(demand, np.float64)
        x = np.asarray(counts, np.float64)
        metrics = evaluate(self.catalog, x, demand)
        if not metrics.satisfied:
            # f32 rounding can stop within its own error of the demand
            K, _, c = self.catalog.matrices()
            x = cover_in_float64(K, c, self._addable(), x, demand)
            metrics = evaluate(self.catalog, x, demand)
        churn = float(np.abs(x - (self.x_current if self.x_current is not None
                                  else np.zeros_like(x))).sum())
        # rounding may overshoot the relaxed solve's churn bound; record the
        # excess (replans ignore the bound by design, so they report 0)
        violation = 0.0 if replanned else max(0.0, churn - float(self.delta_max))
        self.x_current = x
        step = ControllerStep(demand=demand, counts=x,
                              metrics=metrics,
                              churn=churn, replanned=replanned,
                              churn_violation=violation,
                              solver_iters=int(solver_iters),
                              deadline_hit=bool(deadline_hit))
        self.history.append(step)
        return step

    def step(self, demand: np.ndarray,
             x_init: Optional[np.ndarray] = None) -> ControllerStep:
        """Advance one tick: solve for this demand (cold multistart on the
        first call, warm-started incremental solve after) and record it."""
        demand = np.asarray(demand, np.float64)
        prob = self.make_problem(demand)
        if self.x_current is None:
            x, replanned = self.cold_start_counts(prob), True
            self._last_solver_iters = 0
            self._last_deadline_hit = False
        else:
            x, replanned = self.incremental_counts(prob, x_init=x_init), False
        return self.apply_counts(demand, x, replanned,
                                 solver_iters=self._last_solver_iters,
                                 deadline_hit=self._last_deadline_hit)

    def replan_on_failure(self, failed_counts: np.ndarray,
                          demand: np.ndarray) -> ControllerStep:
        """Remove failed nodes from the current allocation, then replan with
        the churn bound relaxed by the failure size (we must at least replace
        what died)."""
        assert self.x_current is not None, "controller has no allocation yet"
        failed = np.minimum(np.asarray(failed_counts, np.float64), self.x_current)
        self.x_current = self.x_current - failed
        old_delta = self.delta_max
        self.delta_max = float(old_delta + failed.sum())
        try:
            out = self.step(demand)
        finally:
            self.delta_max = old_delta
        return out

    def total_cost(self) -> float:
        return sum(s.metrics.total_cost for s in self.history)

    def total_churn(self) -> float:
        return sum(s.churn for s in self.history)
