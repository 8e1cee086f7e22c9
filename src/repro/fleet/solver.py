"""solve_fleet — one compiled program solving the whole fleet.

Mirrors core.solver.solve_relaxation (phase-1 -> barrier/penalty PGD with a
Barzilai-Borwein step and an Armijo backtracking ladder -> feasibility
restoration -> rounding) but carries the full (B tenants, S starts) state
through every step. In the hand-batched hot loop ("kernel"/"ref" modes) each
iteration evaluates the Armijo ladder's B*S*L candidate VALUES in one batched
pass and the objective+gradient at the accepted iterate with a single call
into the batched Pallas alloc_objective kernel ("kernel"; grid over tenants x
point blocks) or its einsum oracle ("ref"). The "vmap" mode instead vmaps the
unmodified core solver — bit-identical per lane to sequential solves, and the
fastest dispatch on CPU where Pallas runs in interpret mode.

Phase-1, greedy rounding and start generation genuinely reuse the core
implementations under vmap — the stacked batch from repro.fleet.batching is
a valid AllocationProblem per vmap slice, padding included.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import terms as core_terms
from repro.core.incremental import (incremental_anytime_chunk,
                                    incremental_anytime_init,
                                    solve_incremental_info)
from repro.core.multistart import make_starts
from repro.core.pgd import (AnytimeConfig, AnytimeReport, PGDConfig, PGDTrace,
                            run_anytime)
from repro.core.objective import CONSTRAINT_PRECISION, is_feasible, objective
from repro.core.problem import AllocationProblem
from repro.core.rounding import round_and_polish
from repro.core.solver import SolverConfig, phase1_point, solve_relaxation
from repro.kernels import resolve_interpret
from repro.kernels.alloc_objective.ops import fleet_value_and_grad
from repro.kernels.alloc_objective.ref import alloc_objective_fleet_value
from repro.obs.telemetry import span

from .batching import (BucketedFleet, FleetBatch, bucket_problems,
                       scatter_from_buckets, stack_problems, tenant_problem)


class FleetSolveResult(NamedTuple):
    """Per-tenant outputs of a batched fleet solve (leading axis = tenant).

    The per-start rounded candidates (``x_int_all`` / ``fun_int_all`` /
    ``feas_int_all``) mirror ``core.multistart.MultiStartResult``: callers
    can re-score the whole candidate set against a different merit — the
    batched MPC replay's ``cold_start="window"`` scores them against each
    tenant's whole lookahead window instead of tick 0."""

    x: jnp.ndarray            # (B, n) best relaxed solution per tenant
    fun: jnp.ndarray          # (B,) objective at x
    x_int: jnp.ndarray        # (B, n) best rounded integer solution
    fun_int: jnp.ndarray      # (B,) objective at x_int
    feasible: jnp.ndarray     # (B,) integer-solution feasibility
    used_barrier: jnp.ndarray  # (B, S)
    all_fun: jnp.ndarray      # (B, S) relaxed objective per start
    iters: jnp.ndarray        # total PGD iterations (fleet-wide)
    x_int_all: jnp.ndarray    # (B, S, n) rounded candidate per start
    fun_int_all: jnp.ndarray  # (B, S) objective per rounded candidate
    feas_int_all: jnp.ndarray  # (B, S) integer feasibility per candidate


# ---------------------------------------------------------------------------
# batched constraint machinery (leaves carry a leading (B,) axis; points may
# be (B, T, n) for any T — starts or the flattened candidate ladder)
# ---------------------------------------------------------------------------


def _bcast(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Reshape a (B, k) problem leaf to broadcast against (B, ..., k) x."""
    return a.reshape(a.shape[0], *([1] * (x.ndim - 2)), a.shape[-1])


def _project(prob: AllocationProblem, X: jnp.ndarray) -> jnp.ndarray:
    return (jnp.clip(X, _bcast(prob.lb, X), _bcast(prob.ub, X))
            * _bcast(prob.mask, X))


def _residuals(prob: AllocationProblem, X: jnp.ndarray):
    KX = jnp.einsum("bmn,b...n->b...m", prob.K, X,
                    precision=CONSTRAINT_PRECISION)
    lo = KX - _bcast(prob.d - prob.mu, X)
    hi = _bcast(prob.d + prob.g, X) - KX
    return lo, hi


def _terms_value(prob: AllocationProblem, X: jnp.ndarray) -> jnp.ndarray:
    """(B, T) sum of attached scenario-term values — the registry's additive
    hook for the hand-batched hot loop (the Pallas kernel computes only the
    four base terms; its oracle contract is untouched)."""
    return jax.vmap(lambda pb, Xt: jax.vmap(
        lambda x: core_terms.active_value(pb, x))(Xt))(prob, X)


def _terms_grad(prob: AllocationProblem, X: jnp.ndarray) -> jnp.ndarray:
    """(B, T, n) gradient counterpart of :func:`_terms_value`."""
    return jax.vmap(lambda pb, Xt: jax.vmap(
        lambda x: core_terms.active_grad(pb, x))(Xt))(prob, X)


def _objective_value(prob: AllocationProblem, X: jnp.ndarray) -> jnp.ndarray:
    """Objective values only for X (B, T, n) — the Armijo-ladder evaluation.
    The gradient (kernel path) is evaluated once per iteration at the
    ACCEPTED point, exactly like core.solver._pgd.  Attached scenario terms
    add on top of the kernel's base-term value; the ``if prob.terms:`` gate
    is Python-static, so the default (no-terms) compiled graph is the seed
    graph byte-for-byte."""
    P = prob.params
    val = alloc_objective_fleet_value(X, prob.K, prob.E, prob.c, prob.d,
                                      P.alpha, P.beta1, P.beta2, P.beta3,
                                      P.gamma)
    if prob.terms:
        val = val + _terms_value(prob, X)
    return val


def _constraint_values(prob: AllocationProblem, X: jnp.ndarray,
                       barrier_t, penalty_w):
    """Barrier and penalty VALUES for X (B, T, n)."""
    lo, hi = _residuals(prob, X)                       # (B, T, m) each
    safe = jnp.all(lo > 0, -1) & jnp.all(hi > 0, -1)   # (B, T)
    bval = -(1.0 / barrier_t) * (
        jnp.sum(jnp.log(jnp.where(lo > 0, lo, 1.0)), -1)
        + jnp.sum(jnp.log(jnp.where(hi > 0, hi, 1.0)), -1))
    bval = jnp.where(safe, bval, jnp.inf)
    vlo = jnp.maximum(-lo, 0.0)
    vhi = jnp.maximum(-hi, 0.0)
    qval = penalty_w * (jnp.sum(vlo**2, -1) + jnp.sum(vhi**2, -1))
    return bval, qval


def _constraint_grads(prob: AllocationProblem, X: jnp.ndarray,
                      barrier_t, penalty_w):
    """Barrier and penalty GRADIENTS for X (B, T, n)."""
    lo, hi = _residuals(prob, X)
    lo_c = jnp.maximum(lo, 1e-9)
    hi_c = jnp.maximum(hi, 1e-9)
    KT = partial(jnp.einsum, "bmn,btm->btn", prob.K,
                 precision=CONSTRAINT_PRECISION)
    bgrad = (1.0 / barrier_t) * (KT(1.0 / hi_c) - KT(1.0 / lo_c))
    vlo = jnp.maximum(-lo, 0.0)
    vhi = jnp.maximum(-hi, 0.0)
    qgrad = penalty_w * 2.0 * (KT(vhi) - KT(vlo))
    return bgrad, qgrad


def _is_feasible(prob: AllocationProblem, X: jnp.ndarray, tol: float):
    """(B, ...) feasibility for X (B, ..., n)."""
    lo, hi = _residuals(prob, X)
    box = (jnp.all(X >= _bcast(prob.lb, X) - tol, -1)
           & jnp.all(X <= _bcast(prob.ub, X) + tol, -1))
    return jnp.all(lo >= -tol, -1) & jnp.all(hi >= -tol, -1) & box


def _pgd_fleet(prob, X0, barrier_t, penalty_w, strict, cfg: SolverConfig,
               use_kernel: bool, interpret: bool):
    """Batched inner PGD over (B, S) simultaneous solves.

    Per-element state exactly mirrors core.solver._pgd; finished elements
    freeze in place while the rest keep iterating.
    """
    B, S, n = X0.shape

    def F_values(Xc, T):
        """Composite values for Xc (B, T, n); T is S or S*L."""
        f = _objective_value(prob, Xc)
        bval, qval = _constraint_values(prob, Xc, barrier_t, penalty_w)
        s = jnp.repeat(strict, T // S, axis=1) if T != S else strict
        return f + jnp.where(s, bval, qval)

    def G_at(Xc):
        """Composite gradient at the (B, S, n) iterate — the hot call routed
        through the batched Pallas kernel (or its einsum oracle); attached
        scenario terms add their registry gradients on top (statically
        absent when ``prob.terms`` is empty)."""
        _, g = fleet_value_and_grad(prob, Xc, interpret=interpret,
                                    use_kernel=use_kernel)
        if prob.terms:
            g = g + _terms_grad(prob, Xc)
        bgrad, qgrad = _constraint_grads(prob, Xc, barrier_t, penalty_w)
        return g + jnp.where(strict[..., None], bgrad, qgrad)

    L = cfg.n_backtracks
    ratios = cfg.backtrack ** jnp.arange(-1, L - 1)    # 1 upscale, as core

    def cond(state):
        x, fx, g, bb, it, done = state
        return jnp.any(~done) & (it < cfg.max_iters)

    def body(state):
        x, fx, g, bb, it, done = state
        steps = bb[..., None] * ratios                                # (B,S,L)
        cands = _project(prob, x[:, :, None, :]
                         - steps[..., None] * g[:, :, None, :])       # (B,S,L,n)
        Fc = F_values(cands.reshape(B, S * L, n), S * L).reshape(B, S, L)
        # Armijo on the projected step: F(x+) <= F(x) + c * g^T (x+ - x)
        dec = Fc - (fx[..., None] + cfg.armijo_c *
                    jnp.sum(g[:, :, None, :] * (cands - x[:, :, None, :]), -1))
        ok = (dec <= 0.0) & jnp.isfinite(Fc)
        idx = jnp.argmax(ok, axis=-1)                  # first (largest) step
        any_ok = jnp.any(ok, axis=-1)
        sel = lambda a, extra: jnp.take_along_axis(
            a, idx.reshape(B, S, 1, *([1] * extra)), axis=2).squeeze(2)
        x_new = jnp.where(any_ok[..., None], sel(cands, 1), x)
        f_new = jnp.where(any_ok, sel(Fc, 0), fx)
        g_new = G_at(x_new)
        # BB1 step from the accepted move (safeguarded into [1e-8, 1e4])
        dx = x_new - x
        dg = g_new - g
        denom = jnp.sum(dx * dg, -1)
        bb_new = jnp.where(jnp.abs(denom) > 1e-12,
                           jnp.abs(jnp.sum(dx * dx, -1) / denom), cfg.step0)
        bb_new = jnp.clip(bb_new, 1e-8, 1e4)
        bb_new = jnp.where(any_ok, bb_new, bb * cfg.backtrack ** L)
        move = jnp.max(jnp.abs(dx), -1)
        newly_done = ((~any_ok) & (bb < 1e-7)) | (any_ok & (move < cfg.tol))
        # freeze elements that were already done before this iteration
        x_new = jnp.where(done[..., None], x, x_new)
        f_new = jnp.where(done, fx, f_new)
        g_new = jnp.where(done[..., None], g, g_new)
        bb_new = jnp.where(done, bb, bb_new)
        return (x_new, f_new, g_new, bb_new, it + 1, done | newly_done)

    X0 = _project(prob, X0)
    state = (X0, F_values(X0, S), G_at(X0), jnp.full((B, S), cfg.step0),
             jnp.asarray(0), jnp.zeros((B, S), bool))
    x, fx, _, _, it, _ = jax.lax.while_loop(cond, body, state)
    return x, fx, it


def _relax_kernel_path(prob, starts, cfg, use_kernel, interpret):
    """Hand-batched phase-1 -> barrier PGD with the kernel-routed hot loop."""
    phase1 = jax.vmap(lambda pb, xs: jax.vmap(
        lambda x0: phase1_point(pb, x0))(xs))
    x = phase1(prob, starts)                                       # (B, S, n)
    lo, hi = _residuals(prob, x)
    strict = (jnp.min(lo, -1) > 1e-3) & (jnp.min(hi, -1) > 1e-3)   # (B, S)

    def round_body(r, carry):
        x, total_it = carry
        t = cfg.barrier_t0 * (cfg.barrier_kappa ** r.astype(jnp.float32))
        x, _, it = _pgd_fleet(prob, x, jnp.asarray(t),
                              jnp.asarray(cfg.penalty_w), strict, cfg,
                              use_kernel, interpret)
        return (x, total_it + it)

    x, iters = jax.lax.fori_loop(0, cfg.barrier_rounds, round_body,
                                 (x, jnp.asarray(0)))
    # feasibility restoration (no-op when already feasible)
    restore = jax.vmap(lambda pb, xs: jax.vmap(
        lambda x0: phase1_point(pb, x0, steps=100, margin_frac=0.0))(xs))
    x = restore(prob, x)
    fun = _objective_value(prob, x)                                 # (B, S)
    feas = _is_feasible(prob, x, 1e-3)
    return x, fun, feas, strict, iters


def _relax_vmap_path(prob, starts, cfg):
    """vmap of the UNMODIFIED core solver. XLA preserves the per-lane op
    structure under vmap, so each lane's trajectory is bit-identical to a
    standalone solve_relaxation call — the reference fleet path (and the
    fastest on CPU, where the Pallas kernel would run in interpret mode)."""
    res = jax.vmap(lambda pb, xs: jax.vmap(
        lambda x0: solve_relaxation(pb, x0, cfg))(xs))(prob, starts)
    return res.x, res.fun, res.feasible, res.used_barrier, jnp.sum(res.iters)


@partial(jax.jit, static_argnames=("cfg", "hot_loop", "interpret"))
def _solve_fleet_impl(prob: AllocationProblem, starts: jnp.ndarray,
                      cfg: SolverConfig, hot_loop: str, interpret: bool
                      ) -> FleetSolveResult:
    B, S, n = starts.shape
    if hot_loop == "vmap":
        x, fun, feas_rel, strict, iters = _relax_vmap_path(prob, starts, cfg)
    else:
        x, fun, feas_rel, strict, iters = _relax_kernel_path(
            prob, starts, cfg, use_kernel=(hot_loop == "kernel"),
            interpret=interpret)

    # round EVERY start (relaxed merit predicts integer cost poorly); the
    # vmapped greedy rounding + objective reuse the core implementations
    x_int = jax.vmap(lambda pb, xs: jax.vmap(
        lambda xr: round_and_polish(pb, xr))(xs))(prob, x)          # (B, S, n)
    f_int = jax.vmap(lambda pb, xs: jax.vmap(
        lambda xi: objective(pb, xi))(xs))(prob, x_int)
    feas_int = jax.vmap(lambda pb, xs: jax.vmap(
        lambda xi: is_feasible(pb, xi, 1e-3))(xs))(prob, x_int)

    take_b = lambda a, j, extra: jnp.take_along_axis(
        a, j.reshape(B, 1, *([1] * extra)), axis=1).squeeze(1)
    merit_int = jnp.where(feas_int, f_int, f_int + 1e12)
    j = jnp.argmin(merit_int, axis=1)                               # (B,)
    merit_rel = jnp.where(feas_rel, fun, fun + 1e12)
    i = jnp.argmin(merit_rel, axis=1)
    return FleetSolveResult(
        x=take_b(x, i, 1), fun=take_b(fun, i, 0),
        x_int=take_b(x_int, j, 1), fun_int=take_b(f_int, j, 0),
        feasible=take_b(feas_int, j, 0),
        used_barrier=strict, all_fun=fun, iters=iters,
        x_int_all=x_int, fun_int_all=f_int, feas_int_all=feas_int)


def solve_fleet(
    fleet: Union[FleetBatch, Sequence[AllocationProblem], AllocationProblem],
    n_starts: int = 4,
    seed: int = 0,
    cfg: Optional[SolverConfig] = None,
    starts: Optional[jnp.ndarray] = None,
    hot_loop: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> FleetSolveResult:
    """Solve every tenant problem in one compiled batched program.

    ``fleet`` may be a FleetBatch, a list of (ragged) AllocationProblems, or
    an already-stacked AllocationProblem with (B,) leading leaf axes.
    ``starts`` overrides the generated (B, S, n) start points.

    ``hot_loop`` picks the relaxation engine:
      * "vmap"   — vmap of the unmodified core solver; per-lane trajectories
                   are bit-identical to sequential solve_relaxation calls.
                   Default on CPU.
      * "kernel" — hand-batched PGD with objective+gradient routed through
                   the batched Pallas alloc_objective kernel (one pallas_call
                   per iteration for the whole fleet). Default on TPU;
                   ``interpret=True`` validates it on CPU.
      * "ref"    — the hand-batched PGD with the einsum oracle instead of
                   the Pallas kernel (kernel-path debugging).
    The PGD step acceptance is chaotic in the last ulps, so "kernel"/"ref"
    agree with sequential solves to solver tolerance (per-tenant ~1e-2,
    fleet aggregate ~1e-3), while "vmap" agrees exactly.
    """
    batch: Optional[FleetBatch] = None
    if isinstance(fleet, FleetBatch):
        batch, prob = fleet, fleet.problem
    elif isinstance(fleet, AllocationProblem):
        prob = fleet
    else:
        batch = stack_problems(list(fleet))
        prob = batch.problem
    cfg = cfg or SolverConfig()
    # the kernel is the default exactly where it compiles (TPU); elsewhere
    # it would run in the Pallas interpreter, so vmap is the default there
    interpret = resolve_interpret(interpret)
    if hot_loop is None:
        hot_loop = "vmap" if resolve_interpret() else "kernel"
    assert hot_loop in ("vmap", "kernel", "ref"), hot_loop
    if starts is None:
        if batch is not None:
            # per-tenant starts at TRUE shapes: invariant to how the fleet
            # is padded/bucketed, and identical to the starts a sequential
            # multistart_solve on the original problem would draw
            starts = make_fleet_starts(batch, n_starts, seed)
        else:
            starts = jax.vmap(lambda pb: make_starts(pb, n_starts, seed))(prob)
    return _solve_fleet_impl(prob, jnp.asarray(starts), cfg, hot_loop,
                             interpret)


def make_fleet_starts(batch: FleetBatch, n_starts: int,
                      seed: int = 0) -> jnp.ndarray:
    """(B, S, n_max) start points, drawn PER TENANT at its true shape.

    ``core.multistart.make_starts`` shapes its random-start scaling by the
    problem dimensions, so drawing on the padded batch would make start
    points (hence solve results) depend on the fleet's padding. Drawing each
    tenant at its true (n, m, p) and zero-embedding keeps solve_fleet results
    independent of batch composition — bucketed and globally-padded stacking
    see literally the same starts, as does a sequential per-tenant loop."""
    out = np.zeros((batch.B, n_starts, batch.n_max), np.float32)
    for b in range(batch.B):
        pb = tenant_problem(batch, b)
        out[b, :, : int(batch.n_true[b])] = np.asarray(
            make_starts(pb, n_starts, seed))
    return jnp.asarray(out)


def solve_fleet_bucketed(
    problems: Sequence[AllocationProblem],
    n_starts: int = 4,
    seed: int = 0,
    cfg: Optional[SolverConfig] = None,
    hot_loop: Optional[str] = None,
    interpret: Optional[bool] = None,
    bucketed: Optional[BucketedFleet] = None,
) -> FleetSolveResult:
    """solve_fleet with shape-bucketed stacking (padding-waste reduction).

    Groups the ragged fleet into power-of-two shape buckets
    (:func:`repro.fleet.batching.bucket_problems`), runs one batched solve
    per bucket, and scatters results back into the ORIGINAL tenant order.
    Returns a FleetSolveResult padded to the global n_max, so callers can
    treat it exactly like an unbucketed ``solve_fleet`` result.

    Because start points are drawn per tenant at true shape
    (:func:`make_fleet_starts`), per-tenant results match unbucketed
    stacking to solver tolerance — and the rounded integer objectives are
    identical in practice on CPU. ``bucketed`` lets callers reuse a
    precomputed bucket layout (the replay engine re-stacks every tick but
    buckets only once)."""
    problems = list(problems)
    if bucketed is None:
        bucketed = bucket_problems(problems)
    n_max = max(int(pb.n) for pb in problems)
    results = [solve_fleet(b, n_starts=n_starts, seed=seed, cfg=cfg,
                           hot_loop=hot_loop, interpret=interpret)
               for b in bucketed.batches]

    def to_n_max(a: np.ndarray, is_solution: bool) -> np.ndarray:
        """Align a bucket's last axis to the global true n_max. Bucket pads
        are powers of two, so they may exceed n_max (truncate: solution
        columns past every member's true n are pinned-zero padding) or fall
        short of it (zero-pad up)."""
        a = np.asarray(a)
        if not is_solution or a.shape[-1] == n_max:
            return a
        if a.shape[-1] > n_max:
            return a[..., :n_max]
        pad = [(0, 0)] * (a.ndim - 1) + [(0, n_max - a.shape[-1])]
        return np.pad(a, pad)

    def gather(field: str, is_solution: bool = False) -> jnp.ndarray:
        rows = [list(to_n_max(getattr(r, field), is_solution))
                for r in results]
        return jnp.asarray(np.stack(scatter_from_buckets(bucketed, rows)))

    return FleetSolveResult(
        x=gather("x", is_solution=True), fun=gather("fun"),
        x_int=gather("x_int", is_solution=True), fun_int=gather("fun_int"),
        feasible=gather("feasible"), used_barrier=gather("used_barrier"),
        all_fun=gather("all_fun"),
        iters=jnp.asarray(sum(int(r.iters) for r in results)),
        x_int_all=gather("x_int_all", is_solution=True),
        fun_int_all=gather("fun_int_all"),
        feas_int_all=gather("feas_int_all"))


# ---------------------------------------------------------------------------
# batched incremental tick (the replay engine's warm-started per-tick solve)
# ---------------------------------------------------------------------------


class FleetStepResult(NamedTuple):
    """One batched incremental tick over the whole fleet.

    ``trace`` is None unless the tick ran with ``capture_trace=True``, in
    which case it is a batched ``core.pgd.PGDTrace`` whose leaves carry a
    leading (B,) lane axis — per-lane convergence rows, fixed-size
    ``steps`` long (see ``repro.obs.solver_trace``)."""

    x: jnp.ndarray         # (B, n) relaxed incremental solution
    x_int: jnp.ndarray     # (B, n) rounded allocation actually deployed
    fun_int: jnp.ndarray   # (B,) objective at x_int
    feasible: jnp.ndarray  # (B,) integer-solution feasibility
    iters: jnp.ndarray     # (B,) adaptive-PGD iterations per lane
    trace: Optional[PGDTrace] = None  # (B, steps) per-lane convergence rows
    deadline_hit: Optional[bool] = None  # anytime tick truncated (None: n/a)
    anytime: Optional[AnytimeReport] = None  # the anytime drive's report


@partial(jax.jit, static_argnames=("steps",))
def _step_fleet_impl(prob: AllocationProblem, x_current: jnp.ndarray,
                     delta_max: jnp.ndarray, x_init: jnp.ndarray,
                     active: jnp.ndarray, steps: int) -> FleetStepResult:
    x_rel, iters = jax.vmap(
        lambda pb, xc, dm, xi: solve_incremental_info(pb, xc, dm, x_init=xi,
                                                      steps=steps)
    )(prob, x_current, delta_max, x_init)
    x_int = jax.vmap(round_and_polish)(prob, x_rel)
    # frozen lanes (active=False) keep their warm start as the answer; the
    # mask is a traced array, so ragged fleets reuse one compiled program
    x_rel = jnp.where(active[:, None], x_rel, x_current)
    x_int = jnp.where(active[:, None], x_int, x_current)
    f_int = jax.vmap(objective)(prob, x_int)
    feas = jax.vmap(lambda pb, xi: is_feasible(pb, xi, 1e-3))(prob, x_int)
    return FleetStepResult(x=x_rel, x_int=x_int, fun_int=f_int, feasible=feas,
                           iters=jnp.where(active, iters, 0))


@partial(jax.jit, static_argnames=("steps",))
def _step_fleet_traced_impl(prob: AllocationProblem, x_current: jnp.ndarray,
                            delta_max: jnp.ndarray, x_init: jnp.ndarray,
                            active: jnp.ndarray, steps: int
                            ) -> FleetStepResult:
    """Traced twin of ``_step_fleet_impl``: same solves, plus per-lane
    PGDTrace capture (the trace is extra while_loop state, not extra math,
    so ``(x, x_int, iters)`` match the untraced program)."""
    x_rel, iters, trace = jax.vmap(
        lambda pb, xc, dm, xi: solve_incremental_info(
            pb, xc, dm, x_init=xi, steps=steps, capture_trace=True)
    )(prob, x_current, delta_max, x_init)
    x_int = jax.vmap(round_and_polish)(prob, x_rel)
    x_rel = jnp.where(active[:, None], x_rel, x_current)
    x_int = jnp.where(active[:, None], x_int, x_current)
    f_int = jax.vmap(objective)(prob, x_int)
    feas = jax.vmap(lambda pb, xi: is_feasible(pb, xi, 1e-3))(prob, x_int)
    return FleetStepResult(x=x_rel, x_int=x_int, fun_int=f_int, feasible=feas,
                           iters=jnp.where(active, iters, 0), trace=trace)


@partial(jax.jit, static_argnames=("cfg",))
def _step_fleet_anytime_init_impl(prob, x_current, delta_max, x_init,
                                  cfg: PGDConfig):
    """Vmapped chunk-state init: every lane's projected warm start plus the
    best-so-far trackers, stacked on a leading (B,) axis."""
    return jax.vmap(
        lambda pb, xc, dm, xi: incremental_anytime_init(pb, xc, dm, xi, cfg)
    )(prob, x_current, delta_max, x_init)


@partial(jax.jit, static_argnames=("cfg",))
def _step_fleet_anytime_chunk_impl(prob, x_current, delta_max, state, it_end,
                                   cfg: PGDConfig):
    """Advance every lane to the traced iteration cap ``it_end`` (closed
    over, so it broadcasts across the vmap). Per-lane op structure is the
    sequential chunk's — converged lanes freeze in place."""
    return jax.vmap(
        lambda pb, xc, dm, s: incremental_anytime_chunk(pb, xc, dm, s,
                                                        it_end, cfg)
    )(prob, x_current, delta_max, state)


@jax.jit
def _step_fleet_anytime_finalize_impl(prob, x_rel, x_current, active, iters):
    """The untruncated tick's tail — rounding, frozen-lane masking,
    objective and feasibility — applied to the anytime best-so-far
    iterates."""
    x_int = jax.vmap(round_and_polish)(prob, x_rel)
    x_rel = jnp.where(active[:, None], x_rel, x_current)
    x_int = jnp.where(active[:, None], x_int, x_current)
    f_int = jax.vmap(objective)(prob, x_int)
    feas = jax.vmap(lambda pb, xi: is_feasible(pb, xi, 1e-3))(prob, x_int)
    return FleetStepResult(x=x_rel, x_int=x_int, fun_int=f_int, feasible=feas,
                           iters=jnp.where(active, iters, 0))


def solve_fleet_step(
    fleet: Union[FleetBatch, AllocationProblem],
    x_current: jnp.ndarray,
    delta_max: Union[float, jnp.ndarray],
    x_init: Optional[jnp.ndarray] = None,
    steps: int = 600,
    active: Optional[np.ndarray] = None,
    capture_trace: bool = False,
    anytime: Optional[AnytimeConfig] = None,
) -> FleetStepResult:
    """One incremental-adoption tick for EVERY tenant in one jitted program.

    The fleet analogue of ``InfrastructureOptimizationController``'s warm
    tick: per tenant, PGD on the objective constrained to the L1 churn ball
    ``||x - x_current||_1 <= delta_max`` (``core.incremental``), then greedy
    rounding — all under one vmap, so a T-tick replay issues T device
    programs instead of T*B.

    ``x_current`` is the (B, n) previous-tick allocation (also the warm
    start); ``x_init`` optionally overrides the warm start, e.g. with the
    previous tick's RELAXED batched solution. ``delta_max`` may be scalar or
    per-tenant (B,). vmap preserves per-lane op structure, so each lane
    matches a sequential ``solve_incremental`` + ``round_and_polish`` call
    on the same padded problem.

    ``active`` is the (B,) ragged-horizon liveness mask: frozen lanes
    (``active[b] == False`` — the tenant's trace has expired) are returned
    with ``x == x_int == x_current`` instead of a fresh solution, so their
    rows carry the last allocation forward unchanged. Defaults to the
    batch's own ``FleetBatch.active`` mask, else all-live. Live lanes are
    unaffected — vmap keeps lanes independent, so results on live tenants
    are identical whether or not frozen rows share the batch.

    ``capture_trace=True`` additionally returns per-lane PGD convergence
    rows in ``FleetStepResult.trace`` (a separately-compiled program whose
    solves agree with the untraced one — test-enforced).

    An *enabled* ``anytime`` config (``core.pgd.AnytimeConfig`` with
    ``deadline_ms`` set) runs the tick chunked against the injectable
    clock and returns each lane's best-so-far feasible iterate when the
    fleet-wide budget expires, with ``FleetStepResult.deadline_hit``
    reporting the truncation and ``FleetStepResult.anytime`` the whole
    :class:`repro.core.pgd.AnytimeReport`; a disabled/absent config takes
    the exact pre-anytime program (Python-level branch — bit-identical
    results). While telemetry records, the anytime branch is two spans:
    ``fleet/anytime`` (the chunked drive) and ``fleet/round`` (rounding,
    masking and scoring, fenced on ``x_int``)."""
    prob = fleet.problem if isinstance(fleet, FleetBatch) else fleet
    if active is None and isinstance(fleet, FleetBatch):
        active = fleet.active_mask
    B = prob.c.shape[0]
    x_current = jnp.asarray(x_current, jnp.float32)
    delta_max = jnp.broadcast_to(jnp.asarray(delta_max, jnp.float32), (B,))
    x_init = x_current if x_init is None else jnp.asarray(x_init, jnp.float32)
    active = (jnp.ones(B, bool) if active is None
              else jnp.asarray(np.asarray(active, bool)))
    if anytime is not None and anytime.enabled:
        if capture_trace:
            raise ValueError("anytime deadlines and capture_trace are "
                             "mutually exclusive; drop one")
        cfg = PGDConfig(max_iters=int(steps))
        # run_anytime ends on a host sync of the last chunk (its done-check
        # or its final fence), so the span needs no fence of its own
        with span("fleet/anytime", cat="fleet"):
            state, report = run_anytime(
                lambda: _step_fleet_anytime_init_impl(prob, x_current,
                                                      delta_max, x_init, cfg),
                lambda s, e: _step_fleet_anytime_chunk_impl(
                    prob, x_current, delta_max, s, e, cfg),
                cfg, anytime)
        with span("fleet/round", cat="fleet") as sp:
            res = _step_fleet_anytime_finalize_impl(prob, state.x_best,
                                                    x_current, active,
                                                    state.it)
            sp.fence(res.x_int)
        return res._replace(deadline_hit=report.deadline_hit, anytime=report)
    impl = _step_fleet_traced_impl if capture_trace else _step_fleet_impl
    return impl(prob, x_current, delta_max, x_init, active, int(steps))
