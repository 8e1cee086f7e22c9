"""Shared projected-gradient engine: Barzilai-Borwein step + Armijo ladder.

Every inner solve loop in this codebase is the same algorithm — propose a
Barzilai-Borwein (BB1) step, safeguard it with an Armijo backtracking ladder
evaluated as one batch (vmap-friendly: no data-dependent trip counts inside
an iteration), accept the largest decreasing candidate, stop when the
projected move is tiny. This module is that loop, extracted once and
parameterized by ``(value_fn, grad_fn, project_fn, config)`` so the three
consumers share a single implementation:

* ``core.solver._pgd``            — the barrier/penalty relaxation solver
  (merit = eq.(1) objective + log-barrier or quadratic penalty).
* ``core.incremental.solve_incremental`` — the controller's warm tick
  (merit = eq.(1) objective; projection = box ∩ L1 churn ball), which the
  batched fleet engine ``solve_fleet_step`` vmaps across tenant lanes.
* ``repro.horizon.solver``        — the time-expanded MPC program (merit =
  per-tick objectives + churn coupling + soft churn bound + planned-tick
  band penalty; projection = exact ``project_incremental`` chaining on the
  committed tick, box on planned rows).

The engine is jit- and vmap-safe: the iterate may have ANY shape (``(n,)``
for a single tick, ``(H, n)`` for a plan), all inner products flatten over
every axis, and the loop is a ``lax.while_loop`` whose batching rule freezes
finished lanes in place — so a vmapped call's per-lane trajectory is
identical to a sequential call on the same data (the property every
batched ≡ sequential equivalence test in this repo leans on).
"""
from __future__ import annotations

import time
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class PGDConfig(NamedTuple):
    """Hashable knobs of the shared BB/Armijo engine (static under jit).

    ``max_iters`` bounds the iteration count; the loop stops earlier when an
    accepted step moves no coordinate by more than ``tol`` (or when the
    ladder collapses without finding a decreasing candidate). ``step0`` is
    both the initial BB step and the reset value when the BB denominator
    degenerates; the ladder evaluates ``n_backtracks`` candidates at ratios
    ``backtrack ** (-1 .. n_backtracks-2)`` of the proposed step (one
    upscale, like ``core.solver``); ``armijo_c`` is the sufficient-decrease
    slope on the PROJECTED step."""

    max_iters: int = 600           # iteration budget (early-stops on tol)
    step0: float = 1.0             # initial / fallback BB step
    n_backtracks: int = 12         # Armijo ladder length
    backtrack: float = 0.5         # ladder ratio
    armijo_c: float = 1e-4         # sufficient-decrease constant
    tol: float = 1e-6              # stop when the accepted move is tiny
    ftol: float = 1e-4             # an accepted step whose RELATIVE merit
                                   # progress falls below this counts as
                                   # "flat" ...
    max_flat: int = 10             # ... and max_flat CONSECUTIVE flat steps
                                   # stop the loop (progress has stalled at
                                   # ~ftol/iter; one flat step alone is NOT
                                   # convergence — BB progress comes in
                                   # bursts separated by plateaus). The
                                   # default trades the merit's last ~0.1%
                                   # for a fraction of the iterations; pass
                                   # ftol=0.0 to only stop on true cycling
                                   # (the high-accuracy barrier-solver mode)


def _flat_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """<a, b> over every axis (iterates may be (n,) or (H, n)).

    Elementwise multiply + reduce rather than ``jnp.vdot``: a vmapped dot
    lowers to a batched ``dot_general`` whose accumulation order differs
    from the unbatched kernel's in the last ulps, and the adaptive line
    search amplifies ulps into different accept/reject decisions — which
    would break the bit-exact batched ≡ sequential trajectory equivalence
    the fleet engines promise. A plain reduce batches order-preservingly."""
    return jnp.sum(a * b)


class PGDTrace(NamedTuple):
    """Per-iteration convergence capture of one :func:`pgd_minimize_traced`
    run — FIXED-SIZE ``(cfg.max_iters,)`` arrays (static shape), so the
    traced engine stays jit- and vmap-safe: a vmapped traced solve returns
    ``(B, max_iters)`` leaves, one full trace per lane. Rows at indices
    ``>= iters`` were never written: ``merit``/``step``/``move`` hold NaN,
    ``accepted`` False and ``rung`` -1 there (the validity sentinel —
    consumers slice ``[:iters]``).

    Fields, one row per iteration actually taken:

    * ``merit``    — merit value AFTER the iteration (the accepted
      candidate's value; unchanged from the previous iterate on a rejected
      ladder). ``merit[iters-1]`` equals the ``fx`` the engine returns.
    * ``step``     — the BB base step proposed at iteration start (the
      ladder evaluates ``step * backtrack**(-1..n_backtracks-2)``).
    * ``accepted`` — whether any ladder rung satisfied Armijo decrease.
    * ``rung``     — index of the accepted ladder rung (0 = the upscaled
      candidate, larger = more backtracking; -1 when the whole ladder was
      rejected).
    * ``move``     — max-abs coordinate move of the step (0 on rejection).
    """

    merit: jnp.ndarray      # (L,) float32 merit after each iteration
    step: jnp.ndarray       # (L,) float32 proposed BB base step
    accepted: jnp.ndarray   # (L,) bool   Armijo ladder found a candidate
    rung: jnp.ndarray       # (L,) int32  accepted ladder index (-1: none)
    move: jnp.ndarray       # (L,) float32 max|dx| of the accepted step


def _empty_trace(L: int) -> PGDTrace:
    return PGDTrace(merit=jnp.full((L,), jnp.nan, jnp.float32),
                    step=jnp.full((L,), jnp.nan, jnp.float32),
                    accepted=jnp.zeros((L,), bool),
                    rung=jnp.full((L,), -1, jnp.int32),
                    move=jnp.full((L,), jnp.nan, jnp.float32))


def _pgd_iteration(value_fn, grad_fn, project_fn, cfg, ratios,
                   x, fx, g, bb, it, flat):
    """One BB/Armijo iteration — the exact op sequence of the monolithic
    loop body, shared by :func:`_pgd_minimize_impl` and the chunked anytime
    loop so a chunked trajectory is bit-identical to the monolithic one.

    Returns ``(x_new, f_new, g_new, bb_new, it_new, flat_new, done,
    any_ok, idx, move)`` — the first seven are the loop-carried solver
    state, the last three feed the optional trace row."""
    steps = bb * ratios
    cands = jax.vmap(
        lambda s: project_fn(x - s * g))(steps)            # (L, *x.shape)
    fcands = jax.vmap(value_fn)(cands)                     # (L,)
    # Armijo on the projected step: F(x+) <= F(x) + c * <g, x+ - x>
    diff = cands - x[None]
    dec = fcands - (fx + cfg.armijo_c *
                    jnp.sum(diff * g[None],
                            axis=tuple(range(1, diff.ndim))))
    ok = (dec <= 0.0) & jnp.isfinite(fcands)
    idx = jnp.argmax(ok)          # first (largest) accepting step
    any_ok = jnp.any(ok)
    x_new = jnp.where(any_ok, cands[idx], x)
    f_new = jnp.where(any_ok, fcands[idx], fx)
    g_new = grad_fn(x_new)
    # BB1 step from the accepted move (safeguarded into [1e-8, 1e4])
    dx = x_new - x
    dg = g_new - g
    denom = _flat_dot(dx, dg)
    bb_new = jnp.where(jnp.abs(denom) > 1e-12,
                       jnp.abs(_flat_dot(dx, dx) / denom), cfg.step0)
    bb_new = jnp.clip(bb_new, 1e-8, 1e4)
    bb_new = jnp.where(any_ok, bb_new,
                       bb * cfg.backtrack ** cfg.n_backtracks)
    move = jnp.max(jnp.abs(dx))
    # converged when an ACCEPTED step barely moves, or when max_flat
    # CONSECUTIVE accepted steps barely improved the merit (boundary
    # cycling: the alternating projection keeps the iterate drifting
    # along a flat ridge). One flat step alone never stops the loop —
    # BB progress comes in bursts separated by plateaus.
    is_flat = any_ok & (f_new >= fx - cfg.ftol * (1.0 + jnp.abs(fx)))
    flat_new = jnp.where(is_flat, flat + 1, jnp.where(any_ok, 0, flat))
    done = ((~any_ok) & (bb < 1e-7)) | (any_ok & (move < cfg.tol)) \
        | (flat_new >= cfg.max_flat)
    return x_new, f_new, g_new, bb_new, it + 1, flat_new, done, \
        any_ok, idx, move


def _pgd_minimize_impl(
    value_fn: Callable[[jnp.ndarray], jnp.ndarray],
    grad_fn: Callable[[jnp.ndarray], jnp.ndarray],
    project_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x0: jnp.ndarray,
    cfg: PGDConfig,
    trace: bool,
):
    """The one BB/Armijo loop, with optional per-iteration trace capture.

    ``trace`` is a PYTHON-level flag resolved at trace time: with
    ``trace=False`` the loop-carried state (hence the compiled program) is
    exactly the pre-trace engine's — the bit-exactness guarantees of every
    batched ≡ sequential test are untouched. With ``trace=True`` the state
    additionally carries a :class:`PGDTrace` written at index ``it`` each
    iteration; the iterate computation itself is THE SAME ops either way,
    so the traced run's ``(x, fx, iters)`` matches the untraced run's."""
    ratios = cfg.backtrack ** jnp.arange(-1, cfg.n_backtracks - 1)  # 1 upscale

    def cond(state):
        x, fx, g, bb, it, flat, done = state[:7]
        return (~done) & (it < cfg.max_iters)

    def body(state):
        x, fx, g, bb, it, flat = state[:6]
        (x_new, f_new, g_new, bb_new, it_new, flat_new, done,
         any_ok, idx, move) = _pgd_iteration(
            value_fn, grad_fn, project_fn, cfg, ratios,
            x, fx, g, bb, it, flat)
        out = (x_new, f_new, g_new, bb_new, it_new, flat_new, done)
        if trace:
            tr: PGDTrace = state[7]
            tr = PGDTrace(
                merit=tr.merit.at[it].set(f_new.astype(jnp.float32)),
                step=tr.step.at[it].set(bb.astype(jnp.float32)),
                accepted=tr.accepted.at[it].set(any_ok),
                rung=tr.rung.at[it].set(
                    jnp.where(any_ok, idx, -1).astype(jnp.int32)),
                move=tr.move.at[it].set(
                    jnp.where(any_ok, move, 0.0).astype(jnp.float32)))
            return out + (tr,)
        return out

    x0 = project_fn(x0)
    state = (x0, value_fn(x0), grad_fn(x0), jnp.asarray(cfg.step0),
             jnp.asarray(0), jnp.asarray(0), jnp.asarray(False))
    if trace:
        state = state + (_empty_trace(cfg.max_iters),)
    final = jax.lax.while_loop(cond, body, state)
    x, fx, it = final[0], final[1], final[4]
    return x, fx, it, (final[7] if trace else None)


def pgd_minimize(
    value_fn: Callable[[jnp.ndarray], jnp.ndarray],
    grad_fn: Callable[[jnp.ndarray], jnp.ndarray],
    project_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x0: jnp.ndarray,
    cfg: PGDConfig = PGDConfig(),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Minimize ``value_fn`` over the set ``project_fn`` projects onto.

    Per iteration: propose ``bb * ratios`` candidate steps, project each
    (``x - s * g``), evaluate all candidate VALUES as one vmapped batch,
    accept the first (largest) candidate satisfying Armijo sufficient
    decrease on the projected step, then refresh the BB1 step from the
    accepted move. No candidate accepted -> shrink the proposal and retry;
    converged (move < tol) or ladder exhausted -> stop.

    Returns ``(x, value, iters)`` where ``iters`` is the number of
    iterations actually taken (the early-stopping wins the benchmarks
    report). The iterate shape is whatever ``x0`` has; ``value_fn`` must map
    it to a scalar and ``grad_fn``/``project_fn`` to its own shape. Use
    :func:`pgd_minimize_traced` to also capture the per-iteration
    convergence trace."""
    x, fx, it, _ = _pgd_minimize_impl(value_fn, grad_fn, project_fn, x0, cfg,
                                      trace=False)
    return x, fx, it


def pgd_minimize_traced(
    value_fn: Callable[[jnp.ndarray], jnp.ndarray],
    grad_fn: Callable[[jnp.ndarray], jnp.ndarray],
    project_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x0: jnp.ndarray,
    cfg: PGDConfig = PGDConfig(),
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, PGDTrace]:
    """:func:`pgd_minimize` with per-iteration convergence capture.

    Returns ``(x, value, iters, trace)`` where ``trace`` is a
    :class:`PGDTrace` of fixed-size ``(cfg.max_iters,)`` arrays — the
    fixed size keeps the capture jit/vmap-safe (vmapping this function
    yields ``(B, max_iters)`` per-lane traces). The iterate math is the
    SAME op sequence as the untraced engine (the trace arrays are extra
    loop state, not extra math), so ``(x, value, iters)`` match a plain
    ``pgd_minimize`` call; ``trace.merit[iters-1] == value`` whenever at
    least one iteration ran. See ``repro.obs.solver_trace`` for analysis
    helpers (validity slicing, per-lane extraction, summaries)."""
    x, fx, it, tr = _pgd_minimize_impl(value_fn, grad_fn, project_fn, x0, cfg,
                                       trace=True)
    return x, fx, it, tr


class AnytimeConfig(NamedTuple):
    """Host-side knobs of the chunked-budget *anytime* mode.

    The anytime driver runs the engine in ``chunk_iters``-iteration chunks
    (each chunk one jitted ``while_loop`` call with a TRACED iteration cap,
    so every chunk reuses one compiled program) and checks ``clock``
    between chunks: once ``deadline_ms`` wall milliseconds have elapsed it
    stops and the caller returns the best-so-far iterate *by merit*, not
    the last iterate. ``clock`` is injectable (seconds, monotonic;
    ``time.perf_counter`` by default) so tests and the degradation bench
    can drive deterministic fake time — it is only ever called host-side,
    never under jit.

    ``deadline_ms=None`` means "no budget": every consumer branches on it
    at PYTHON level and takes its pre-anytime untruncated path, so the
    compiled graph — and therefore the allocations, bit for bit — are
    exactly the non-anytime engine's (test-enforced)."""

    deadline_ms: Optional[float] = None   # wall budget; None = disabled
    chunk_iters: int = 32                 # iterations per clock check
    clock: Callable[[], float] = time.perf_counter   # injectable, host-only

    @property
    def enabled(self) -> bool:
        """Whether this config actually enforces a budget (``deadline_ms``
        is set). Consumers branch on this at Python level."""
        return self.deadline_ms is not None


class AnytimeReport(NamedTuple):
    """Host-side outcome of one :func:`run_anytime` drive.

    ``deadline_hit`` is True iff the clock expired while iterations
    remained (the returned iterate was truncated); a solve that converges
    or exhausts ``max_iters`` inside the budget reports False. ``chunks``
    counts chunk launches (0 when the budget was spent before the first
    chunk — the caller then holds the projected, feasible warm start).
    ``budget_ms`` is the ``deadline_ms`` the drive was given and
    ``max_step_ms`` the longest interval between two consecutive clock
    reads — the fenced init or one fenced chunk — so ``elapsed_ms`` never
    exceeds ``budget_ms + max_step_ms``: the budget plus one chunk."""

    deadline_hit: bool
    elapsed_ms: float
    chunks: int
    budget_ms: float
    max_step_ms: float


class PGDChunkState(NamedTuple):
    """Resumable loop-carried state of the chunked anytime engine.

    Fields 0–6 are EXACTLY the monolithic loop's state tuple (same dtypes,
    same update ops via :func:`_pgd_iteration`), plus the best-so-far pair
    ``(x_best, f_best)`` tracked across chunks. ``x_best`` is always a
    PROJECTED (feasible) point: it starts at the projected warm start and
    only ever moves to accepted (projected) iterates with strictly better
    merit. Works unbatched or vmapped (leaves gain a leading lane axis;
    ``done`` becomes a per-lane vector)."""

    x: jnp.ndarray        # current iterate
    fx: jnp.ndarray       # merit at x
    g: jnp.ndarray        # gradient at x
    bb: jnp.ndarray       # BB step
    it: jnp.ndarray       # iterations taken
    flat: jnp.ndarray     # consecutive flat-step counter
    done: jnp.ndarray     # converged / stalled flag
    x_best: jnp.ndarray   # best-merit iterate so far (feasible)
    f_best: jnp.ndarray   # merit at x_best


def pgd_chunk_init(
    value_fn: Callable[[jnp.ndarray], jnp.ndarray],
    grad_fn: Callable[[jnp.ndarray], jnp.ndarray],
    project_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x0: jnp.ndarray,
    cfg: PGDConfig,
) -> PGDChunkState:
    """Build the iteration-0 :class:`PGDChunkState` (projects ``x0`` first,
    exactly like the monolithic loop — so the zero-budget answer is already
    feasible). Jit/vmap-safe; callers wrap it in their own jitted impl."""
    x0 = project_fn(x0)
    fx = value_fn(x0)
    # bb strongly typed, as every chunk returns it: a weak-typed init would
    # make the second chunk call compile the chunk program again
    return PGDChunkState(
        x=x0, fx=fx, g=grad_fn(x0), bb=jnp.asarray(cfg.step0, jnp.float32),
        it=jnp.asarray(0), flat=jnp.asarray(0), done=jnp.asarray(False),
        x_best=x0, f_best=fx)


def pgd_chunk_run(
    value_fn: Callable[[jnp.ndarray], jnp.ndarray],
    grad_fn: Callable[[jnp.ndarray], jnp.ndarray],
    project_fn: Callable[[jnp.ndarray], jnp.ndarray],
    state: PGDChunkState,
    it_end: jnp.ndarray,
    cfg: PGDConfig,
) -> PGDChunkState:
    """Advance the chunked engine until ``it >= it_end`` (a TRACED scalar:
    one compiled program serves every chunk) or convergence. Each
    iteration is :func:`_pgd_iteration` — the monolithic loop's exact op
    sequence — plus the best-so-far merit tracking, so running chunks
    back-to-back reproduces the monolithic trajectory iterate for
    iterate."""
    ratios = cfg.backtrack ** jnp.arange(-1, cfg.n_backtracks - 1)  # 1 upscale
    it_cap = jnp.minimum(it_end, cfg.max_iters)

    def cond(s: PGDChunkState):
        return (~s.done) & (s.it < it_cap)

    def body(s: PGDChunkState):
        (x, fx, g, bb, it, flat, done, _any_ok, _idx, _move) = \
            _pgd_iteration(value_fn, grad_fn, project_fn, cfg, ratios,
                           s.x, s.fx, s.g, s.bb, s.it, s.flat)
        better = fx < s.f_best
        return PGDChunkState(
            x=x, fx=fx, g=g, bb=bb, it=it, flat=flat, done=done,
            x_best=jnp.where(better, x, s.x_best),
            f_best=jnp.where(better, fx, s.f_best))

    return jax.lax.while_loop(cond, body, state)


def run_anytime(init_fn, chunk_fn, cfg: PGDConfig,
                anytime: AnytimeConfig):
    """Drive a chunked solve against the wall clock; the generic host loop
    behind every anytime consumer (incremental, fleet, horizon).

    ``init_fn()`` returns the initial (possibly vmapped) state pytree with
    ``done``/``it``/``x_best``/``f_best`` leaves; ``chunk_fn(state,
    it_end)`` advances it to the traced iteration cap. Between chunks the
    driver syncs ``state.done`` to the host (which fences the previous
    chunk, so the clock reads true elapsed compute) and stops when all
    lanes converged, ``cfg.max_iters`` is reached, or ``deadline_ms``
    expires — whichever first. A non-positive ``deadline_ms`` returns the
    init state untouched: the projected warm start, always feasible.

    The final state is fenced before the last clock read, so
    ``elapsed_ms`` covers every chunk's device time. Returns ``(state,
    AnytimeReport)``."""
    if anytime.deadline_ms is None:
        raise ValueError("run_anytime requires AnytimeConfig.deadline_ms; "
                         "branch to the untruncated engine when it is None")
    clock = anytime.clock
    chunk = max(1, int(anytime.chunk_iters))
    deadline = float(anytime.deadline_ms)
    t0 = t_prev = clock()
    state = init_fn()
    it_end = 0
    deadline_hit = False
    chunks = 0
    max_step = 0.0
    max_iters = int(cfg.max_iters)
    while it_end < max_iters and not bool(np.all(np.asarray(state.done))):
        now = clock()       # the done-sync above fenced the previous step
        max_step, t_prev = max(max_step, now - t_prev), now
        if (now - t0) * 1e3 >= deadline:
            deadline_hit = True
            break
        it_end = min(it_end + chunk, max_iters)
        state = chunk_fn(state, jnp.asarray(it_end))
        chunks += 1
    if not deadline_hit:
        jax.block_until_ready(state)
        now = clock()
        max_step = max(max_step, now - t_prev)
    return state, AnytimeReport(deadline_hit=deadline_hit,
                                elapsed_ms=(now - t0) * 1e3, chunks=chunks,
                                budget_ms=deadline, max_step_ms=max_step * 1e3)
