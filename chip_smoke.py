#!/usr/bin/env python
"""Bring-up smoke test: the allocator's main path on one TPU chip.

    python chip_smoke.py

Runs from the repo root in ONE process (it starts no child process) on the
full cloud catalog (n = 1,880 instance types, m = 4 resources, p = 2
providers) and the lane counts operators run. Phases, in order:

0. device — turn on the persistent compile cache (``repro.compile_cache``:
   ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``) and
   require a TPU as the first JAX device. There is no CPU fallback.
1. kernel — the compiled ``alloc_objective`` fleet kernel (the per-iterate
   gradient call of ``solve_fleet``'s kernel hot loop, 256 tenants x 4
   points) against its einsum oracle; the compiled HLO must hold the
   kernel (``tpu_custom_call``).
2. solve — ``solve_fleet`` on the same 256 tenants with the TPU default hot
   loop (the kernel), ``hot_loop="ref"`` and ``hot_loop="vmap"`` from the
   same starts: every integer allocation covers its demand (numpy float64,
   as ``repro.core.metrics`` checks); the kernel agrees with both per
   tenant and on the fleet aggregate.
3. replay — ``replay_fleet`` batched vs the sequential reference engine on
   16 tenants (diurnal and flash-crowd traces, 8 ticks, myopic): cost
   integrals agree and no committed allocation leaves demand uncovered.
4. serve — ``ServeEngine`` with 256 lanes and a 50 ms tick budget, filled
   with 256 tenants, then warm ticks of flash-crowd demand from every
   tenant: every decision is feasible; then the same 50 ms straight to
   ``solve_fleet_step`` at 256 lanes, which must run fenced chunks. Every
   anytime report's ``elapsed_ms`` is within its budget plus one chunk.

Every check prints its numbers; a phase with a failed check exits non-zero
before the next phase starts. Each phase prints its wall time split into
compile time (tracing, lowering, XLA compile or persistent-cache reads) and
run time. The last line of stdout is the verdict
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import jax
import numpy as np

N_TENANTS = 256          # fleet width of phases 1, 2 and serve lanes
N_STARTS = 4             # multistart width: T of the per-iterate kernel call
REPLAY_TENANTS = 16
REPLAY_TICKS = 8
SERVE_DEADLINE_MS = 50.0
SERVE_WARMUP_TICKS = 2   # compile the anytime init/finalize, then chunk
SERVE_STEADY_TICKS = 5   # warm ticks that must compile nothing
# base demand per resource (cpu, mem_gb, net_units, storage_gb), as the serve
# demo draws it; tenants scale it log-uniformly from 1x to 50x
BASE_DEMAND = np.array([8.0, 16.0, 4.0, 100.0])

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Collects the host intervals JAX spends tracing, lowering and
    compiling (or reading the persistent cache), through
    ``jax.monitoring``. Nested events (an inner jit traced inside an outer
    one) overlap, so time is the length of the union of the intervals."""

    def __init__(self):
        self.intervals = []
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()
            self.intervals.append((end - secs, end))
            if event == _COMPILE_EVENTS[-1]:
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds_since(self, t0: float) -> float:
        """Length of the union of compile intervals after ``t0``."""
        total, reach = 0.0, t0
        for lo, hi in sorted(self.intervals):
            lo = max(lo, reach)
            if hi > lo:
                total += hi - lo
                reach = hi
        return total


@contextmanager
def phase(name: str, clock: CompileClock):
    """Print the phase's wall time split into compile and run."""
    print(f"== phase {name}", flush=True)
    t0 = time.perf_counter()
    n0, h0 = clock.compiles, clock.cache_hits
    yield
    wall = time.perf_counter() - t0
    comp = clock.seconds_since(t0)
    print(f"phase {name}: wall {wall:.3f} s = compile {comp:.3f} s + run "
          f"{wall - comp:.3f} s ({clock.compiles - n0} programs compiled, "
          f"{clock.cache_hits - h0} read from the persistent cache)",
          flush=True)


class Checks:
    """A phase's checks: each prints its verdict; :meth:`done` exits
    non-zero when any failed, so the next phase never starts."""

    def __init__(self, phase_name: str):
        self.phase_name = phase_name
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  check {'ok' if ok else 'FAILED'}: {what}", flush=True)
        if not ok:
            self.failed.append(what)

    def done(self) -> None:
        if self.failed:
            raise SystemExit(f"chip_smoke: phase {self.phase_name} failed: "
                             + "; ".join(self.failed))


def require_tpu():
    """Phase 0's device check: the first JAX device must be a TPU. Exits
    non-zero naming the platform found otherwise — no CPU fallback."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is platform {dev.platform!r} ({dev.device_kind})")
    return dev


def tenant_demands(n: int, seed: int) -> np.ndarray:
    """(n, 4) raw demand vectors: BASE_DEMAND scaled log-uniformly from 1x
    to 50x per tenant and jittered +-50% per resource."""
    rng = np.random.default_rng(seed)
    size = np.exp(rng.uniform(0.0, np.log(50.0), n))
    return BASE_DEMAND * size[:, None] * rng.uniform(0.5, 1.5, (n, 4))


def coverage_slack(catalog, X: np.ndarray, demands: np.ndarray) -> np.ndarray:
    """Per-tenant min over resources of provided - demand, raw units, float64
    (``repro.core.metrics.evaluate`` counts a tenant satisfied when this
    is >= -1e-6)."""
    K = catalog.matrices()[0].astype(np.float64)
    provided = np.asarray(X, np.float64)[:, :K.shape[1]] @ K.T
    return np.min(provided - demands, axis=1)


def rel_err(a, b) -> float:
    """max |a - b| over max |b|, over the whole array."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def build_fleet(catalog, n_tenants: int, seed: int = 0):
    """Stacked batch of ``n_tenants`` problems on ``catalog`` plus their
    raw demands and the (B, N_STARTS, n) multistart points."""
    from repro.core.api import problem_from_demand
    from repro.fleet.batching import stack_problems
    from repro.fleet.solver import make_fleet_starts

    demands = tenant_demands(n_tenants, seed)
    batch = stack_problems([problem_from_demand(catalog, d) for d in demands])
    starts = make_fleet_starts(batch, N_STARTS, seed=0)
    return batch, demands, starts


def phase_kernel(batch, starts, interpret: bool = False) -> dict:
    """Phase 1: the fleet kernel against its einsum oracle."""
    from repro.kernels.alloc_objective.ops import fleet_value_and_grad

    check = Checks("kernel")
    prob = batch.problem
    kernel = fleet_value_and_grad.lower(prob, starts, use_kernel=True,
                                        interpret=interpret).compile()
    if not interpret:
        check("tpu_custom_call" in kernel.as_text(),
              "compiled HLO holds the Pallas kernel (tpu_custom_call)")
    f_k, g_k = jax.block_until_ready(kernel(prob, starts))
    f_o, g_o = jax.block_until_ready(
        fleet_value_and_grad(prob, starts, use_kernel=False))
    ef, eg = rel_err(f_k, f_o), rel_err(g_k, g_o)
    print(f"  kernel vs oracle at B={starts.shape[0]}, T={starts.shape[1]}, "
          f"n={starts.shape[2]}: max rel err f {ef:.3e}, g {eg:.3e}")
    check(bool(np.all(np.isfinite(f_k)) and np.all(np.isfinite(g_k))),
          "kernel outputs are finite")
    check(ef < 1e-4 and eg < 1e-4, "kernel agrees with the oracle (< 1e-4)")
    check.done()
    return {"err_f": ef, "err_g": eg}


def phase_solve(catalog, batch, demands, starts) -> dict:
    """Phase 2: the default cold fleet solve (the kernel hot loop on TPU)
    against the einsum-oracle hot loop (``ref``: same algorithm, only the
    objective evaluation differs) and against ``vmap`` (the adaptive
    per-lane solver), per tenant and on the fleet aggregate."""
    from repro.fleet.solver import solve_fleet
    from repro.kernels import resolve_interpret

    check = Checks("solve")
    default = "vmap" if resolve_interpret() else "kernel"
    others = [name for name in ("ref", "vmap") if name != default]
    out = {}
    for name, hot_loop in [(default, None)] + [(o, o) for o in others]:
        t0 = time.perf_counter()
        res = jax.block_until_ready(
            solve_fleet(batch, starts=starts, hot_loop=hot_loop))
        X = np.asarray(res.x_int, np.float64)
        slack = coverage_slack(catalog, X, demands)
        out[name] = np.asarray(res.fun_int, np.float64)
        print(f"  solve_fleet hot_loop={name}: {time.perf_counter() - t0:.3f}"
              f" s, {int(res.iters)} PGD iterations, solver-feasible "
              f"{int(np.sum(np.asarray(res.feasible)))}/{len(X)}, worst "
              f"coverage slack {slack.min():.6g}")
        check(bool(np.all(X == np.round(X))), f"{name}: x_int is integral")
        check(bool(np.all(slack >= -1e-6)),
              f"{name}: every x_int covers its demand "
              f"({int(np.sum(slack < -1e-6))} uncovered)")
    result = {}
    for other in others:
        spread = np.abs(out[default] - out[other]) / np.abs(out[other])
        per = float(spread.max())
        agg = float(abs(out[default].sum() - out[other].sum())
                    / abs(out[other].sum()))
        print(f"  fun_int {default} vs {other}: max per-tenant rel diff "
              f"{per:.3e} ({int(np.sum(spread > 0.05))} tenants beyond "
              f"0.05), aggregate {agg:.3e}")
        check(per <= 0.05, f"per-tenant fun_int {default} vs {other} "
                           f"within rtol 0.05")
        check(agg < 2e-2, f"fleet aggregate fun_int {default} vs {other} "
                          f"within 2e-2")
        result[other] = {"per_tenant": per, "aggregate": agg}
    check.done()
    return result


def phase_replay(catalog, n_tenants: int = REPLAY_TENANTS,
                 ticks: int = REPLAY_TICKS) -> dict:
    """Phase 3: batched replay vs the sequential reference engine."""
    from repro.fleet.replay import TenantSpec, replay_fleet
    from repro.fleet.traces import make_trace

    check = Checks("replay")
    specs = [TenantSpec(f"tenant-{k}",
                        make_trace("diurnal" if k % 2 == 0 else "flash_crowd",
                                   base, ticks, seed=k))
             for k, base in enumerate(tenant_demands(n_tenants, seed=1))]
    totals = {}
    for mode in ("batched", "sequential"):
        t0 = time.perf_counter()
        res = replay_fleet(catalog, specs, replay_mode=mode,
                           run_ca_baseline=False)
        uncovered = sum(not s.metrics.satisfied
                        for t in res.tenants for s in t.steps)
        totals[mode] = res.metrics.total_cost_integral
        print(f"  replay {mode}: {time.perf_counter() - t0:.3f} s, "
              f"{res.metrics.total_tenant_ticks} tenant-ticks, cost integral "
              f"{totals[mode]:.6f}")
        check(uncovered == 0, f"{mode}: every committed allocation covers "
                              f"its demand ({uncovered} uncovered)")
    agg = abs(totals["batched"] - totals["sequential"]) / totals["sequential"]
    print(f"  cost integral batched vs sequential: rel diff {agg:.3e}")
    check(agg < 2e-2, "cost integrals agree within 2e-2")
    check.done()
    return {"aggregate": float(agg)}


def phase_serve(catalog, clock: CompileClock, lanes: int = N_TENANTS,
                steady_ticks: int = SERVE_STEADY_TICKS,
                deadline_ms: float = SERVE_DEADLINE_MS) -> dict:
    """Phase 4: fill every lane, then warm flash-crowd ticks: warm-up
    ticks that compile, then steady ticks that must not. Last, the same
    budget straight to ``solve_fleet_step`` at the same width, so that
    fenced chunks run even where the engine's host work has spent the
    tick's budget before its solve starts."""
    from repro.core.api import problem_from_demand
    from repro.core.pgd import AnytimeConfig
    from repro.fleet.batching import stack_problems
    from repro.fleet.solver import solve_fleet_step
    from repro.fleet.traces import flash_crowd_trace
    from repro.serve import ServeEngine

    check = Checks("serve")
    eng = ServeEngine(catalog, lanes, deadline_ms=deadline_ms)
    warm_ticks = SERVE_WARMUP_TICKS + steady_ticks
    traces = {f"tenant-{k}": flash_crowd_trace(base, warm_ticks + 2, seed=k)
              for k, base in enumerate(tenant_demands(lanes, seed=2))}
    for name, tr in traces.items():
        eng.register(name, demand=tr[0])
    t0 = time.perf_counter()
    recs = eng.tick()
    print(f"  fill tick: {len(recs)} cold joins in "
          f"{time.perf_counter() - t0:.3f} s")
    check(len(eng.tenants()) == lanes and len(recs) == lanes,
          f"all {lanes} lanes live")
    infeasible = sum(not r.feasible for r in recs)
    steady_ms, truncated, worst_over = [], [], -np.inf
    for t in range(1, warm_ticks + 1):
        if t == SERVE_WARMUP_TICKS + 1:
            compiles_before_steady = clock.compiles
        for name, tr in traces.items():
            eng.submit(name, tr[t])
        recs = eng.tick()
        rep = eng.last_anytime
        check(len(recs) == lanes and rep is not None,
              f"warm tick {t}: one budgeted solve decided all {lanes} "
              f"tenants")
        infeasible += sum(not r.feasible for r in recs)
        if rep is None:
            continue
        worst_over = max(worst_over, rep.elapsed_ms - (rep.budget_ms
                                                       + rep.max_step_ms))
        print(f"  warm tick {t}: tick {recs[0].latency_ms:.3f} ms, anytime "
              f"budget {rep.budget_ms:.3f} ms, elapsed {rep.elapsed_ms:.3f} "
              f"ms in {rep.chunks} chunks (longest step "
              f"{rep.max_step_ms:.3f} ms), truncated {rep.deadline_hit}")
        if t > SERVE_WARMUP_TICKS:
            steady_ms.append(recs[0].latency_ms)
            truncated.append(rep.deadline_hit)
    print(f"  info only: {len(steady_ms)} steady warm ticks p50 "
          f"{np.percentile(steady_ms, 50):.3f} ms, p99 "
          f"{np.percentile(steady_ms, 99):.3f} ms, truncated share "
          f"{np.mean(truncated):.3f}")
    steady_compiles = clock.compiles - compiles_before_steady
    check(steady_compiles == 0, f"steady warm ticks compiled nothing "
                                f"({steady_compiles} programs)")
    check(infeasible == 0,
          f"every decision feasible ({infeasible} infeasible)")

    demand = np.stack([tr[warm_ticks + 1] for tr in traces.values()])
    X_cur = np.stack([eng.allocation(n) for n in traces]).astype(np.float32)
    batch = stack_problems([problem_from_demand(catalog, d) for d in demand])
    anytime = AnytimeConfig(deadline_ms=deadline_ms,
                            chunk_iters=eng.chunk_iters)
    for run in ("warm-up", "warm"):   # the warm-up compiles the chunk
        compiles_before = clock.compiles
        # blocked on, so the rounding it queues does not run inside the
        # next call's budget
        res = jax.block_until_ready(solve_fleet_step(
            batch, X_cur, eng.delta_max, steps=eng.solver_steps,
            anytime=anytime))
        rep = res.anytime
        print(f"  solve_fleet_step alone, {run}: budget {rep.budget_ms:.3f} "
              f"ms, elapsed {rep.elapsed_ms:.3f} ms in {rep.chunks} chunks "
              f"(longest step {rep.max_step_ms:.3f} ms), "
              f"{clock.compiles - compiles_before} programs compiled")
    check(clock.compiles == compiles_before,
          "warm solve_fleet_step alone compiled nothing")
    slack = coverage_slack(catalog, np.asarray(res.x_int), demand)
    worst_over = max(worst_over, rep.elapsed_ms - (rep.budget_ms
                                                   + rep.max_step_ms))
    print(f"  solve_fleet_step alone: worst coverage slack {slack.min():.6g}")
    check(rep.chunks >= 1, "solve_fleet_step alone ran fenced chunks")
    check(bool(np.all(slack >= -1e-6)), "solve_fleet_step alone: every "
          f"x_int covers its demand ({int(np.sum(slack < -1e-6))} uncovered)")
    check(worst_over <= 0.0, "every anytime elapsed_ms <= budget + one chunk "
                             f"(worst margin {worst_over:.3f} ms)")
    check.done()
    return {"steady_ms": steady_ms}


def main() -> int:
    """Run phases 0-4; print the JSON verdict last."""
    from repro.compile_cache import setup_compile_cache
    from repro.core.catalog import make_cloud_catalog

    clock = CompileClock()
    with phase("0 device", clock):
        cache_dir = setup_compile_cache()
        dev = require_tpu()
        count = len(jax.devices())
        print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
              f"count {count}; jax {jax.__version__}; compile cache "
              f"{cache_dir}", flush=True)
    catalog = make_cloud_catalog()
    with phase("1 kernel", clock):
        batch, demands, starts = build_fleet(catalog, N_TENANTS)
        phase_kernel(batch, starts)
    with phase("2 solve", clock):
        phase_solve(catalog, batch, demands, starts)
    with phase("3 replay", clock):
        phase_replay(catalog)
    with phase("4 serve", clock):
        phase_serve(catalog, clock)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    raise SystemExit(main())
