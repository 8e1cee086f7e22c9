"""Receding-horizon (MPC) benchmark: lookahead depth x forecaster x trace.

Sweeps the MPC controller over H ∈ {1, 4, 8, 16} (quick: {1, 4, 8}) and
every forecaster kind on diurnal and flash-crowd fleets, against the myopic
controller on the SAME fleets — the cost/churn/SLO tradeoff surface the
ISSUE's tentpole asks for:

* diurnal      — the churn-chasing case: the myopic controller pays churn
                 following every day/night swing; lookahead + the smoothed
                 inter-tick coupling hold a steadier allocation.
* flash_crowd  — the late-reaction case: the myopic controller starts
                 scaling only when the burst has landed; a forecaster that
                 sees it coming pre-provisions inside the churn budget.

Each (trace, forecaster, H) cell reports the fleet cost integral, total
churn, SLO-violation ticks, the worst churn-bound overrun, and the combined
COST+CHURN OBJECTIVE

    J = cost_integral + churn_cost * total_churn

where ``churn_cost`` is calibrated to the catalog's median hourly price
(moving a node costs about an hour of it: drain + reschedule + warm-up).
Regret per cell is J minus the oracle forecaster's J at the same (trace, H)
— the price of forecast error alone (docs/horizon.md).

Every cell also records ``solver_iters`` — the total inner-PGD iterations
the replay's warm ticks actually spent (summed over tenants and ticks, read
off the recorded ``ControllerStep.solver_iters``). By default each cell
runs under BOTH horizon engines — the adaptive BB/Armijo solver (the
primary, whose metrics fill the cell) and the original fixed-step solver
(``objective_fixed`` / ``solver_iters_fixed`` / ``adaptive_beats_fixed``)
— which is the tentpole's speedup evidence: the adaptive engine must match
or beat the fixed engine's J while spending fewer iterations at H>=8.
``--solver adaptive`` / ``--solver fixed`` restrict the sweep to one
engine to reproduce either side of that claim in isolation.

The JSON also carries a ``solver_scaling`` section (admm vs adaptive vs
fixed on batched H ∈ {8, 16, 32, 64} windows): each engine's steady-state
wall time and mean window merit at the default 600-iteration-equivalent
budget, plus a time-to-quality escalation — how many steps (and how much
wall time) the adaptive engine needs to MATCH the ADMM merit. At H=32/64
the adaptive engine's flat-stop plateaus above ADMM's merit at every
budget; only ``ftol=0`` at 16–32x the step count reaches it, at an order
of magnitude more wall time (the measured form of the ISSUE's "handles
H=32/64 only at materially higher wall time").

Run:  PYTHONPATH=src python benchmarks/horizon_bench.py
          [--quick] [--json PATH] [--solver {adaptive,fixed,admm,both}]

Always writes machine-readable results (default benchmarks/BENCH_horizon.json)
like fleet_bench does, so the MPC-vs-myopic trajectory is tracked across PRs.
Every replay runs instrumented (repro.obs): per-cell ``t_replay`` is split
into ``t_compile`` (first-call compile-tagged ticks) and ``t_execute``
(steady state), and the JSON gains a ``telemetry`` section (run-wide
compile/steady split, pooled steady-tick percentiles, one cell's per-phase
breakdown) plus a ``provenance`` block (git SHA, jax versions, platform).
The acceptance gate: at least one (trace, forecaster, H>1) cell must beat the
myopic controller's J on the same fleet.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import Catalog, make_cloud_catalog
from repro.fleet import TenantSpec, make_trace, replay_fleet
from repro.horizon import (FORECASTER_KINDS, HorizonProblem,
                           HorizonSolverConfig, expand_problems,
                           solve_horizon_fleet_step)
from repro.horizon.solver import _horizon_merit_fns
from repro.obs import ReplayReport, percentiles, provenance_block, telemetry
from repro.testing import make_toy_problem

DEFAULT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_horizon.json")
# production-scale demand: allocations land at tens of nodes per tenant, so
# diurnal swings and flash bursts move whole nodes (at paper-scenario scale
# a single node absorbs the swings and every controller degenerates to the
# same static allocation)
BASE = np.array([8.0, 16.0, 4.0, 100.0]) * 25
NOISE = 0.08     # realistic demand jitter — what the myopic controller
                 # chases node-by-node and the coupled plan smooths over


def _fleet(catalog: Catalog, trace_kind: str, B: int, T: int):
    """B tenants on one shared catalog (one shape bucket -> one compiled
    program per H), staggered scales/seeds, all on ``trace_kind`` demand."""
    specs = []
    for s in range(B):
        kwargs = dict(seed=s, noise=NOISE)
        if trace_kind == "diurnal":
            kwargs.update(amplitude=0.45, phase=3.0 * s)
        elif trace_kind == "flash_crowd":
            kwargs.update(burst_scale=2.5, decay=5.0)
        specs.append(TenantSpec(
            name=f"{trace_kind}{s}",
            trace=make_trace(trace_kind, BASE * (0.7 + 0.2 * (s % 3)), T,
                             **kwargs),
            n_starts=2, delta_max=6.0))
    return specs


def _cell_metrics(metrics, churn_cost: float) -> dict:
    return dict(
        cost=metrics.total_cost_integral,
        churn=metrics.total_churn,
        slo_ticks=metrics.total_slo_violation_ticks,
        max_churn_violation=metrics.max_churn_violation,
        objective=metrics.total_cost_integral
        + churn_cost * metrics.total_churn,
    )


def _total_solver_iters(res) -> int:
    """Warm-tick PGD iterations the whole replay spent (fleet total)."""
    return int(sum(s.solver_iters for t in res.tenants for s in t.steps))


def _instrumented_replay(**kw):
    """One instrumented ``replay_fleet``: ``(result, timing, steady_ticks,
    report)`` where ``timing`` splits the wall clock into compile-tagged
    vs steady-state tick time (the per-cell t_replay used to fold JIT
    compilation into whichever cell ran a shape first) and
    ``steady_ticks`` are the raw steady-state tick latencies in ms for
    run-wide percentile pooling. The compile tag means "first call for
    this compile key IN THIS CELL": later cells re-running an
    already-compiled shape still tag ~2 ticks compile, so cross-cell
    compile seconds are a small overestimate — the steady-state numbers
    are the comparable ones."""
    t0 = time.time()
    with telemetry() as rec:
        res = replay_fleet(**kw)
    dt = time.time() - t0
    rep = ReplayReport.from_recorder(rec)
    tick = next((p for p in rep.phases if p.name == "replay/tick"), None)
    timing = dict(
        t_replay=dt,
        t_compile=(tick.compile_ms / 1e3 if tick else 0.0),
        t_execute=(tick.execute_ms / 1e3 if tick else 0.0))
    steady = [e.dur_us / 1e3 for e in rec.events
              if e.name == "replay/tick" and e.phase != "compile"]
    return res, timing, steady, rep


# the fixed-step baseline the adaptive engine is benchmarked against — the
# same 600-step budget both engines get per warm tick
FIXED_CFG = HorizonSolverConfig(solver="fixed")

# the consensus-ADMM engine at the SAME per-tick compute as the 600-step
# monolithic engines: 30 outer sweeps x 20 inner prox iterations per tick
ADMM_CFG = HorizonSolverConfig(solver="admm", admm_iters=30, inner_steps=20)

MPC_CFGS = {"adaptive": None, "fixed": FIXED_CFG, "admm": ADMM_CFG}

# "matching" tolerance for the adaptive-vs-fixed J comparison: replay J is
# rounding-quantized (whole nodes move or don't), so sub-half-percent gaps
# are below the metric's own granularity on these fleets
MATCH_RTOL = 5e-3


def adaptive_fixed_summary(cells):
    """The tentpole's speedup evidence, machine-readable: over the H>1
    cells that ran both engines, how many beat / match fixed-step J, the
    worst relative gap, and the minimum H>=8 iteration-reduction factor."""
    both = [c for c in cells
            if c["H"] > 1 and c.get("objective_fixed") is not None]
    if not both:
        return None
    rel = lambda c: c["objective"] / c["objective_fixed"] - 1.0
    worst = max(both, key=rel)
    h8 = [c for c in both if c["H"] >= 8]
    return dict(
        n_cells=len(both),
        n_beat=sum(1 for c in both if c["objective"] <= c["objective_fixed"]),
        n_match=sum(1 for c in both if rel(c) <= MATCH_RTOL),
        match_rtol=MATCH_RTOL,
        worst_rel_gap=rel(worst),
        worst_cell=f"{worst['trace']}/{worst['forecaster']}/H={worst['H']}",
        h8_all_match=all(rel(c) <= MATCH_RTOL for c in h8),
        h8_min_iters_reduction=(min(c["solver_iters_fixed"]
                                    / max(c["solver_iters"], 1)
                                    for c in h8) if h8 else None),
    )


def _scaling_fleet(B: int, H: int):
    """B lanes of H-tick demand-ramped windows (one shape bucket), plus the
    stacked ``HorizonProblem`` the batched fleet step consumes."""
    lanes = [expand_problems([make_toy_problem(seed=31 * b + 3 * h,
                                               demand_scale=1.0 + 0.04 * h)
                              for h in range(H)]) for b in range(B)]
    stacked = HorizonProblem(
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                               *(l.problem for l in lanes)),
        lanes[0].coupling_w, lanes[0].coupling_eps)
    return lanes, stacked


def _timed_fleet_solve(hp, xc, delta_max, cfg, repeats: int):
    """Compile, then time ``repeats`` steady-state batched solves; returns
    ``(result, compile_s, steady_ms)`` with steady_ms the per-solve mean."""
    t0 = time.time()
    res = solve_horizon_fleet_step(hp, xc, delta_max, cfg=cfg)
    jax.block_until_ready(res.plan)
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(repeats):
        res = solve_horizon_fleet_step(hp, xc, delta_max, cfg=cfg)
        jax.block_until_ready(res.plan)
    return res, compile_s, (time.time() - t0) / repeats * 1e3


def _mean_window_merit(lanes, plans, xc, delta_max, cfg) -> float:
    """Mean full-window merit over lanes — the SAME objective every engine
    minimizes, so cross-engine J values are directly comparable."""
    dm = jnp.asarray(delta_max, jnp.float32)
    return float(np.mean([
        float(_horizon_merit_fns(l, xc[i], dm, cfg.penalty_w,
                                 cfg.delta_penalty_w)[0](plans[i]))
        for i, l in enumerate(lanes)]))


def solver_scaling(B: int = 4, horizons=(8, 16, 32, 64), repeats: int = 3,
                   delta_max: float = 8.0):
    """admm vs adaptive vs fixed on batched H-tick windows: equal-budget
    merit + wall time per engine, then the time-to-quality escalation — the
    adaptive steps (ftol=0, doubling from 2400) needed to MATCH the ADMM
    merit. The ISSUE's speedup claim, measured: at H=32/64 the default
    adaptive budget plateaus above ADMM's merit, and matching it costs an
    order of magnitude more wall time."""
    out = []
    print("\n" + "=" * 100)
    print(f"Solver scaling: B={B} lanes, H in {tuple(horizons)}, "
          f"equal budget {ADMM_CFG.admm_iters * ADMM_CFG.inner_steps} "
          f"iters/tick, then adaptive escalation to ADMM merit")
    print("=" * 100)
    print(f"  {'H':>3s} {'engine':>16s} {'J (window)':>11s} {'ms':>8s} "
          f"{'vs admm t':>9s}")
    for H in horizons:
        lanes, hp = _scaling_fleet(B, H)
        n = hp.problem.c.shape[2]
        xc = jnp.full((B, n), 1.0, jnp.float32)
        row = dict(H=H, B=B, engines={})
        engines = [("admm", ADMM_CFG),
                   ("adaptive", HorizonSolverConfig(steps=600)),
                   ("fixed", FIXED_CFG)]
        for name, cfg in engines:
            res, comp, ms = _timed_fleet_solve(hp, xc, delta_max, cfg,
                                               repeats)
            J = _mean_window_merit(lanes, res.plan, xc, delta_max, cfg)
            row["engines"][name] = dict(J=J, steady_ms=ms, compile_s=comp)
            ratio = ms / row["engines"]["admm"]["steady_ms"]
            print(f"  {H:3d} {name:>16s} {J:11.4f} {ms:8.0f} {ratio:8.1f}x")
        J_admm = row["engines"]["admm"]["J"]
        t_admm = row["engines"]["admm"]["steady_ms"]
        # time-to-quality: flat-stopping plateaus above ADMM's merit, so the
        # escalation must run with ftol=0 and raw step count
        match = None
        for steps in (2400, 9600, 19200):
            cfg = HorizonSolverConfig(steps=steps, ftol=0.0)
            res, comp, ms = _timed_fleet_solve(hp, xc, delta_max, cfg, 1)
            J = _mean_window_merit(lanes, res.plan, xc, delta_max, cfg)
            match = dict(steps=steps, J=J, steady_ms=ms,
                         matched=bool(J <= J_admm),
                         wall_vs_admm=ms / t_admm)
            tag = "MATCHED" if match["matched"] else "still above admm J"
            print(f"  {H:3d} {'adaptive ftol=0':>16s} {J:11.4f} {ms:8.0f} "
                  f"{ms / t_admm:8.1f}x  steps={steps} {tag}")
            if match["matched"]:
                break
        row["adaptive_to_match"] = match
        out.append(row)
    return out


def run(B: int = 4, T: int = 48, horizons=(1, 4, 8, 16),
        forecasters=None, trace_kinds=("diurnal", "flash_crowd"),
        solvers=("adaptive", "fixed")):
    """The full sweep; returns the JSON-ready results dict. ``solvers``
    picks the horizon engines each MPC cell runs under — the first entry is
    the PRIMARY whose metrics fill the cell; when both run, the cell also
    carries the fixed-vs-adaptive comparison fields."""
    forecasters = forecasters or sorted(FORECASTER_KINDS)
    assert all(s in MPC_CFGS for s in solvers), solvers
    catalog = Catalog(make_cloud_catalog().instances[::40])
    churn_cost = float(np.median([it.hourly_price
                                  for it in catalog.instances]))
    out = dict(config=dict(B=B, T=T, horizons=list(horizons),
                           forecasters=list(forecasters),
                           trace_kinds=list(trace_kinds),
                           solvers=list(solvers),
                           churn_cost=churn_cost, catalog_n=catalog.n),
               myopic={}, cells=[])
    print("=" * 100)
    print(f"Horizon benchmark: B={B} tenants, T={T} ticks, catalog "
          f"n={catalog.n}, churn_cost=${churn_cost:.3f}/unit, "
          f"solvers={'+'.join(solvers)}")
    print("=" * 100)

    # run-wide telemetry rollup: compile/steady seconds summed over every
    # instrumented replay, tick latencies pooled for percentiles, and the
    # last adaptive MPC cell's full per-phase report as an exemplar
    tel = dict(compile_s=0.0, execute_s=0.0)
    steady_ticks: list = []
    example_report = None

    for kind in trace_kinds:
        specs = _fleet(catalog, kind, B, T)
        myo, timing, steady, _ = _instrumented_replay(
            catalog=catalog, tenants=specs, run_ca_baseline=False,
            replay_mode="batched")
        myo_cell = _cell_metrics(myo.metrics, churn_cost)
        myo_cell.update(timing)
        myo_cell["solver_iters"] = _total_solver_iters(myo)
        out["myopic"][kind] = myo_cell
        tel["compile_s"] += timing["t_compile"]
        tel["execute_s"] += timing["t_execute"]
        steady_ticks.extend(steady)
        print(f"\n[{kind}] myopic: cost ${myo_cell['cost']:.2f}  churn "
              f"{myo_cell['churn']:.1f}  slo {myo_cell['slo_ticks']}  "
              f"J ${myo_cell['objective']:.2f}  "
              f"iters {myo_cell['solver_iters']}  "
              f"[compile {timing['t_compile']:.1f}s, "
              f"steady {timing['t_execute']:.1f}s]")
        print(f"  {'forecaster':>14s} {'H':>3s} {'cost':>9s} {'churn':>8s} "
              f"{'slo':>4s} {'J':>9s} {'vs myopic':>10s} {'iters':>7s} "
              f"{'fixed J':>9s} {'f-iters':>7s}")
        for H in horizons:
            for fc in forecasters:
                per_solver = {}
                for solver in solvers:
                    cfg = MPC_CFGS[solver]
                    res, timing, steady, rep = _instrumented_replay(
                        catalog=catalog, tenants=specs,
                        run_ca_baseline=False, replay_mode="batched",
                        controller="mpc", horizon=H, forecaster=fc,
                        solver_config=cfg)
                    sc = _cell_metrics(res.metrics, churn_cost)
                    sc["solver_iters"] = _total_solver_iters(res)
                    sc.update(timing)
                    per_solver[solver] = sc
                    tel["compile_s"] += timing["t_compile"]
                    tel["execute_s"] += timing["t_execute"]
                    steady_ticks.extend(steady)
                    if solver == "adaptive":
                        example_report = rep
                cell = dict(per_solver[solvers[0]])
                cell.update(trace=kind, forecaster=fc, H=H,
                            solver=solvers[0],
                            beats_myopic=bool(cell["objective"]
                                              < myo_cell["objective"]))
                fx = per_solver.get("fixed") if solvers[0] != "fixed" else None
                if fx is not None:
                    cell["objective_fixed"] = fx["objective"]
                    cell["solver_iters_fixed"] = fx["solver_iters"]
                    cell["adaptive_beats_fixed"] = bool(
                        cell["objective"] <= fx["objective"])
                out["cells"].append(cell)
                delta = 100.0 * (cell["objective"] / myo_cell["objective"]
                                 - 1.0)
                fx_j = f"{fx['objective']:9.2f}" if fx else "        -"
                fx_i = f"{fx['solver_iters']:7d}" if fx else "      -"
                print(f"  {fc:>14s} {H:3d} {cell['cost']:9.2f} "
                      f"{cell['churn']:8.1f} {cell['slo_ticks']:4d} "
                      f"{cell['objective']:9.2f} {delta:+9.1f}% "
                      f"{cell['solver_iters']:7d} {fx_j} {fx_i}")

    # regret per cell: J minus the oracle's J at the same (trace, H)
    oracle_J = {(c["trace"], c["H"]): c["objective"]
                for c in out["cells"] if c["forecaster"] == "oracle"}
    for c in out["cells"]:
        ref = oracle_J.get((c["trace"], c["H"]))
        c["regret_vs_oracle"] = (None if ref is None
                                 else c["objective"] - ref)

    # BENCH telemetry section: run-wide compile/steady split, pooled
    # steady-state tick percentiles, and one cell's per-phase breakdown
    tel["n_steady_ticks"] = len(steady_ticks)
    tel["tick_ms"] = percentiles(steady_ticks, (50, 95, 99))
    if example_report is not None:
        tel["example_cell"] = example_report.to_dict()
    out["telemetry"] = tel
    if tel["tick_ms"]:
        print(f"\n[telemetry] compile {tel['compile_s']:.1f}s vs steady "
              f"{tel['execute_s']:.1f}s across the sweep; steady tick "
              f"p50 {tel['tick_ms']['p50']:.1f}ms  "
              f"p99 {tel['tick_ms']['p99']:.1f}ms")

    out["adaptive_vs_fixed"] = adaptive_fixed_summary(out["cells"])
    if out["adaptive_vs_fixed"] is not None:
        s = out["adaptive_vs_fixed"]
        print(f"\n[adaptive vs fixed] H>1: {s['n_beat']}/{s['n_cells']} "
              f"cells beat fixed outright, {s['n_match']}/{s['n_cells']} "
              f"within {100 * MATCH_RTOL:.1f}%; worst "
              f"{100 * s['worst_rel_gap']:+.2f}% "
              f"({s['worst_cell']}); H>=8 iters reduction "
              f">= {s['h8_min_iters_reduction']:.1f}x, all H>=8 cells "
              f"within tolerance: {s['h8_all_match']}")

    winners = [c for c in out["cells"] if c["H"] > 1 and c["beats_myopic"]]
    out["n_winning_cells"] = len(winners)
    if winners:
        # compare by improvement RELATIVE to each cell's own myopic baseline
        # — absolute J is not comparable across trace kinds (different
        # demand shapes mean different fleet-wide cost scales)
        rel = lambda c: c["objective"] / out["myopic"][c["trace"]]["objective"]
        best = min(winners, key=rel)
        out["best"] = best
        print(f"\n[best H>1 cell] {best['trace']} / {best['forecaster']} / "
              f"H={best['H']}: J ${best['objective']:.2f} vs myopic "
              f"${out['myopic'][best['trace']]['objective']:.2f} "
              f"({100.0 * (rel(best) - 1.0):+.1f}%)")
    else:
        print("\nWARNING: no (trace, forecaster, H>1) cell beat the myopic "
              "controller — acceptance gate NOT met")
    return out


def main(argv):
    """CLI: --quick trims the MPC grid (the solver_scaling section always
    covers H up to 64 — it times single batched solves, not replays);
    --json PATH overrides the output file; --solver
    {adaptive,fixed,admm,both} picks the horizon engine(s) each MPC cell
    runs under (default both monolithic engines — the adaptive-vs-fixed
    speedup evidence; the admm comparison lives in solver_scaling)."""
    quick = "--quick" in argv
    json_path = DEFAULT_JSON
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            raise SystemExit("--json requires a path argument")
        json_path = argv[i + 1]
    solvers = ("adaptive", "fixed")
    if "--solver" in argv:
        i = argv.index("--solver")
        if i + 1 >= len(argv) or argv[i + 1] not in ("adaptive", "fixed",
                                                     "admm", "both"):
            raise SystemExit("--solver requires adaptive, fixed, admm or "
                             "both")
        if argv[i + 1] != "both":
            solvers = (argv[i + 1],)
    if quick:
        out = run(B=3, T=24, horizons=(1, 4, 8),
                  forecasters=("last_value", "holt_winters", "oracle"),
                  solvers=solvers)
    else:
        out = run(solvers=solvers)
    out["solver_scaling"] = solver_scaling()
    out["config"]["quick"] = quick
    # trace seeds are tenant indices (make_fleet's spec loop); the config
    # digest makes bench_compare refuse quick-vs-full or cross-solver pairs
    out["provenance"] = provenance_block(
        argv, config=out["config"], seeds=list(range(out["config"]["B"])))
    with open(json_path, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"\n[json] wrote {json_path}")


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    main(sys.argv[1:])
