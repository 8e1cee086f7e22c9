#!/usr/bin/env python
"""Serving benchmark: decision latency under bursty arrival + the
anytime deadline's staleness-vs-objective tradeoff (``make bench-serve``).

Two sections land in ``BENCH_serve.json``:

* ``latency`` — p50/p99 decision-tick latency of a :class:`repro.serve.
  ServeEngine` under a flash-crowd arrival pattern (staggered joins, a
  mid-session depart/join churn event, per-tick coin-flip demand
  arrival), swept over lane capacity B and the enforced per-tick
  ``deadline_ms``. Each cell warms the compiled programs first (one cold
  + one warm tick, then the record buffer is cleared), so percentiles
  measure steady state, not XLA compilation.
* ``degradation`` — the enforced-deadline contract on ONE fixed warm
  solve: the same problem and warm start swept over solve budgets with a
  deterministic fake clock (fixed ms per clock read). Because every
  budget walks the SAME chunked trajectory and the anytime driver keeps
  the merit-argmin prefix, a tighter budget can only return an equal or
  worse objective — ``monotone_objective`` — while every returned
  allocation stays feasible (``all_feasible``). This is the graceful-
  degradation evidence: latency buys objective, never correctness.

The provenance block (config digest + seeds) makes the file comparable
by ``tools/bench_compare.py`` exactly like the other BENCH_*.json files.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--quick] [--json PATH]
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO, "benchmarks", "BENCH_serve.json")

CONFIG = {
    "bench": "serve_bench",
    "catalog_stride": 40,
    "base_demand": [8.0, 16.0, 4.0, 100.0],
    "arrival_p": 0.7,
    "ticks": 16,
    "delta_max": 64.0,
    "chunk_iters": 32,
    "lanes": [16, 64, 256],
    "deadline_ms": [None, 100.0, 50.0, 20.0],
    "quick_lanes": [4, 8],
    "quick_deadline_ms": [None, 50.0],
    # the degradation instance is a LARGE demand jump (x3) so the
    # untruncated warm solve needs a few hundred iterations — tight
    # budgets then genuinely truncate instead of the solve converging
    # inside the first chunk at every budget
    "degradation_budgets_ms": [0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 64.0],
    "degradation_chunk_iters": 8,
    "degradation_clock_step_ms": 0.25,
    "degradation_demand_scale": 3.0,
}
SEEDS = [0]


def _make_catalog():
    from repro.core import Catalog, make_cloud_catalog
    return Catalog(make_cloud_catalog().instances[::CONFIG["catalog_stride"]])


def _latency_cell(catalog, lanes: int, deadline_ms, seed: int) -> dict:
    """One (B, deadline) cell: warmed flash-crowd serving session."""
    from repro.fleet.traces import flash_crowd_trace
    from repro.serve import ServeEngine

    rng = np.random.default_rng(seed)
    base = np.asarray(CONFIG["base_demand"], np.float64)
    ticks = int(CONFIG["ticks"])
    eng = ServeEngine(catalog, lanes, deadline_ms=deadline_ms,
                      chunk_iters=CONFIG["chunk_iters"],
                      delta_max=CONFIG["delta_max"])
    traces = {f"t{k}": flash_crowd_trace(
        base * rng.uniform(0.5, 1.5, size=base.shape), ticks + 2,
        seed=seed + k) for k in range(lanes)}
    names = sorted(traces)
    # warmup: compile the cold and warm programs outside the measurement
    for name in names:
        eng.register(name, demand=traces[name][0])
    eng.tick()
    for name in names:
        eng.submit(name, traces[name][1])
    eng.tick()
    eng.records.clear()
    cursor = {name: 2 for name in names}
    churn_tick = ticks // 2
    for t in range(ticks):
        if t == churn_tick:
            gone = eng.tenants()[0]
            eng.depart(gone)
            joiner = f"{gone}-successor"
            traces[joiner] = flash_crowd_trace(
                base * rng.uniform(0.5, 1.5, size=base.shape), ticks + 2,
                seed=seed + 1001)
            eng.register(joiner, demand=traces[joiner][0])
            cursor[joiner] = 1
        for name in eng.tenants():
            tr = traces[name]
            if cursor[name] <= 1 or rng.random() < CONFIG["arrival_p"]:
                eng.submit(name, tr[min(cursor[name], len(tr) - 1)])
                cursor[name] += 1
        eng.tick()
    return eng.summary().to_dict()


def _degradation_sweep() -> dict:
    """Fixed (problem, warm start), deterministic fake clock, budget sweep:
    the anytime contract's graceful-degradation curve."""
    import jax.numpy as jnp

    from repro.core import (AnytimeConfig, is_feasible, multistart_solve,
                            objective_value, problem_from_demand,
                            round_and_polish, solve_incremental_info)

    catalog = _make_catalog()
    base = np.asarray(CONFIG["base_demand"], np.float64)
    prob0 = problem_from_demand(catalog, base)
    x_cur = np.asarray(multistart_solve(prob0, n_starts=4).x_int, np.float64)
    prob = problem_from_demand(catalog,
                               base * CONFIG["degradation_demand_scale"])
    delta = jnp.asarray(CONFIG["delta_max"], jnp.float32)
    step_s = CONFIG["degradation_clock_step_ms"] / 1e3

    rows = []
    for budget in CONFIG["degradation_budgets_ms"]:
        state = {"t": 0.0}

        def clock():
            state["t"] += step_s
            return state["t"]

        anytime = AnytimeConfig(deadline_ms=float(budget),
                                chunk_iters=CONFIG["degradation_chunk_iters"],
                                clock=clock)
        x_best, iters, report = solve_incremental_info(
            prob, jnp.asarray(x_cur, jnp.float32), delta, anytime=anytime)
        x_int = round_and_polish(prob, x_best)
        rows.append({
            "budget_ms": float(budget),
            "iters": int(iters),
            "deadline_hit": bool(report.deadline_hit),
            "chunks": int(report.chunks),
            "objective_relaxed": float(objective_value(prob, x_best)),
            "objective_int": float(objective_value(prob, x_int)),
            "feasible": bool(is_feasible(prob, x_int, 1e-3)),
        })
    merits = [r["objective_relaxed"] for r in rows]
    return {
        "rows": rows,
        "checks": {
            # budgets are sorted ascending, so merit must be non-increasing:
            # more budget never returns a worse best-so-far iterate
            "monotone_objective": bool(all(
                b <= a + 1e-6 for a, b in zip(merits, merits[1:]))),
            "monotone_iters": bool(all(
                r2["iters"] >= r1["iters"]
                for r1, r2 in zip(rows, rows[1:]))),
            "all_feasible": bool(all(r["feasible"] for r in rows)),
            # the sweep only demonstrates degradation if the deadline has
            # teeth: the tightest budget must truncate, the most generous
            # must let the solve run to convergence
            "tight_budget_truncates": bool(rows[0]["deadline_hit"]),
            "generous_budget_completes": bool(not rows[-1]["deadline_hit"]),
        },
    }


def run(quick: bool = False) -> dict:
    catalog = _make_catalog()
    lanes = CONFIG["quick_lanes"] if quick else CONFIG["lanes"]
    deadlines = (CONFIG["quick_deadline_ms"] if quick
                 else CONFIG["deadline_ms"])
    latency = {}
    for B in lanes:
        for dl in deadlines:
            key = f"B{B}_deadline_{'none' if dl is None else f'{dl:g}ms'}"
            print(f"[serve_bench] latency cell {key} ...", flush=True)
            latency[key] = _latency_cell(catalog, B, dl, seed=SEEDS[0])
            print(f"[serve_bench]   p50 {latency[key]['p50_latency_ms']:.2f} "
                  f"ms  p99 {latency[key]['p99_latency_ms']:.2f} ms  "
                  f"truncated {latency[key]['truncated_rate']:.1%}",
                  flush=True)
    print("[serve_bench] degradation sweep ...", flush=True)
    degradation = _degradation_sweep()
    return {"latency": latency, "degradation": degradation,
            "config": {**CONFIG, "quick": quick}}


def main(argv) -> int:
    quick = "--quick" in argv
    out = DEFAULT_OUT
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            raise SystemExit("--json requires a path argument")
        out = argv[i + 1]

    from repro.obs import provenance_block

    doc = run(quick=quick)
    doc["provenance"] = provenance_block(argv, config=CONFIG, seeds=SEEDS)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    checks = doc["degradation"]["checks"]
    print(f"[serve_bench] wrote {out}")
    print(f"[serve_bench] degradation checks: {checks}")
    if not (checks["monotone_objective"] and checks["all_feasible"]
            and checks["tight_budget_truncates"]):
        print("[serve_bench] FAIL: anytime degradation contract violated")
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    sys.exit(main(sys.argv[1:]))
