"""Fleet subsystem benchmark: batched multi-tenant solving vs the naive
per-problem Python loop.

Six sections:
  1. RAGGED fleet, end-to-end (the production case): every tenant has its own
     catalog slice shape, so the naive loop pays one XLA compile PER DISTINCT
     SHAPE while solve_fleet pads + compiles ONCE. This is where batching is
     transformative (CvxCluster's batch-structured-solve argument).
  2. UNIFORM fleet, warm steady-state: pure lockstep-batching throughput with
     compilation amortized on both sides.
  3. Agreement: the batched solve must reproduce the naive loop's objectives.
  4. SHAPE BUCKETING: padding-waste reduction (and solve agreement) from
     grouping a ragged fleet into power-of-two shape buckets instead of one
     global pad.
  5. REPLAY: end-to-end trace replay, batched engine (one solve per shape
     bucket per tick) vs the sequential per-tenant controller loop, on a
     ragged fleet of per-tenant catalogs with RAGGED per-tenant horizons.
  6. CA BASELINE: vectorized lockstep CA replay
     (simulate_cluster_autoscaler_batch, one numpy program per tick for the
     whole fleet) vs the sequential per-tenant simulator loop.

Run:  PYTHONPATH=src python benchmarks/fleet_bench.py [--quick] [--json PATH]

Every run also writes the machine-readable results to BENCH_fleet.json
(default: benchmarks/BENCH_fleet.json) so the perf trajectory — batched
replay speedup, padding-waste fractions, CA-replay throughput — is tracked
across PRs instead of living only in printed prose. Speedups are reported
both end-to-end (compile included) and steady-state (compile-tagged ticks
excluded, via repro.obs telemetry spans); the JSON carries a ``telemetry``
section (per-phase compile/execute split and latency percentiles from the
instrumented replay) and a ``provenance`` block (git SHA, jax versions,
platform) so numbers are comparable across machines and PRs.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from repro.core import Catalog, SolverConfig, make_cloud_catalog, multistart_solve
from repro.fleet import (TenantSpec, bucket_problems, make_trace,
                         padding_stats, replay_fleet, solve_fleet,
                         solve_fleet_bucketed, stack_problems)
from repro.fleet.replay import _ca_baseline, _replay_ca_fleet
from repro.obs import ReplayReport, provenance_block, telemetry
from repro.testing import make_toy_problem

CFG = SolverConfig()
DEFAULT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_fleet.json")


def _ragged_fleet(B: int):
    """B tenants, every one a distinct (m, n) shape — 64 distinct shapes at
    B=64, exactly what a real multi-tenant fleet looks like."""
    return [make_toy_problem(seed=s, n=24 + s, m=3 + s % 2) for s in range(B)]


def _uniform_fleet(B: int, n: int):
    return [make_toy_problem(seed=s, n=n) for s in range(B)]


def _naive_loop(probs, n_starts):
    out = []
    for p in probs:
        ms = multistart_solve(p, n_starts=n_starts, cfg=CFG)
        out.append((float(ms.fun_int), float(np.min(np.where(
            np.asarray(ms.all_feasible), np.asarray(ms.all_fun), np.inf)))))
    return out


def run(B: int = 64, n_starts: int = 4):
    out = {}
    print("=" * 100)
    print(f"Fleet benchmark: batched multi-tenant solve, B={B}, "
          f"{n_starts} starts per tenant")
    print("=" * 100)

    # ---- 1. ragged fleet, end-to-end (includes JIT on both sides) ----------
    probs = _ragged_fleet(B)
    batch = stack_problems(probs)
    t0 = time.time()
    res = solve_fleet(batch, n_starts=n_starts, cfg=CFG)
    res.fun.block_until_ready()
    t_fleet_cold = time.time() - t0

    t0 = time.time()
    naive = _naive_loop(probs, n_starts)
    t_naive_cold = time.time() - t0

    speedup_cold = t_naive_cold / t_fleet_cold
    print(f"[ragged, end-to-end] {B} tenants, {B} distinct shapes")
    print(f"  solve_fleet : {t_fleet_cold:7.1f}s  "
          f"({B / t_fleet_cold:6.1f} problems/s)  [1 compile]")
    print(f"  naive loop  : {t_naive_cold:7.1f}s  "
          f"({B / t_naive_cold:6.1f} problems/s)  [{B} compiles]")
    print(f"  speedup     : {speedup_cold:.1f}x  (includes compile on "
          f"both sides)")
    out["ragged_cold"] = dict(t_fleet=t_fleet_cold, t_naive=t_naive_cold,
                              speedup=speedup_cold)

    # ---- ragged fleet, steady state: the same solves with compilation
    # amortized, so the cold-vs-warm difference IS the compile time each
    # side paid above — the honest decomposition of the headline speedup
    t0 = time.time()
    r2 = solve_fleet(batch, n_starts=n_starts, cfg=CFG)
    r2.fun.block_until_ready()
    t_fleet_warm_r = time.time() - t0
    t0 = time.time()
    _naive_loop(probs, n_starts)
    t_naive_warm_r = time.time() - t0
    print(f"[ragged, steady-state] fleet {t_fleet_warm_r:.1f}s vs naive "
          f"{t_naive_warm_r:.1f}s: {t_naive_warm_r / t_fleet_warm_r:.1f}x  "
          f"(compile share of cold run: fleet "
          f"{t_fleet_cold - t_fleet_warm_r:.1f}s, naive "
          f"{t_naive_cold - t_naive_warm_r:.1f}s)")
    out["ragged_warm"] = dict(
        t_fleet=t_fleet_warm_r, t_naive=t_naive_warm_r,
        speedup=t_naive_warm_r / t_fleet_warm_r,
        t_compile_fleet=t_fleet_cold - t_fleet_warm_r,
        t_compile_naive=t_naive_cold - t_naive_warm_r)

    # ---- agreement on the ragged fleet -------------------------------------
    fun_int = np.asarray(res.fun_int)
    naive_int = np.asarray([f for f, _ in naive])
    per_tenant = np.abs(fun_int - naive_int) / np.maximum(np.abs(naive_int),
                                                          1e-9)
    agg = abs(fun_int.sum() - naive_int.sum()) / abs(naive_int.sum())
    feas = bool(np.all(np.asarray(res.feasible)))
    print(f"[agreement] integer objective vs naive loop: "
          f"median {np.median(per_tenant):.2e}, max {per_tenant.max():.2e}, "
          f"fleet aggregate {agg:.2e}, all feasible: {feas}")
    out["agreement"] = dict(median=float(np.median(per_tenant)),
                            max=float(per_tenant.max()), aggregate=float(agg),
                            all_feasible=feas)

    # ---- 2. uniform fleet, warm steady-state -------------------------------
    probs_u = _uniform_fleet(B, n=96)
    batch_u = stack_problems(probs_u)
    r = solve_fleet(batch_u, n_starts=n_starts, cfg=CFG)   # compile
    r.fun.block_until_ready()
    t0 = time.time()
    r = solve_fleet(batch_u, n_starts=n_starts, cfg=CFG)
    r.fun.block_until_ready()
    t_fleet_warm = time.time() - t0
    _naive_loop(probs_u[:1], n_starts)                     # compile
    t0 = time.time()
    _naive_loop(probs_u, n_starts)
    t_naive_warm = time.time() - t0
    print(f"[uniform n=96, warm] fleet {t_fleet_warm:.1f}s "
          f"({B / t_fleet_warm:.1f} problems/s) vs naive {t_naive_warm:.1f}s "
          f"({B / t_naive_warm:.1f} problems/s): "
          f"{t_naive_warm / t_fleet_warm:.1f}x")
    out["uniform_warm"] = dict(t_fleet=t_fleet_warm, t_naive=t_naive_warm,
                               speedup=t_naive_warm / t_fleet_warm)

    # ---- 3. scaling with fleet size ----------------------------------------
    rows = []
    for b in (8, 16, 32, B):
        pb = stack_problems(_uniform_fleet(b, n=48))
        r = solve_fleet(pb, n_starts=n_starts, cfg=CFG)    # compile
        r.fun.block_until_ready()
        t0 = time.time()
        r = solve_fleet(pb, n_starts=n_starts, cfg=CFG)
        r.fun.block_until_ready()
        dt = time.time() - t0
        rows.append(dict(B=b, t=dt, pps=b / dt))
        print(f"[scaling] B={b:3d}: {dt:6.2f}s  {b / dt:6.1f} problems/s")
    out["scaling"] = rows

    # ---- 4. shape-bucketed stacking ----------------------------------------
    out["bucketing"] = run_bucketing(B, n_starts)

    # ---- 5. batched vs sequential trace replay -----------------------------
    out["replay"] = run_replay(B)
    # hoist the instrumented replay's span rollup to the BENCH JSON's
    # top-level telemetry section (compile/execute split, per-phase p50/p99)
    out["telemetry"] = out["replay"].pop("telemetry")

    # ---- 6. vectorized vs sequential CA baseline replay --------------------
    out["ca_replay"] = run_ca_replay(B)
    return out


def _skewed_fleet(B: int):
    """A very heterogeneous fleet: a few big tenants dominate the global pad
    (n up to ~120) while most tenants are small (n ~16-40)."""
    probs = []
    for s in range(B):
        n = 100 + s % 3 * 10 if s % 8 == 0 else 16 + (7 * s) % 25
        probs.append(make_toy_problem(seed=s, n=n, m=3 + s % 2))
    return probs


def run_bucketing(B: int = 64, n_starts: int = 4):
    """Padding-waste reduction + agreement for power-of-two shape buckets."""
    probs = _skewed_fleet(B)
    bucketed = bucket_problems(probs)
    g = padding_stats(probs)
    bk = padding_stats(probs, bucketed)
    cells_saved = 1.0 - bk["padded_cells"] / g["padded_cells"]
    print(f"[bucketing] ragged B={B} fleet "
          f"({len({(int(p.n), int(p.m)) for p in probs})} distinct shapes, "
          f"{bucketed.n_buckets} buckets)")
    print(f"  global pad  : {g['padded_cells']:9.0f} cells, "
          f"{100 * g['waste_frac']:5.1f}% padding waste")
    print(f"  bucketed pad: {bk['padded_cells']:9.0f} cells, "
          f"{100 * bk['waste_frac']:5.1f}% padding waste")
    print(f"  padded-cell reduction: {100 * cells_saved:.1f}%")

    t0 = time.time()
    r_flat = solve_fleet(stack_problems(probs), n_starts=n_starts, cfg=CFG)
    r_flat.fun.block_until_ready()
    t_flat = time.time() - t0
    t0 = time.time()
    r_buck = solve_fleet_bucketed(probs, n_starts=n_starts, cfg=CFG,
                                  bucketed=bucketed)
    t_buck = time.time() - t0
    fi_f, fi_b = np.asarray(r_flat.fun_int), np.asarray(r_buck.fun_int)
    agree = float(np.max(np.abs(fi_f - fi_b) / np.maximum(np.abs(fi_f), 1e-9)))
    print(f"  solve: global {t_flat:.1f}s vs bucketed {t_buck:.1f}s "
          f"({bucketed.n_buckets} compiles), integer-objective agreement "
          f"max rel {agree:.2e}")
    return dict(waste_global=g["waste_frac"], waste_bucketed=bk["waste_frac"],
                padded_cells_global=g["padded_cells"],
                padded_cells_bucketed=bk["padded_cells"],
                cell_reduction=cells_saved, t_flat=t_flat, t_bucketed=t_buck,
                n_buckets=bucketed.n_buckets, agreement_max_rel=agree)


def _tick_split(rec):
    """``(t_compile_s, t_execute_s, report)`` from an instrumented replay's
    recorder. Uses ONLY the ``replay/tick`` spans — they nest every other
    phase, so summing them never double-counts — with the recorder's
    first-call-per-compile-key tagging deciding which ticks carried XLA
    compilation."""
    rep = ReplayReport.from_recorder(rec)
    tick = next((p for p in rep.phases if p.name == "replay/tick"), None)
    if tick is None:
        return 0.0, 0.0, rep
    return tick.compile_ms / 1e3, tick.execute_ms / 1e3, rep


def run_replay(B: int = 64, T: int = 3):
    """End-to-end replay: batched engine vs sequential controller loop.

    Every tenant gets its own catalog slice (a distinct (n,) shape), so the
    sequential loop pays one multistart compile + one incremental-solve
    compile per tenant, while the batched engine compiles once per occupied
    shape bucket and steps the whole fleet per tick. Horizons are RAGGED
    (lengths cycle through T, T-1, ..., 1): finished tenants freeze in their
    batch lanes (active masks) and the engines must still agree.

    Both replays run instrumented (``repro.obs.telemetry``): the reported
    speedup is split into END-TO-END (compile included — what one run of
    this fleet costs) and STEADY-STATE (compile-tagged ticks excluded —
    what every further tick costs), and the batched run's full
    ``ReplayReport`` becomes the BENCH JSON's ``telemetry`` section."""
    full = make_cloud_catalog()
    base = np.array([8.0, 16.0, 4.0, 100.0])
    specs = []
    for s in range(B):
        cat = Catalog(full.instances[s % 7:: 20 + s])  # n ~ 23..94, ragged
        T_s = T - s % T if B >= T else T               # horizons T..1
        specs.append(TenantSpec(
            name=f"t{s:02d}", catalog=cat,
            trace=make_trace("diurnal", base * (0.5 + (s % 5) / 4), T_s,
                             seed=s, amplitude=0.3),
            n_starts=2))
    shapes = {spec.catalog.n for spec in specs}
    ticks = sum(spec.trace.shape[0] for spec in specs)
    print(f"[replay] ragged B={B} fleet, {ticks} tenant-ticks "
          f"(ragged horizons 1..{T}), {len(shapes)} distinct catalog shapes")

    t0 = time.time()
    with telemetry() as rec_b:
        bat = replay_fleet(full, specs, run_ca_baseline=False,
                           replay_mode="batched")
    t_batched = time.time() - t0
    c_b, e_b, rep_b = _tick_split(rec_b)
    print(f"  batched    : {t_batched:7.1f}s "
          f"({ticks / t_batched:6.1f} tenant-ticks/s)  "
          f"[compile {c_b:.1f}s, steady {e_b:.1f}s]")
    t0 = time.time()
    with telemetry() as rec_s:
        seq = replay_fleet(full, specs, run_ca_baseline=False,
                           replay_mode="sequential")
    t_seq = time.time() - t0
    c_s, e_s, rep_s = _tick_split(rec_s)
    print(f"  sequential : {t_seq:7.1f}s "
          f"({ticks / t_seq:6.1f} tenant-ticks/s)  "
          f"[compile {c_s:.1f}s, steady {e_s:.1f}s]")
    speedup = t_seq / t_batched
    speedup_steady = e_s / max(e_b, 1e-9)
    cost_s = seq.metrics.total_cost_integral
    cost_b = bat.metrics.total_cost_integral
    drift = abs(cost_b - cost_s) / max(abs(cost_s), 1e-9)
    print(f"  speedup    : {speedup:.1f}x end-to-end, "
          f"{speedup_steady:.1f}x steady-state   "
          f"(cost integral agreement: {drift:.2e} rel)")
    return dict(t_batched=t_batched, t_sequential=t_seq, speedup=speedup,
                speedup_steady=speedup_steady,
                t_batched_compile=c_b, t_batched_execute=e_b,
                t_sequential_compile=c_s, t_sequential_execute=e_s,
                tenant_ticks=ticks, cost_batched=cost_b,
                cost_sequential=cost_s, cost_rel_drift=drift,
                distinct_shapes=len(shapes),
                telemetry=dict(batched=rep_b.to_dict(),
                               sequential=rep_s.to_dict()))


def run_ca_replay(B: int = 64, T: int = 24):
    """CA baseline replay throughput: vectorized lockstep stepper vs the
    sequential per-tenant simulator loop, one shared catalog (the vectorized
    engine batches per distinct catalog), diurnal+ramp mix over T ticks."""
    cat = Catalog(make_cloud_catalog().instances[::20])
    base = np.array([8.0, 16.0, 4.0, 100.0])
    specs = [TenantSpec(
        name=f"ca{s:02d}",
        trace=make_trace("ramp" if s % 3 else "diurnal",
                         base * (0.5 + (s % 5) / 4), T, seed=s),
        n_starts=2) for s in range(B)]
    ticks = B * T
    print(f"[ca-replay] B={B} fleet, T={T} ticks, catalog n={cat.n}")
    t0 = time.time()
    vec = _replay_ca_fleet(cat, specs, "random", "wave")
    t_vec = time.time() - t0
    print(f"  vectorized : {t_vec:7.1f}s ({ticks / t_vec:7.1f} tenant-ticks/s)")
    t0 = time.time()
    seq = [_ca_baseline(cat, spec, "random", "wave") for spec in specs]
    t_seq = time.time() - t0
    print(f"  sequential : {t_seq:7.1f}s ({ticks / t_seq:7.1f} tenant-ticks/s)")
    cost_v = sum(m.cost_integral for m, _ in vec)
    cost_s = sum(m.cost_integral for m, _ in seq)
    agree = bool(all(np.array_equal(cv, cs) for (_, cv), (_, cs)
                     in zip(vec, seq)))
    print(f"  speedup    : {t_seq / t_vec:.1f}x   "
          f"(final counts identical: {agree})")
    assert abs(cost_v - cost_s) <= 1e-9 * max(abs(cost_s), 1.0)
    return dict(t_vectorized=t_vec, t_sequential=t_seq,
                speedup=t_seq / t_vec, tenant_ticks=ticks,
                ticks_per_s_vectorized=ticks / t_vec,
                ticks_per_s_sequential=ticks / t_seq,
                counts_identical=agree, cost_integral=cost_v)


def main(argv):
    quick = "--quick" in argv
    json_path = DEFAULT_JSON
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            raise SystemExit("--json requires a path argument")
        json_path = argv[i + 1]
    B = 16 if quick else 64
    out = run(B=B)
    out["config"] = dict(quick=quick, B=B)
    # trace seeds are the tenant indices (see run_*'s spec construction);
    # config rides into the digest so bench_compare refuses quick-vs-full
    out["provenance"] = provenance_block(argv, config=out["config"],
                                         seeds=list(range(B)))
    with open(json_path, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"\n[json] wrote {json_path}")


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    main(sys.argv[1:])
