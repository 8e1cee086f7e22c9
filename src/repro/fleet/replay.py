"""Trace-driven replay: step every tenant's controller through its demand
trace, and (optionally) run the Cluster-Autoscaler baseline on the SAME
traces — the SLO/cost evaluation loop the static paper scenarios lack.

The optimizer side uses the production control loop
(InfrastructureOptimizationController): warm-started incremental solves with
bounded churn. The CA side carries its node counts tick to tick exactly like
the real autoscaler (scale-up on unschedulable demand, utilization-gated
scale-down).

Two replay engines drive the optimizer side (``replay_mode``):

* ``"sequential"`` — the reference loop: one controller solve per tenant per
  tick. Pays one XLA program dispatch (and, for ragged fleets, one compile
  per distinct tenant shape) per tenant per tick.
* ``"batched"`` — the fleet engine: tenants are grouped into power-of-two
  shape buckets (``repro.fleet.batching.bucket_problems`` dims), and each
  tick runs ONE ``solve_fleet`` call per bucket for the cold start and ONE
  ``solve_fleet_step`` call per bucket for every warm tick, warm-started
  from the previous tick's batched solution. Per-tenant problems, starts,
  warm starts and churn bounds are identical to the sequential engine, so
  per-tenant integer allocations (hence objectives and metrics) match the
  sequential path on CPU — see tests/fleet/test_replay.py.

Traces may be RAGGED (different per-tenant lengths): the batched engine
keeps every tenant in its batch lane for the full fleet horizon but marks
expired tenants frozen via a per-tenant active mask (``FleetBatch.active``).
Frozen rows keep their last allocation as a fixed warm start, are returned
untouched by ``solve_fleet_step``, and contribute no further churn, cost or
SLO metrics — exactly like a sequential replay that simply stops stepping
that tenant at the end of its trace.

Controller state (counts, churn, history, metrics) lives in the SAME
per-tenant ``InfrastructureOptimizationController`` objects in both modes;
the batched engine just computes the counts centrally and feeds them back
via ``controller.apply_counts``. See docs/fleet.md for the full contract.

Both engines can also drive the receding-horizon controller
(``controller="mpc"``, ``repro.horizon``): each tick forecasts ``horizon``
ticks, solves one time-expanded convex program, and commits only tick 0.
The batched MPC engine issues one ``solve_horizon_fleet_step`` per shape
bucket per warm tick — the same grouping, cold start and ragged-horizon
freezing as the myopic batched engine. See docs/horizon.md.

The CA baseline sizes each tenant's node pools from the trace's PER-RESOURCE
PEAK demand (``trace.max(axis=0)``) — sizing from any single tick would hand
the baseline a pool set that cannot schedule the peak of a ramp or flash
crowd, producing structurally-unsatisfiable ticks that unfairly inflate
``cost_savings_vs_baseline_pct``. By default the whole baseline fleet is
replayed by the vectorized lockstep stepper
(``simulate_cluster_autoscaler_batch``, one tenant-batched numpy program per
tick per distinct catalog); ``ca_engine="sequential"`` keeps the per-tenant
oracle loop, and the two agree tick-for-tick.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.autoscaler import (default_pools_for,
                                   simulate_cluster_autoscaler,
                                   simulate_cluster_autoscaler_batch)
from repro.core.catalog import Catalog
from repro.core.catalog import M as RESOURCE_DIM
from repro.core.controller import (ControllerStep,
                                   InfrastructureOptimizationController)
from repro.core.metrics import AllocationMetrics, evaluate
from repro.core.pgd import AnytimeConfig
from repro.core.problem import PenaltyParams
from repro.obs import metrics as obs_metrics
from repro.obs.health import HealthMonitor
from repro.obs.telemetry import gauge, span

from .batching import (bucket_dims, embed_solutions, stack_problems,
                       union_term_kinds)
from .metrics import FleetReplayMetrics, TenantReplayMetrics, tenant_metrics
from .solver import make_fleet_starts, solve_fleet, solve_fleet_step


@dataclass
class TenantSpec:
    """One tenant cluster: a demand trace plus its controller knobs.

    The trace is validated at construction (2-D, at least one tick, and its
    resource columns matching the catalog's resource dim) so a malformed
    spec fails HERE with a clear message instead of deep inside the solver
    with an opaque broadcast error."""

    name: str
    trace: np.ndarray                            # (T, m) demand per tick
    delta_max: float = 8.0                       # max L1 churn per tick
    n_starts: int = 4
    params: Optional[PenaltyParams] = None
    allowed_idx: Optional[np.ndarray] = None     # approved instance types
    catalog: Optional[Catalog] = None            # overrides the fleet catalog
    ca_pool_idx: Optional[np.ndarray] = None     # CA node pools (default: the
                                                 # cheapest covering types)
    # scenario surface (repro.core.terms / docs/scenarios.md): ``terms`` is a
    # static tuple of scenario-term specs (PricedTerm or (kind, params))
    # attached to every tick's problem; the spot pair drives the per-tick
    # availability overlay — ``spot_availability`` row t zeroes the
    # interrupted ``spot_idx`` types' capacity (mask/ub/lb) for that tick.
    terms: tuple = ()
    spot_idx: Optional[np.ndarray] = None        # (S,) catalog spot-twin idx
    spot_availability: Optional[np.ndarray] = None   # (T', S) in {0, 1}

    def __post_init__(self) -> None:
        """Fail fast on malformed traces (see class docstring)."""
        trace = np.asarray(self.trace)
        if trace.ndim != 2:
            raise ValueError(
                f"TenantSpec {self.name!r}: trace must be a 2-D (T, m) array "
                f"of per-tick demand, got shape {trace.shape}")
        if trace.shape[0] < 1:
            raise ValueError(
                f"TenantSpec {self.name!r}: trace must have at least one "
                f"tick, got shape {trace.shape}")
        # every Catalog lowers to K with one row per RESOURCES entry; a
        # tenant catalog (if any) decides, else the fleet catalog — both
        # share the global resource convention
        m = (self.catalog.matrices()[0].shape[0]
             if self.catalog is not None else RESOURCE_DIM)
        if trace.shape[1] != m:
            raise ValueError(
                f"TenantSpec {self.name!r}: trace has {trace.shape[1]} "
                f"resource columns but the catalog's resource dim is {m} "
                f"(demand rows must be ordered like "
                f"repro.core.catalog.RESOURCES)")
        if (self.spot_idx is None) != (self.spot_availability is None):
            raise ValueError(
                f"TenantSpec {self.name!r}: spot_idx and spot_availability "
                f"must be given together (the availability overlay needs "
                f"both the spot-twin indices and their on/off trace)")
        if self.spot_availability is not None:
            avail = np.asarray(self.spot_availability)
            n_spot = len(np.asarray(self.spot_idx))
            if avail.ndim != 2 or avail.shape[1] != n_spot:
                raise ValueError(
                    f"TenantSpec {self.name!r}: spot_availability must be a "
                    f"2-D (T', S) array with S == len(spot_idx) == {n_spot}, "
                    f"got shape {avail.shape} (make it with "
                    f"make_trace('spot_interruption', ...))")


@dataclass
class TenantReplay:
    """One tenant's replayed history plus its aggregated metrics."""

    spec: TenantSpec
    steps: List[ControllerStep]
    metrics: TenantReplayMetrics
    ca_metrics: Optional[TenantReplayMetrics] = None
    ca_counts: Optional[np.ndarray] = None       # final CA allocation


@dataclass
class FleetReplayResult:
    """Everything a replay produced: per-tenant histories + fleet rollup.

    ``solver_traces`` is None unless the replay ran with
    ``capture_solver_trace=True``: one list per tenant holding that
    tenant's per-WARM-tick PGD convergence rows (``core.pgd.PGDTrace``,
    numpy leaves; cold ticks run the multistart solver, which is not
    traced). Both engines and both controllers fill it the same way — see
    ``repro.obs.solver_trace`` for the schema and analysis helpers."""

    tenants: List[TenantReplay]
    metrics: FleetReplayMetrics
    solver_traces: Optional[List[List]] = None


def default_ca_pools(catalog: Catalog, demand: np.ndarray,
                     k: int = 8) -> np.ndarray:
    """The k most cost-efficient single-type covers of ``demand`` — the node
    pools an operator would plausibly configure for this workload.

    For trace replays, ``demand`` must be the trace's PER-RESOURCE PEAK
    (``trace.max(axis=0)``), not a single tick: an operator provisions pools
    for the load they expect, and sizing from e.g. the first tick of a ramp
    leaves the baseline structurally unable to schedule the peak — phantom
    SLO violations that would inflate the optimizer's reported savings."""
    K, _, c = catalog.matrices()
    d = np.asarray(demand, np.float64)
    safe_K = np.where(K > 0, K, 1e-9)
    cover = np.max(d[:, None] / safe_K, axis=0)          # units of each type
    covers_all = np.all((K > 0) | (d[:, None] == 0), axis=0)
    cost = np.where(covers_all, cover * c, np.inf)
    order = np.argsort(cost)
    return order[: min(k, int(np.isfinite(cost).sum()))]


def _replay_ca(catalog: Catalog, spec: TenantSpec, pool_idx: np.ndarray,
               expander: str, mode: str):
    """Carry the Cluster-Autoscaler baseline tick to tick over one trace."""
    K, _, _ = catalog.matrices()
    counts_prev = np.zeros(catalog.n, np.float64)
    tick_metrics: List[AllocationMetrics] = []
    churns: List[float] = []
    for demand in np.asarray(spec.trace, np.float64):
        existing = {int(j): int(counts_prev[j])
                    for j in np.nonzero(counts_prev)[0]}
        pools = default_pools_for(catalog, pool_idx, existing=existing)
        res = simulate_cluster_autoscaler(catalog, pools, demand,
                                          expander=expander, mode=mode)
        churns.append(float(np.abs(res.counts - counts_prev).sum()))
        counts_prev = res.counts
        tick_metrics.append(evaluate(catalog, res.counts, demand))
    return tick_metrics, churns, counts_prev


def _ca_pool_idx(cat: Catalog, spec: TenantSpec) -> np.ndarray:
    """The tenant's CA node-pool types: explicit ``ca_pool_idx``, else pools
    sized from the trace's per-resource peak demand (the bugfixed default —
    see :func:`default_ca_pools`)."""
    if spec.ca_pool_idx is not None:
        return spec.ca_pool_idx
    return default_ca_pools(cat, np.asarray(spec.trace, np.float64).max(axis=0))


def _ca_baseline(catalog: Catalog, spec: TenantSpec, ca_expander: str,
                 ca_mode: str):
    """Run the sequential-oracle CA baseline for one tenant."""
    cat = spec.catalog or catalog
    tick_metrics, churns, ca_counts = _replay_ca(
        cat, spec, _ca_pool_idx(cat, spec), ca_expander, ca_mode)
    return tenant_metrics(f"{spec.name}/ca", tick_metrics, churns), ca_counts


def _replay_ca_fleet(catalog: Catalog, tenants: Sequence[TenantSpec],
                     expander: str, mode: str):
    """Vectorized CA baseline replay: carry ALL tenants' pool counts tick to
    tick at once.

    Tenants are grouped by (shared) catalog; each group advances through one
    :func:`simulate_cluster_autoscaler_batch` call per tick — the per-tick
    deficit/feasibility linear algebra is one numpy matmul over the group
    instead of a Python loop of per-tenant matvecs. Ragged traces are
    supported: a tenant leaves its group's active set when its trace ends.
    Results are tick-for-tick identical to the sequential per-tenant loop
    (``ca_engine="sequential"``), which stays the test oracle.

    Returns one ``(TenantReplayMetrics, final_counts)`` pair per tenant."""
    cats = [spec.catalog or catalog for spec in tenants]
    groups: Dict[int, List[int]] = {}
    for i, cat in enumerate(cats):
        groups.setdefault(id(cat), []).append(i)
    out: List = [None] * len(tenants)
    for idx in groups.values():
        cat = cats[idx[0]]
        traces = [np.asarray(tenants[i].trace, np.float64) for i in idx]
        pool_idx = [_ca_pool_idx(cat, tenants[i]) for i in idx]
        counts = np.zeros((len(idx), cat.n), np.float64)
        tick_metrics: List[List[AllocationMetrics]] = [[] for _ in idx]
        churns: List[List[float]] = [[] for _ in idx]
        for t in range(max(tr.shape[0] for tr in traces)):
            act = [k for k, tr in enumerate(traces) if t < tr.shape[0]]
            demands = np.stack([traces[k][t] for k in act])
            pools_t = []
            for k in act:
                existing = {int(j): int(counts[k, j])
                            for j in np.nonzero(counts[k])[0]}
                pools_t.append(default_pools_for(cat, pool_idx[k],
                                                 existing=existing))
            res = simulate_cluster_autoscaler_batch(cat, pools_t, demands,
                                                    expander=expander,
                                                    mode=mode)
            for k, r in zip(act, res):
                churns[k].append(float(np.abs(r.counts - counts[k]).sum()))
                counts[k] = r.counts
                tick_metrics[k].append(evaluate(cat, r.counts, traces[k][t]))
        for pos, i in enumerate(idx):
            out[i] = (tenant_metrics(f"{tenants[i].name}/ca",
                                     tick_metrics[pos], churns[pos]),
                      counts[pos].copy())
    return out


def _make_controller(catalog: Catalog, spec: TenantSpec
                     ) -> InfrastructureOptimizationController:
    return InfrastructureOptimizationController(
        catalog=spec.catalog or catalog, delta_max=spec.delta_max,
        params=spec.params, n_starts=spec.n_starts,
        allowed_idx=spec.allowed_idx, terms=spec.terms,
        spot_idx=spec.spot_idx, spot_availability=spec.spot_availability)


def _make_mpc_controller(catalog: Catalog, spec: TenantSpec, *, horizon: int,
                         forecaster: str, forecaster_kwargs: Optional[dict],
                         coupling_w: float, coupling_eps: float,
                         solver_steps: int, solver_config=None,
                         cold_start: str = "myopic"):
    """Build one tenant's receding-horizon controller (the MPC counterpart
    of :func:`_make_controller`); the forecaster gets the tenant's own trace
    so ``forecaster="oracle"`` reads that tenant's future. ``solver_config``
    (a ``repro.horizon.HorizonSolverConfig``) configures the per-tick
    engine; when None the controller builds one from ``solver_steps``.

    repro.horizon is imported lazily: it reuses ``repro.fleet.batching`` for
    window stacking, so a module-level import here would be circular."""
    from repro.horizon import ModelPredictiveController, make_forecaster
    fc = make_forecaster(forecaster,
                         trace=np.asarray(spec.trace, np.float64),
                         **(forecaster_kwargs or {}))
    return ModelPredictiveController(
        catalog=spec.catalog or catalog, delta_max=spec.delta_max,
        params=spec.params, n_starts=spec.n_starts,
        allowed_idx=spec.allowed_idx, terms=spec.terms,
        spot_idx=spec.spot_idx, spot_availability=spec.spot_availability,
        horizon=horizon, forecaster=fc,
        coupling_w=coupling_w, coupling_eps=coupling_eps,
        solver_steps=solver_steps, solver_config=solver_config,
        cold_start=cold_start)


def _assemble_replay(spec: TenantSpec, steps: List[ControllerStep],
                     ca: Optional[Tuple]) -> TenantReplay:
    """Roll one tenant's step history (plus a precomputed CA baseline
    ``(metrics, counts)`` pair, or None) into a TenantReplay — shared by
    both replay engines."""
    met = tenant_metrics(spec.name, [s.metrics for s in steps],
                         [s.churn for s in steps],
                         churn_violations=[s.churn_violation for s in steps],
                         solver_iters=[s.solver_iters for s in steps])
    ca_met, ca_counts = ca if ca is not None else (None, None)
    return TenantReplay(spec=spec, steps=steps, metrics=met,
                        ca_metrics=ca_met, ca_counts=ca_counts)


def replay_tenant(catalog: Catalog, spec: TenantSpec, *,
                  run_ca_baseline: bool = True,
                  ca_expander: str = "random",
                  ca_mode: str = "wave") -> TenantReplay:
    """Sequential reference replay of ONE tenant: a controller solve per tick
    plus (optionally) the CA baseline on the same trace."""
    ctl = _make_controller(catalog, spec)
    steps = [ctl.step(demand) for demand in np.asarray(spec.trace, np.float64)]
    ca = (_ca_baseline(catalog, spec, ca_expander, ca_mode)
          if run_ca_baseline else None)
    return _assemble_replay(spec, steps, ca)


def _spot_unavailable(spec: TenantSpec, t: int) -> int:
    """Number of this tenant's spot twins interrupted at tick ``t`` (the
    same clamped-row convention the controller's spot overlay uses)."""
    if spec.spot_idx is None or spec.spot_availability is None:
        return 0
    avail = np.asarray(spec.spot_availability)
    return int((avail[min(t, len(avail) - 1)] <= 0.0).sum())


class _TickObserver:
    """Shared per-tick observation plumbing for the three replay loops:
    decides once whether anything is watching (a :class:`HealthMonitor`
    and/or an installed ``repro.obs.metrics`` registry), times ticks with
    the monitor's injectable clock, and fans each tick's duration and
    iteration count out to both sinks. When nothing is watching, every
    method is a cheap no-op and NO clock is ever read — the engines'
    production paths are unchanged (the bit-identical on/off contract)."""

    __slots__ = ("health", "reg", "clock", "active", "_t0")

    def __init__(self, health: Optional[HealthMonitor]):
        self.health = health
        self.reg = obs_metrics.current_metrics()
        self.clock = health.clock if health is not None else time.perf_counter
        self.active = health is not None or self.reg is not None
        self._t0 = 0.0

    def tick_start(self) -> None:
        """Stamp the tick's start time (no-op when nothing watches)."""
        if self.active:
            self._t0 = self.clock()

    def tick_end(self, t: int, solver_iters: int,
                 compile_key=None) -> None:
        """Close the tick: duration to the latency histogram + deadline
        budget, iteration count to the effort histogram. ``compile_key``
        (the engine's tick-span compile key) lets the health monitor split
        first-sighting compile time out of the deadline budget instead of
        flagging every first warm tick after a jit cache miss as a miss."""
        if not self.active:
            return
        dur_ms = (self.clock() - self._t0) * 1e3
        if self.reg is not None:
            self.reg.histogram("replay/tick_ms").observe(dur_ms)
            self.reg.histogram("replay/solver_iters").observe(solver_iters)
        if self.health is not None:
            self.health.observe_tick(t, dur_ms, compile_key=compile_key)

    def step(self, **kw) -> None:
        """Forward one committed (tenant, tick) to the health monitor."""
        if self.health is not None:
            self.health.observe_step(**kw)


def _replay_sequential(ctls, tenants: Sequence[TenantSpec], controller: str,
                       capture_solver_trace: bool,
                       health: Optional[HealthMonitor] = None,
                       anytime: Optional[AnytimeConfig] = None):
    """The instrumented sequential loop shared by both controllers: one
    ``replay/tick`` span per (tenant, tick), warm ticks optionally tracing
    the solver through the controller's ``capture_solver_trace`` flag.
    Returns ``(histories, solver_traces)`` like the batched engines.

    With a :class:`HealthMonitor` attached, each (tenant, tick) is timed
    (per-TENANT tick — the sequential engine has no fleet tick) and
    observed: the tick's problem is built up front (``make_problem`` is
    pure and history has not advanced yet, so it is THE problem ``step``
    solves) and the controller's ``last_x_rel`` feeds the KKT gauge."""
    histories, solver_traces = [], []
    obs = _TickObserver(health)
    for ctl, spec in zip(ctls, tenants):
        ctl.capture_solver_trace = capture_solver_trace
        ctl.anytime = anytime
        steps = []
        for t, demand in enumerate(np.asarray(spec.trace, np.float64)):
            prob = ctl.make_problem(demand) if health is not None else None
            n_tr = len(ctl.solver_traces)
            obs.tick_start()
            # compile key: the cold (t=0) and warm programs compile
            # separately, per problem shape and per traced/untraced variant
            # (and per anytime on/off — the chunked driver is its own program)
            tick_key = ("seq_tick", controller, ctl.catalog.n, t > 0,
                        capture_solver_trace,
                        anytime is not None and anytime.enabled)
            with span("replay/tick", cat="replay", tick=t,
                      engine="sequential", controller=controller,
                      tenant=spec.name, compile_key=tick_key):
                step = ctl.step(demand)
                steps.append(step)
            obs.tick_end(t, step.solver_iters, compile_key=tick_key)
            gauge("replay/solver_iters", step.solver_iters)
            solver = ("multistart" if step.replanned
                      else ctl.solver_config.solver if controller == "mpc"
                      else "adaptive")
            obs.step(tenant=spec.name, tick=t, step=step, solver=solver,
                     prob=prob, x_rel=ctl.last_x_rel,
                     trace=(ctl.solver_traces[-1]
                            if len(ctl.solver_traces) > n_tr else None),
                     spot_unavailable=_spot_unavailable(spec, t))
        histories.append(steps)
        solver_traces.append(list(ctl.solver_traces))
    return histories, solver_traces


# ---------------------------------------------------------------------------
# batched fleet engine
# ---------------------------------------------------------------------------


def _replay_batch_groups(ctls: Sequence[InfrastructureOptimizationController],
                         tenants: Sequence[TenantSpec]
                         ) -> Dict[Tuple, List[int]]:
    """Group tenant indices by (shape bucket, n_starts).

    Tenant shapes are tick-invariant (the catalog fixes (n, m, p); demand
    normalization rescales K but never reshapes it), so grouping happens once
    per replay. ``n_starts`` joins the key because cold-start stacking needs
    a uniform (B, S, n) start tensor per group."""
    groups: Dict[Tuple, List[int]] = {}
    for b, (ctl, spec) in enumerate(zip(ctls, tenants)):
        cat = ctl.catalog
        key = bucket_dims(cat.n, len(cat.matrices()[0]),
                          len(cat.providers)) + (spec.n_starts,)
        groups.setdefault(key, []).append(b)
    return groups


def _replay_fleet_batched(catalog: Catalog, tenants: Sequence[TenantSpec], *,
                          warm_start: str = "counts",
                          solver_steps: int = 600,
                          hot_loop: Optional[str] = None,
                          capture_solver_trace: bool = False,
                          health: Optional[HealthMonitor] = None,
                          anytime: Optional[AnytimeConfig] = None):
    """Step ALL tenants through their traces with one batched solve per shape
    bucket per tick. Returns ``(histories, solver_traces)``: per-tenant step
    histories (controller objects hold the same state the sequential engine
    would leave behind) and — with ``capture_solver_trace`` — each tenant's
    per-warm-tick PGD convergence rows (else empty lists).

    Horizons may be RAGGED: the fleet runs for ``max_b T_b`` ticks, and a
    tenant whose trace ends freezes in place. Its batch lane persists (so
    bucket shapes — and compiled programs — never change mid-replay), holding
    the last allocation as a fixed warm start; ``solve_fleet_step`` returns
    frozen rows untouched (``FleetBatch.active``), no ``apply_counts`` is
    recorded, and its history stops at exactly ``T_b`` steps — identical to
    a sequential replay of that tenant alone.

    Telemetry (``repro.obs``): each tick is a ``replay/tick`` span wrapping
    a ``replay/build`` span (every tenant's problem) and per-bucket
    ``replay/stack`` (stacking, start points, churn bounds) /
    ``replay/solve`` / ``replay/round`` spans;
    solve spans carry a compile key per (program, bucket shape) so first
    calls are tagged as compile time. A :class:`HealthMonitor` additionally
    observes every committed (tenant, tick) — counts, relaxed solution for
    the KKT gauge, trace for stall detection — and the FLEET tick's
    duration against the deadline budget. Spans, metrics and health only
    measure — allocations are bit-identical with observability on or off
    (test-enforced)."""
    assert warm_start in ("counts", "relaxed"), warm_start
    assert len(tenants) > 0, "empty fleet"
    traces = [np.asarray(spec.trace, np.float64) for spec in tenants]
    assert all(tr.shape[0] >= 1 for tr in traces), "empty trace"
    T_len = np.asarray([tr.shape[0] for tr in traces])

    ctls = [_make_controller(catalog, spec) for spec in tenants]
    groups = _replay_batch_groups(ctls, tenants)
    # previous tick's RELAXED batched solution per tenant (warm_start="relaxed")
    x_rel_prev: List[Optional[np.ndarray]] = [None] * len(tenants)
    # per-tenant problem of the CURRENT tick; frozen tenants keep their last
    # one so stacked shapes stay put (its solve result is discarded)
    probs: List = [None] * len(tenants)
    solver_traces: List[List] = [[] for _ in tenants]
    obs = _TickObserver(health)

    for t in range(int(T_len.max())):
        obs.tick_start()
        # ticks 0 (cold program) and 1 (warm program) each trigger an XLA
        # compile; min(t, 1) makes exactly those two first-seen (tagged
        # phase="compile"), so tick percentiles reflect steady state
        tick_key = ("tick", "batched", "myopic", min(t, 1))
        with span("replay/tick", cat="replay", tick=t, engine="batched",
                  controller="myopic", compile_key=tick_key):
            tick_iters = 0
            with span("replay/build", cat="replay"):
                for b, ctl in enumerate(ctls):
                    if t < T_len[b]:
                        probs[b] = ctl.make_problem(traces[b][t])
            for key, idx in sorted(groups.items()):
                n_pad, m_pad, p_pad, n_starts = key
                active = T_len[idx] > t                 # (Bk,) liveness
                if not active.any():
                    continue    # whole bucket expired: nothing left to solve
                with span("replay/stack", cat="replay", bucket=str(key)):
                    batch = stack_problems([probs[b] for b in idx],
                                           n_max=n_pad, m_max=m_pad,
                                           p_max=p_pad, active=active)
                    if t > 0:
                        X_cur = embed_solutions(
                            batch, [ctls[b].x_current for b in idx])
                        X_init = None
                        if (warm_start == "relaxed"
                                and x_rel_prev[idx[0]] is not None):
                            X_init = embed_solutions(
                                batch, [x_rel_prev[b] for b in idx])
                        delta = np.asarray(
                            [tenants[b].delta_max for b in idx], np.float32)
                if t == 0:
                    # cold start: one batched multistart solve for the
                    # bucket, per-tenant starts drawn at true shape (seed 0,
                    # as the sequential controller's multistart_solve does).
                    # Every tenant is live at t=0 (traces are non-empty).
                    with span("replay/solve", cat="replay", bucket=str(key),
                              compile_key=("solve_fleet", key, len(idx)),
                              cold=True) as sp:
                        starts = make_fleet_starts(batch, n_starts, seed=0)
                        res = solve_fleet(batch, starts=starts,
                                          hot_loop=hot_loop)
                        sp.fence(res.x_int)
                    X_int = np.asarray(res.x_int, np.float64)
                    lane_iters = np.zeros(len(idx), np.int64)
                    tick_iters += int(res.iters)
                    bucket_hit = False
                else:
                    with span("replay/solve", cat="replay", bucket=str(key),
                              compile_key=("solve_fleet_step", key, len(idx),
                                           capture_solver_trace,
                                           anytime is not None
                                           and anytime.enabled)) as sp:
                        res = solve_fleet_step(
                            batch, X_cur, delta, x_init=X_init,
                            steps=solver_steps,
                            capture_trace=capture_solver_trace,
                            anytime=anytime)
                        sp.fence(res.x_int)
                    X_int = np.asarray(res.x_int, np.float64)
                    lane_iters = np.asarray(res.iters, np.int64)
                    tick_iters += int(lane_iters.sum())
                    bucket_hit = bool(res.deadline_hit or False)
                # only pay the relaxed-solution transfer when it will be
                # used (warm start or the health monitor's KKT gauge)
                X_rel = (np.asarray(res.x)
                         if warm_start == "relaxed" or health is not None
                         else None)
                # cold-start FleetSolveResult has no trace field; warm ticks
                # carry one only when capture_solver_trace asked for it
                batch_tr = getattr(res, "trace", None)
                lane_tr = (None if batch_tr is None
                           else [np.asarray(f) for f in batch_tr])
                with span("replay/round", cat="replay", bucket=str(key)):
                    for i, b in enumerate(idx):
                        if not active[i]:
                            continue  # frozen: no churn, no metrics, no state
                        n_true = int(batch.n_true[i])
                        step = ctls[b].apply_counts(
                            traces[b][t], X_int[i, :n_true],
                            replanned=(t == 0),
                            solver_iters=int(lane_iters[i]),
                            deadline_hit=bucket_hit)
                        tr_b = (None if lane_tr is None else
                                type(batch_tr)(*(f[i] for f in lane_tr)))
                        if tr_b is not None:
                            solver_traces[b].append(tr_b)
                        if X_rel is not None and warm_start == "relaxed":
                            x_rel_prev[b] = X_rel[i, :n_true]
                        obs.step(tenant=tenants[b].name, tick=t, step=step,
                                 solver=("multistart" if t == 0
                                         else "adaptive"),
                                 lane=i, prob=probs[b],
                                 x_rel=(None if X_rel is None
                                        else X_rel[i, :n_true]),
                                 trace=tr_b,
                                 spot_unavailable=_spot_unavailable(
                                     tenants[b], t))
            gauge("replay/solver_iters", tick_iters)
        obs.tick_end(t, tick_iters, compile_key=tick_key)
    return [ctl.history for ctl in ctls], solver_traces


def _replay_fleet_batched_mpc(catalog: Catalog, tenants: Sequence[TenantSpec],
                              *, horizon: int, forecaster: str,
                              forecaster_kwargs: Optional[dict],
                              coupling_w: float, coupling_eps: float,
                              solver_steps: int, solver_config=None,
                              cold_start: str = "myopic",
                              hot_loop: Optional[str] = None,
                              capture_solver_trace: bool = False,
                              health: Optional[HealthMonitor] = None,
                              anytime: Optional[AnytimeConfig] = None):
    """Batched receding-horizon replay: one ``solve_horizon_fleet_step``
    call per shape bucket per warm tick, the fleet analogue of
    ``ModelPredictiveController.step``. Returns ``(histories,
    solver_traces)`` exactly like :func:`_replay_fleet_batched`, and emits
    the same ``replay/*`` telemetry spans.

    Mirrors :func:`_replay_fleet_batched` exactly where the two overlap:
    the same (bucket, n_starts) grouping, the same ``solve_fleet`` cold
    start (the MPC cold tick IS the myopic cold tick — no allocation means
    no churn to plan around; with ``cold_start="window"`` the same solve's
    per-start rounded candidates are re-ranked by each tenant's whole
    window, exactly like the sequential controller), and the same
    ragged-horizon freezing. The warm tick stacks each live tenant's H-tick
    window (observed demand + forecasts) padded to its bucket's dims,
    solves all lanes in one jitted vmapped program (engine and budget from
    ``solver_config``), commits tick 0 via ``apply_counts`` with the lane's
    iteration count, and stores each lane's relaxed plan back on its
    controller for the next tick's shifted warm start. Per-tenant integer
    allocations match the sequential MPC engine on CPU (test-enforced),
    forecaster state included — forecasts depend only on the observed
    trace, never on solver output."""
    import jax
    import jax.numpy as jnp

    from repro.horizon import (HorizonProblem, select_window_candidate,
                               solve_horizon_fleet_step,
                               window_candidate_scores)

    assert len(tenants) > 0, "empty fleet"
    traces = [np.asarray(spec.trace, np.float64) for spec in tenants]
    T_len = np.asarray([tr.shape[0] for tr in traces])

    ctls = [_make_mpc_controller(catalog, spec, horizon=horizon,
                                 forecaster=forecaster,
                                 forecaster_kwargs=forecaster_kwargs,
                                 coupling_w=coupling_w,
                                 coupling_eps=coupling_eps,
                                 solver_steps=solver_steps,
                                 solver_config=solver_config,
                                 cold_start=cold_start)
            for spec in tenants]
    groups = _replay_batch_groups(ctls, tenants)
    # each live tenant's CURRENT window of per-tick problems; frozen tenants
    # keep their last one so stacked shapes stay put (results discarded)
    windows: List = [None] * len(tenants)
    solver_traces: List[List] = [[] for _ in tenants]
    obs = _TickObserver(health)
    solver_name = ctls[0].solver_config.solver

    for t in range(int(T_len.max())):
      obs.tick_start()
      # same compile-tick tagging rationale as the myopic engine above
      tick_key = ("tick", "batched", "mpc", min(t, 1))
      with span("replay/tick", cat="replay", tick=t, engine="batched",
                controller="mpc", compile_key=tick_key):
        tick_iters = 0
        for b, ctl in enumerate(ctls):
            if t < T_len[b]:
                windows[b] = ctl.window_problems(
                    ctl.window_demands(traces[b][t]))
        for key, idx in sorted(groups.items()):
            n_pad, m_pad, p_pad, n_starts = key
            active = T_len[idx] > t
            if not active.any():
                continue
            if t == 0:
                # cold start: identical to the myopic batched engine (and to
                # a sequential cold_start_counts call per tenant); with
                # cold_start="window" the SAME per-start rounded candidates
                # are re-ranked by each tenant's whole-window objective at
                # its true shape (matching the sequential controller's
                # cold_window_counts selection exactly)
                with span("replay/stack", cat="replay", bucket=str(key)):
                    batch = stack_problems([windows[b][0] for b in idx],
                                           n_max=n_pad, m_max=m_pad,
                                           p_max=p_pad, active=active)
                with span("replay/solve", cat="replay", bucket=str(key),
                          compile_key=("solve_fleet", key, len(idx)),
                          cold=True) as sp:
                    starts = make_fleet_starts(batch, n_starts, seed=0)
                    res = solve_fleet(batch, starts=starts, hot_loop=hot_loop)
                    sp.fence(res.x_int)
                tick_iters += int(res.iters)
                X_int = np.asarray(res.x_int, np.float64)
                X_rel = np.asarray(res.x) if health is not None else None
                cand_all = np.asarray(res.x_int_all, np.float64)
                feas_all = np.asarray(res.feas_int_all, bool)
                with span("replay/round", cat="replay", bucket=str(key)):
                    for i, b in enumerate(idx):
                        n_true = int(batch.n_true[i])
                        if cold_start == "window":
                            cands = cand_all[i, :, :n_true]
                            scores = window_candidate_scores(windows[b],
                                                             cands)
                            x = cands[select_window_candidate(scores,
                                                              feas_all[i])]
                        else:
                            x = X_int[i, :n_true]
                        step = ctls[b].apply_counts(traces[b][t], x,
                                                    replanned=True)
                        ctls[b].plan = np.tile(x, (horizon, 1))
                        obs.step(tenant=tenants[b].name, tick=t, step=step,
                                 solver="multistart", lane=i,
                                 prob=windows[b][0],
                                 x_rel=(None if X_rel is None
                                        else X_rel[i, :n_true]),
                                 spot_unavailable=_spot_unavailable(
                                     tenants[b], t))
                continue
            # warm tick: stack each tenant's H-tick window at the bucket's
            # pad dims, then one vmapped horizon solve for the whole bucket.
            # Every per-tenant stack is forced to the BUCKET's union term
            # signature (absent tenants get exact-no-op zero params) so the
            # window pytrees share one treedef and tree_map can batch them.
            with span("replay/stack", cat="replay", bucket=str(key)):
                kinds = union_term_kinds([windows[b][0] for b in idx])
                stacked = [stack_problems(windows[b], n_max=n_pad,
                                          m_max=m_pad, p_max=p_pad,
                                          term_kinds=kinds).problem
                           for b in idx]
                prob_bh = jax.tree_util.tree_map(
                    lambda *leaves: jnp.stack(leaves), *stacked)
                X_cur = np.zeros((len(idx), n_pad), np.float32)
                X_init = np.zeros((len(idx), horizon, n_pad), np.float32)
                for i, b in enumerate(idx):
                    n_true = ctls[b].catalog.n
                    X_cur[i, :n_true] = ctls[b].x_current
                    X_init[i, :, :n_true] = ctls[b].shifted_plan()
            delta = np.asarray([tenants[b].delta_max for b in idx],
                               np.float32)
            hp = HorizonProblem(
                problem=prob_bh,
                coupling_w=jnp.asarray(coupling_w, jnp.float32),
                coupling_eps=jnp.asarray(coupling_eps, jnp.float32))
            # every controller in the replay shares one resolved config
            # (built in __post_init__ when solver_config was None)
            with span("replay/solve", cat="replay", bucket=str(key),
                      compile_key=("solve_horizon_fleet_step", key, len(idx),
                                   horizon, capture_solver_trace,
                                   anytime is not None
                                   and anytime.enabled)) as sp:
                res = solve_horizon_fleet_step(
                    hp, X_cur, delta, x_init=X_init, active=active,
                    cfg=ctls[idx[0]].solver_config,
                    capture_trace=capture_solver_trace,
                    anytime=anytime)
                sp.fence(res.x_int)
            X_int = np.asarray(res.x_int, np.float64)
            plans = np.asarray(res.plan, np.float64)
            lane_iters = np.asarray(res.iters, np.int64)
            tick_iters += int(lane_iters.sum())
            bucket_hit = bool(res.deadline_hit or False)
            lane_tr = (None if res.trace is None
                       else [np.asarray(f) for f in res.trace])
            diag_np = (None if res.diag is None
                       else [np.asarray(f) for f in res.diag])
            with span("replay/round", cat="replay", bucket=str(key)):
                for i, b in enumerate(idx):
                    if not active[i]:
                        continue
                    n_true = ctls[b].catalog.n
                    step = ctls[b].apply_counts(
                        traces[b][t], X_int[i, :n_true], replanned=False,
                        solver_iters=int(lane_iters[i]),
                        deadline_hit=bucket_hit)
                    ctls[b].plan = plans[i, :, :n_true]
                    tr_b = (None if lane_tr is None else
                            type(res.trace)(*(f[i] for f in lane_tr)))
                    if tr_b is not None:
                        solver_traces[b].append(tr_b)
                    obs.step(tenant=tenants[b].name, tick=t, step=step,
                             solver=solver_name, lane=i,
                             prob=windows[b][0],
                             x_rel=plans[i, 0, :n_true], trace=tr_b,
                             diag=(None if diag_np is None else
                                   type(res.diag)(*(f[i]
                                                    for f in diag_np))),
                             spot_unavailable=_spot_unavailable(
                                 tenants[b], t))
        gauge("replay/solver_iters", tick_iters)
      obs.tick_end(t, tick_iters, compile_key=tick_key)
    return [ctl.history for ctl in ctls], solver_traces


def replay_fleet(catalog: Catalog, tenants: Sequence[TenantSpec], *,
                 replay_mode: str = "sequential",
                 controller: str = "myopic",
                 horizon: int = 8,
                 forecaster: str = "last_value",
                 forecaster_kwargs: Optional[dict] = None,
                 coupling_w: Optional[float] = None,
                 coupling_eps: Optional[float] = None,
                 solver_config=None,
                 cold_start: str = "myopic",
                 run_oracle_baseline: bool = False,
                 run_ca_baseline: bool = True,
                 ca_engine: str = "vectorized",
                 ca_expander: str = "random",
                 ca_mode: str = "wave",
                 warm_start: str = "counts",
                 solver_steps: int = 600,
                 hot_loop: Optional[str] = None,
                 capture_solver_trace: bool = False,
                 health: Optional[HealthMonitor] = None,
                 anytime: Optional[AnytimeConfig] = None) -> FleetReplayResult:
    """Replay every tenant; returns per-tenant histories + fleet aggregates.

    ``replay_mode`` selects the optimizer engine:

    * ``"sequential"`` (reference) — one controller solve per tenant per tick.
    * ``"batched"`` — one batched solve call per shape bucket per tick (see
      module docstring). Traces may have different per-tenant lengths:
      finished tenants freeze in their batch lane (``FleetBatch.active``)
      and stop accruing churn/metrics. Produces per-tenant integer
      allocations identical to the sequential engine on CPU, ragged
      horizons included.

    ``controller`` selects the control loop both engines drive:

    * ``"myopic"`` (reference) — the paper's §III.E loop: each tick solves
      for the CURRENT demand under the L1 churn bound.
    * ``"mpc"`` — the receding-horizon controller (``repro.horizon``):
      each tick forecasts ``horizon`` ticks with ``forecaster``
      (a ``repro.horizon.forecast`` registry kind; ``forecaster_kwargs``
      forwarded, the tenant's own trace supplied so ``"oracle"`` works),
      solves the time-expanded program with smoothed inter-tick churn
      coupling (``coupling_w`` / ``coupling_eps``, defaulting to
      ``repro.horizon.problem``'s tuned values), and commits tick 0.
      ``horizon=1`` with any forecaster reproduces the myopic controller's
      integer allocations exactly (test-enforced).

    ``solver_config`` (MPC only; a ``repro.horizon.HorizonSolverConfig``)
    configures every warm tick's horizon solve per replay — engine choice
    (``solver="adaptive"`` BB/Armijo ladder vs ``"fixed"`` step), iteration
    budget, tolerance, ladder parameters and penalty weights — instead of
    relying on module constants (when None, a default config is built from
    ``solver_steps``). ``cold_start`` (MPC only) selects the cold tick's
    candidate ranking: ``"myopic"`` (tick-0 merit, the default) or
    ``"window"`` (the same multistart candidates re-scored against each
    tenant's whole lookahead window — see ``repro.horizon.controller``).
    Both engines honor both knobs identically (equivalence holds).

    ``run_oracle_baseline`` (MPC only) additionally replays the SAME fleet
    and controller under the ground-truth oracle forecaster and attaches
    its metrics as ``FleetReplayMetrics.oracle`` — enabling
    ``regret_vs_oracle`` (what forecast error cost).

    ``warm_start`` (batched myopic mode only) picks the incremental solve's
    warm start: ``"counts"`` (the previous integer allocation — what the
    sequential controller uses) or ``"relaxed"`` (the previous tick's relaxed
    batched solution); the MPC controller always warm-starts from its
    shifted previous plan. ``solver_steps`` is the PGD iteration budget of
    each warm tick; the default 600 matches ``solve_incremental`` — required
    for engine equivalence. ``hot_loop`` forwards to :func:`solve_fleet`
    for the cold-start solve.

    ``ca_engine`` selects the baseline replay implementation (the baseline
    itself is always the same numpy CA simulation, pools sized from each
    trace's peak demand): ``"vectorized"`` (default) steps all tenants per
    tick through one :func:`simulate_cluster_autoscaler_batch` call per
    distinct catalog; ``"sequential"`` loops
    :func:`simulate_cluster_autoscaler` per tenant — the oracle the
    vectorized engine must match tick-for-tick.

    ``capture_solver_trace=True`` records every warm tick's PGD convergence
    rows and returns them as ``FleetReplayResult.solver_traces`` (both
    engines, both controllers; MPC requires the adaptive engine — the fixed
    loop has no ladder to trace). Traced solves compute bit-identical
    allocations; they are merely separately-compiled programs that also
    write the per-iteration log.

    Run a replay inside ``with repro.obs.telemetry() as rec:`` to collect
    per-tick/per-phase timing spans, then aggregate them with
    ``repro.obs.report.ReplayReport.from_recorder(rec)``. Without a
    recorder installed every instrumentation point is a no-op, and either
    way allocations, churn and metrics are bit-identical (test-enforced).

    ``health`` (a ``repro.obs.HealthMonitor``) attaches per-tick health
    monitoring to the optimizer replay: SLO-breach/churn-violation/spot-
    interruption counters, committed-tick KKT-residual gauges on the
    relaxed solutions, solver stall detection (from captured traces),
    non-finite guards and the observe-only per-tick deadline budget. The
    monitor's rolled-up ``HealthReport`` lands on
    ``FleetReplayMetrics.health`` (and in ``summary()``). Baselines are
    never monitored — the CA replay runs no solver and the oracle twin is
    a reference, not the system under observation. Run inside ``with
    repro.obs.collect_metrics() as reg:`` to additionally fill
    ``replay/tick_ms`` and ``replay/solver_iters`` histograms on ``reg``
    (Prometheus/JSON exportable). Health and metrics observe only:
    per-tenant integer allocations are bit-identical with them on or off
    (test-enforced).

    ``anytime`` (a ``repro.core.AnytimeConfig`` with a ``deadline_ms``)
    enforces a per-solve deadline on every WARM tick in both engines and
    both controllers: the solve runs in iteration chunks against the
    config's injectable clock and deploys its best-so-far feasible iterate
    when the budget expires, marking the tick's ``ControllerStep`` with
    ``deadline_hit`` (batched engines flag every lane of a truncated
    bucket solve — the bucket shares one chunked program). Cold multistart
    ticks are never truncated (there is no prior allocation to fall back
    on). ``None`` — or a config without a deadline — keeps the untruncated
    engines bit-exactly (Python-level branch, test-enforced). Mutually
    exclusive with ``capture_solver_trace`` (a truncated trace is not the
    convergence evidence the trace consumers expect), and MPC replays
    require the adaptive engine (the fixed and admm engines have no
    chunk-resumable state)."""
    if len(tenants) == 0:
        raise ValueError("replay_fleet needs at least one TenantSpec; got an "
                         "empty tenant list")
    assert replay_mode in ("sequential", "batched"), replay_mode
    assert controller in ("myopic", "mpc"), controller
    assert ca_engine in ("vectorized", "sequential"), ca_engine
    if (anytime is not None and anytime.enabled and capture_solver_trace):
        raise ValueError("anytime deadlines and capture_solver_trace are "
                         "mutually exclusive; drop one")
    if run_oracle_baseline and controller != "mpc":
        raise ValueError("run_oracle_baseline compares a forecast-driven MPC "
                         "replay against its oracle-forecast twin; it "
                         'requires controller="mpc"')
    if controller == "mpc":
        # defaults resolved HERE, not above: the myopic path must not import
        # repro.horizon at all (the fleet->horizon edge stays deferred)
        if coupling_w is None or coupling_eps is None:
            from repro.horizon import DEFAULT_COUPLING_EPS, DEFAULT_COUPLING_W
            coupling_w = (DEFAULT_COUPLING_W if coupling_w is None
                          else coupling_w)
            coupling_eps = (DEFAULT_COUPLING_EPS if coupling_eps is None
                            else coupling_eps)
        mpc_kwargs = dict(horizon=horizon, forecaster=forecaster,
                          forecaster_kwargs=forecaster_kwargs,
                          coupling_w=coupling_w, coupling_eps=coupling_eps,
                          solver_steps=solver_steps,
                          solver_config=solver_config, cold_start=cold_start)
        if replay_mode == "sequential":
            ctls = [_make_mpc_controller(catalog, spec, **mpc_kwargs)
                    for spec in tenants]
            histories, traces_out = _replay_sequential(
                ctls, tenants, "mpc", capture_solver_trace, health=health,
                anytime=anytime)
        else:
            histories, traces_out = _replay_fleet_batched_mpc(
                catalog, tenants, hot_loop=hot_loop,
                capture_solver_trace=capture_solver_trace, health=health,
                anytime=anytime, **mpc_kwargs)
    elif replay_mode == "sequential":
        ctls = [_make_controller(catalog, spec) for spec in tenants]
        histories, traces_out = _replay_sequential(
            ctls, tenants, "myopic", capture_solver_trace, health=health,
            anytime=anytime)
    else:
        histories, traces_out = _replay_fleet_batched(
            catalog, tenants, warm_start=warm_start,
            solver_steps=solver_steps, hot_loop=hot_loop,
            capture_solver_trace=capture_solver_trace, health=health,
            anytime=anytime)
    if not run_ca_baseline:
        cas = [None] * len(tenants)
    elif ca_engine == "vectorized":
        cas = _replay_ca_fleet(catalog, tenants, ca_expander, ca_mode)
    else:
        cas = [_ca_baseline(catalog, spec, ca_expander, ca_mode)
               for spec in tenants]
    oracle_metrics = None
    if run_oracle_baseline:  # the oracle twin is a baseline: never traced
        oracle = replay_fleet(catalog, tenants, replay_mode=replay_mode,
                              controller="mpc", horizon=horizon,
                              forecaster="oracle", coupling_w=coupling_w,
                              coupling_eps=coupling_eps,
                              solver_config=solver_config,
                              cold_start=cold_start,
                              run_ca_baseline=False, warm_start=warm_start,
                              solver_steps=solver_steps, hot_loop=hot_loop)
        oracle_metrics = [r.metrics for r in oracle.tenants]
    with span("replay/metrics", cat="replay"):
        replays = [_assemble_replay(spec, steps, ca)
                   for spec, steps, ca in zip(tenants, histories, cas)]
        metrics = FleetReplayMetrics(
            tenants=[r.metrics for r in replays],
            baseline=([r.ca_metrics for r in replays]
                      if run_ca_baseline else None),
            replay_mode=replay_mode, controller=controller,
            oracle=oracle_metrics,
            health=health.report() if health is not None else None)
    return FleetReplayResult(
        tenants=replays, metrics=metrics,
        solver_traces=traces_out if capture_solver_trace else None)
