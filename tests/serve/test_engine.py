"""ServeEngine: tenant lifecycle over fixed batch lanes, mid-session
join/depart isolation, staleness accounting, and the enforced anytime
budget under an injectable clock (ISSUE tentpole, repro.serve)."""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import Catalog, make_cloud_catalog
from repro.serve import ServeEngine

D0 = np.array([8.0, 16.0, 4.0, 100.0])


@pytest.fixture(scope="module")
def catalog():
    return Catalog(make_cloud_catalog().instances[::40])


def _fake_clock(step_ms=4.0):
    fake = SimpleNamespace(t=0.0)

    def clock():
        fake.t += step_ms / 1e3
        return fake.t

    return clock


def test_lifecycle_errors(catalog):
    eng = ServeEngine(catalog, 2)
    eng.register("a")
    with pytest.raises(ValueError, match="already registered"):
        eng.register("a")
    eng.register("b")
    with pytest.raises(ValueError, match="at capacity"):
        eng.register("c")
    with pytest.raises(KeyError, match="unknown tenant"):
        eng.submit("zz", D0)
    eng.depart("b")
    assert eng.tenants() == ["a"]
    with pytest.raises(ValueError):
        ServeEngine(catalog, 0)


@pytest.mark.slow
def test_departed_lane_is_reused_with_fresh_state(catalog):
    """A joiner reuses the departed tenant's lane (capacity conserved, no
    batch reshaping) but starts from a cold multistart solve — no warm
    state leaks across the tenancy change."""
    eng = ServeEngine(catalog, 2)
    lane_b = eng.register("b", demand=D0 * 0.5)
    eng.register("a", demand=D0)
    eng.tick()
    eng.depart("b")
    assert eng.register("c", demand=D0 * 0.7) == lane_b
    recs = eng.tick()
    rec_c = next(r for r in recs if r.tenant == "c")
    assert rec_c.cold and rec_c.staleness == 0
    assert eng.allocation("c") is not None


@pytest.mark.slow
def test_join_depart_does_not_perturb_other_lanes(catalog):
    """Mid-session churn isolation: tenant a's decisions must be
    bit-identical whether or not ANOTHER lane's tenancy changed —
    vmap lanes are independent and the compiled batch shape is fixed."""
    def session(churn: bool):
        eng = ServeEngine(catalog, 3)
        eng.register("a", demand=D0)
        eng.register("b", demand=D0 * 0.5)
        eng.tick()
        for t in range(3):
            if churn and t == 1:
                eng.depart("b")
                eng.register("c", demand=D0 * 0.8)
            eng.submit("a", D0 * (1.0 + 0.02 * (t + 1)))
            if "b" in eng.tenants():
                eng.submit("b", D0 * 0.5)
            eng.tick()
        return [s.counts for s in
                eng._lanes[eng._by_name["a"]].controller.history]

    plain, churned = session(False), session(True)
    assert len(plain) == len(churned) == 4
    for a, b in zip(plain, churned):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_staleness_counts_ticks_since_last_decision(catalog):
    eng = ServeEngine(catalog, 1)
    eng.register("a", demand=D0)
    eng.tick()                      # t=0: cold decision
    eng.tick()                      # t=1: no demand -> no decision
    eng.tick()                      # t=2: idle again
    eng.submit("a", D0 * 1.05)
    recs = eng.tick()               # t=3: decided after 3 idle ticks
    assert [r.staleness for r in recs] == [3]
    assert eng.summary().max_staleness == 3
    # idle ticks produce no records but still advance the counter
    assert eng.tick_count == 4 and len(eng.records) == 2


@pytest.mark.slow
def test_deadline_truncates_warm_solve_deterministically(catalog):
    """With the injectable clock burning 4ms per reading, a 10ms tick
    budget must truncate the warm batched solve: the decision reports
    ``deadline_hit`` with a small iteration count, and the engine's
    summary reports the truncation and miss rates."""
    eng = ServeEngine(catalog, 2, deadline_ms=10.0, chunk_iters=8,
                      clock=_fake_clock(4.0))
    eng.register("a", demand=D0)
    eng.tick()
    eng.submit("a", D0 * 1.5)
    recs = eng.tick()
    assert len(recs) == 1
    assert recs[0].deadline_hit
    assert 0 < recs[0].solver_iters <= 16
    s = eng.summary()
    assert s.truncated_rate == 0.5          # 1 of 2 decisions truncated
    assert s.miss_rate > 0                  # fake clock blows the budget
    assert s.deadline_ms == 10.0


@pytest.mark.slow
def test_no_deadline_serves_untruncated(catalog):
    eng = ServeEngine(catalog, 2)
    eng.register("a", demand=D0)
    eng.tick()
    eng.submit("a", D0 * 1.1)
    recs = eng.tick()
    assert not recs[0].deadline_hit
    assert eng.summary().truncated_rate == 0.0


@pytest.mark.slow
def test_health_monitor_observes_decisions(catalog):
    from repro.obs import HealthMonitor

    clock = _fake_clock(2.0)
    mon = HealthMonitor(deadline_ms=1.0, kkt_every=0, clock=clock)
    eng = ServeEngine(catalog, 2, clock=clock, health=mon)
    eng.register("a", demand=D0)
    eng.tick()                       # first sighting of the cold tick key
    eng.submit("a", D0 * 1.05)
    eng.tick()                       # first sighting of the warm tick key
    eng.submit("a", D0 * 1.1)
    eng.tick()                       # steady state: budgeted (and missed)
    rep = mon.report()
    assert rep.ticks_observed == 3
    assert rep.compile_excluded_ticks == 2
    assert rep.deadline_miss_ticks == 1


@pytest.mark.slow
def test_main_demo_runs(capsys):
    from repro.serve.__main__ import run_demo

    eng = run_demo(lanes=2, ticks=4, deadline_ms=None, verbose=True)
    out = capsys.readouterr().out
    assert "latency p50/p99" in out
    assert eng.summary().decisions > 0


@pytest.mark.slow
def test_tick_span_tree_and_queue_wait(catalog):
    """While telemetry records, a tick is a ``serve/tick`` span whose
    children cover the tick's parts in order (the anytime solve's own
    ``fleet/*`` spans one level further down), and whose tags count the
    lanes and give each taken lane's wait from its first pending demand
    to the tick's start, on the engine's clock."""
    from repro.obs import telemetry

    now = SimpleNamespace(t=100.0)
    eng = ServeEngine(catalog, 3, deadline_ms=1e4, chunk_iters=8,
                      clock=lambda: now.t)
    eng.register("a", demand=D0)            # pending since 100.0
    now.t = 100.5
    eng.register("b")                       # nothing pending yet
    now.t = 101.0
    eng.submit("b", D0 * 0.5)               # pending since 101.0
    now.t = 102.0
    with telemetry() as rec:
        eng.tick()                          # both cold joins
        now.t = 103.0
        eng.submit("a", D0 * 1.2)           # pending since 103.0 ...
        now.t = 103.5
        eng.submit("a", D0 * 1.3)           # ... coalesced: still 103.0
        now.t = 104.25
        recs = eng.tick()                   # a's warm solve
    assert all(ln.pending_since is None for ln in eng._lanes)

    ticks = rec.spans("serve/tick")
    assert len(ticks) == 2
    cold_tick, warm_tick = ticks
    assert cold_tick.tags["queue_wait_ms"] == pytest.approx([2000.0, 1000.0])
    assert (cold_tick.tags["cold"], cold_tick.tags["lanes"]) == (2, 2)
    assert warm_tick.tags["queue_wait_ms"] == pytest.approx([1250.0])
    assert (warm_tick.tags["cold"], warm_tick.tags["lanes"]) == (0, 1)
    assert warm_tick.tags["iters"] == recs[0].solver_iters > 0
    assert warm_tick.tags["chunks"] >= 1
    assert warm_tick.tags["truncated"] is False

    def within(outer):
        end = outer.ts_us + outer.dur_us
        return sorted((e for e in rec.events if e is not outer
                       and outer.ts_us <= e.ts_us
                       and e.ts_us + e.dur_us <= end),
                      key=lambda e: e.ts_us)

    assert [(e.name, e.depth) for e in within(cold_tick)] == \
        [("serve/cold", 1), ("serve/cold", 1)]
    assert [(e.name, e.depth) for e in within(warm_tick)] == [
        ("serve/build", 1), ("serve/stack", 1), ("serve/solve", 1),
        ("fleet/anytime", 2), ("fleet/round", 2), ("serve/apply", 1)]
    solve = rec.spans("serve/solve")[0]
    assert [e.name for e in within(solve)] == ["fleet/anytime",
                                               "fleet/round"]
