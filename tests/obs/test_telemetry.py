"""Telemetry spans: zero-overhead no-op when disabled, compile/execute
tagging when enabled — and the contract that matters most: telemetry NEVER
changes allocations (bit-equality with telemetry on vs off, both engines)."""
import numpy as np
import pytest

from repro.core import Catalog, make_cloud_catalog
from repro.fleet import TenantSpec, replay_fleet
from repro.fleet.traces import constant_trace, diurnal_trace
from repro.obs import (ReplayReport, Recorder, counter, current_recorder,
                       gauge, span, telemetry)
from repro.obs.telemetry import _NOOP_CM, _NOOP_SPAN

BASE = np.array([8.0, 16.0, 4.0, 100.0])


@pytest.fixture(scope="module")
def tiny_catalog():
    return Catalog(make_cloud_catalog().instances[::40])


@pytest.fixture(scope="module")
def specs():
    return [
        TenantSpec(name="a", trace=diurnal_trace(BASE, 3, amplitude=0.3,
                                                 noise=0.0), n_starts=2),
        TenantSpec(name="b", trace=constant_trace(BASE * 0.6, 3), n_starts=2),
    ]


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    """With no recorder installed, span() must return THE shared no-op
    context manager (no per-call allocation) whose fence is identity."""
    assert current_recorder() is None
    cm = span("replay/tick", compile_key=("k",), tick=0)
    assert cm is _NOOP_CM
    with cm as sp:
        assert sp is _NOOP_SPAN
        obj = object()
        assert sp.fence(obj) is obj          # no block_until_ready, no copy
        assert sp.tag(a=1) is sp
    counter("x")                              # both must be silent no-ops
    gauge("y", 1.0)


def test_telemetry_scope_installs_and_restores():
    assert current_recorder() is None
    with telemetry() as rec:
        assert current_recorder() is rec
        with telemetry(enabled=False) as none_rec:
            assert none_rec is None           # explicit no-op scope
        with telemetry() as inner:            # nested scope shadows...
            assert current_recorder() is inner
        assert current_recorder() is rec      # ...and restores on exit
    assert current_recorder() is None


# ---------------------------------------------------------------------------
# enabled path: nesting, tagging, counters/gauges
# ---------------------------------------------------------------------------

def test_compile_execute_tagging_and_nesting():
    with telemetry() as rec:
        with span("outer", cat="t", compile_key=("prog", 32)):
            with span("inner", cat="t"):
                pass
        with span("outer", cat="t", compile_key=("prog", 32)) as sp:
            sp.tag(tick=1)
        counter("n_solves", 2)
        gauge("waste", 0.25)
    evs = {(e.name, e.phase, e.depth) for e in rec.events}
    assert ("inner", None, 1) in evs          # nested one level down
    assert ("outer", "compile", 0) in evs     # first key sighting
    assert ("outer", "execute", 0) in evs     # repeat is steady-state
    assert rec.spans("outer", phase="execute")[0].tags == {"tick": 1}
    assert rec.counters["n_solves"] == 2.0
    assert [v for _, v in rec.gauges["waste"]] == [0.25]
    assert rec.total_us("outer") > 0
    assert "outer" in rec.summary()


def test_span_is_a_trace_annotation_only_while_recording(monkeypatch):
    """A recording span opens a ``jax.profiler.TraceAnnotation`` of its
    name around its whole interval, fence included; the disabled path
    never constructs one."""
    import jax.profiler

    opened = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    with span("off"):
        pass
    assert opened == []
    with telemetry() as rec:
        with span("outer"):
            with span("inner", fence=np.zeros(2)):
                pass
    assert opened == [("enter", "outer"), ("enter", "inner"),
                      ("exit", "inner"), ("exit", "outer")]
    assert [e.name for e in rec.events] == ["inner", "outer"]
    with span("off again"):
        pass
    assert len(opened) == 4


# ---------------------------------------------------------------------------
# the contract: telemetry never changes allocations (both engines)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    "sequential",
    # the batched replay pays a multi-second vmap compile: full tier
    pytest.param("batched", marks=pytest.mark.slow),
])
def test_replay_bit_identical_with_telemetry_on(tiny_catalog, specs, mode):
    """ISSUE acceptance: a fully instrumented replay (telemetry recorder
    installed AND per-lane solver-trace capture on) must produce per-tick
    integer allocations, churn and metrics BIT-IDENTICAL to the plain
    run — the recorder only fences completion, never recomputes."""
    plain = replay_fleet(tiny_catalog, specs, run_ca_baseline=False,
                         replay_mode=mode)
    with telemetry() as rec:
        instr = replay_fleet(tiny_catalog, specs, run_ca_baseline=False,
                             replay_mode=mode, capture_solver_trace=True)
    for rp, ri in zip(plain.tenants, instr.tenants):
        for sp_, si in zip(rp.steps, ri.steps):
            np.testing.assert_array_equal(sp_.counts, si.counts)
            assert sp_.churn == si.churn
            assert sp_.solver_iters == si.solver_iters
        assert rp.metrics == ri.metrics
    assert instr.metrics.summary() == plain.metrics.summary()
    # the instrumented run actually recorded the replay
    assert len(rec.spans("replay/tick")) > 0
    assert instr.solver_traces is not None
    assert plain.solver_traces is None


def test_instrumented_replay_produces_report(tiny_catalog, specs):
    """ReplayReport rolls the recorder up with a compile/execute split and
    per-tick latency percentiles (ISSUE acceptance criterion)."""
    with telemetry() as rec:
        replay_fleet(tiny_catalog, specs, run_ca_baseline=False,
                     replay_mode="batched")
    rep = ReplayReport.from_recorder(rec)
    assert rep.n_ticks == 3
    assert rep.compile_ms > 0                 # first tick compiled something
    assert rep.execute_ms > 0
    assert set(rep.tick_ms) == {"p50", "p95", "p99"}
    names = {p.name for p in rep.phases}
    assert {"replay/tick", "replay/stack", "replay/solve"} <= names
    assert rep.padding_waste                  # stack_problems gauged waste
    assert rep.solver_iters.get("total", 0) > 0
    assert "replay report" in rep.render()


def test_report_degrades_on_empty_recorder():
    rep = ReplayReport.from_recorder(Recorder())
    assert rep.n_ticks == 0 and rep.phases == [] and rep.tick_ms == {}
    assert "0 ticks" in rep.render()


@pytest.mark.slow
def test_serve_bit_identical_with_telemetry_on(tiny_catalog):
    """The serving engine's decisions are bit-identical with a recorder
    installed: its spans fence the solve and copy nothing that the
    untraced tick does not."""
    from repro.serve import ServeEngine

    def session():
        ticks = iter(range(10**6))        # 1 ms a reading: never truncates
        eng = ServeEngine(tiny_catalog, 3, deadline_ms=1e4, chunk_iters=8,
                          clock=lambda: next(ticks) / 1e3)
        eng.register("a", demand=BASE)
        eng.register("b", demand=BASE * 0.5)
        eng.tick()
        for k in range(2):
            eng.submit("a", BASE * (1.2 + 0.3 * k))
            eng.submit("b", BASE * (0.6 - 0.05 * k))
            eng.tick()
        return eng

    plain = session()
    with telemetry() as rec:
        instr = session()
    for name in ("a", "b"):
        hp = plain._lanes[plain._by_name[name]].controller.history
        hi = instr._lanes[instr._by_name[name]].controller.history
        assert len(hp) == len(hi) == 3
        for sp_, si in zip(hp, hi):
            np.testing.assert_array_equal(sp_.counts, si.counts)
            assert sp_.solver_iters == si.solver_iters
    assert [(r.tenant, r.solver_iters, r.objective) for r in plain.records] \
        == [(r.tenant, r.solver_iters, r.objective) for r in instr.records]
    assert len(rec.spans("serve/tick")) == 3
