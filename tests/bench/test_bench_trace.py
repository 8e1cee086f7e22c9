"""The trace reduction: busy union, idle share, kernel time, breakdown."""
import json
from types import SimpleNamespace

import pytest

from conftest import DATA


def _trace():
    from bench.tracing import DeviceTrace

    # window [100, 200) ns; ops overlap on chip 0 ([110,130) and [120,140)
    # -> 30 busy), one op reaches past the window's end (clipped to 10)
    ops = {"/device:TPU:0": [("fusion.1", 110, 20), ("%fleet_value_and_grad.7 custom-call", 120, 20),
                             ("copy", 160, 10), ("fusion.1", 190, 30),
                             ("early", 50, 20)]}
    host = [("window", 100, 100), ("tick", 100, 50), ("submit", 150, 20),
            ("tick", 170, 40)]
    return DeviceTrace(ops=ops, host=host, window=(100, 200))


def test_op_names_keep_the_instruction_and_mark_custom_calls():
    from bench.tracing import op_name

    assert op_name("%fusion.158 = (f32[16,12]{0,1}) fusion(f32[16] %a), "
                   "kind=kLoop") == "%fusion.158"
    assert op_name("%fleet_value_and_grad.24 = (f32[16,8,1]{2,1,0}, "
                   "f32[16,8,2048]) custom-call(f32[16,8,2048] %x)") == \
        "%fleet_value_and_grad.24 custom-call"


def test_busy_union_and_idle_share():
    t = _trace()
    assert t.window_s == pytest.approx(100e-9)
    # [110,140) + [160,170) + [190,200) = 50 ns
    assert t.busy_s() == pytest.approx(50e-9)
    assert t.idle_share() == pytest.approx(0.5)


def test_busy_is_averaged_over_chips():
    from bench.tracing import DeviceTrace

    t = DeviceTrace(ops={"/device:TPU:0": [("a", 0, 10)],
                         "/device:TPU:1": [("a", 0, 30)]},
                    window=(0, 40))
    assert t.busy_s() == pytest.approx(20e-9)


def test_kernel_time_and_roofline_reader():
    from bench import harness

    t = _trace()
    events = t.kernel_events(lambda n: "custom-call" in n)
    assert events == [("%fleet_value_and_grad.7 custom-call", 120, 20)]
    read = harness.load_reader("alloc_objective_roofline")
    shapes = {"B": 2, "T": 3, "n": 5, "m": 4, "p": 2}
    ctx = SimpleNamespace(trace=t, driver=SimpleNamespace(kernel_shapes=shapes),
                          peaks={"flops_per_s": 1e12, "bytes_per_s": 1e11})
    # 616 bytes / 1e11 B/s = 6.16 ns least over 20 ns measured
    assert read(ctx) == pytest.approx(100 * 6.16 / 20)
    ctx.trace = None
    assert read(ctx) is None


def test_breakdown_labels_gaps_by_innermost_annotation():
    t = _trace()
    b = t.breakdown(top=3)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    # gaps [140,160) (middle 150: inside "submit"), [170,190) (middle
    # 180: inside the second "tick"), [100,110) (middle 105: first "tick")
    assert b["idle_gaps"] == [["submit", pytest.approx(20e-9)],
                              ["tick", pytest.approx(20e-9)],
                              ["tick", pytest.approx(10e-9)]]


def test_idle_share_reader_percent():
    from bench import harness

    ctx = SimpleNamespace(trace=_trace())
    assert harness.load_reader("device_idle_share.serve")(ctx) == \
        pytest.approx(50.0)
    assert harness.load_reader("device_idle_share.replay")(
        SimpleNamespace(trace=None)) is None


def test_span_readers():
    from bench import harness

    S = lambda name, ts, dur: SimpleNamespace(name=name, ts_us=ts,
                                              dur_us=dur)
    spans = [S("replay/tick", 0, 1000), S("replay/solve", 100, 300),
             S("replay/solve", 500, 200), S("replay/tick", 2000, 3000),
             S("replay/solve", 2100, 1000), S("serve/tick", 0, 4000),
             S("serve/tick", 0, 2000), S("serve/tick", 0, 3000)]
    ctx = SimpleNamespace(spans=spans)
    # per tick solve: 0.5 ms and 1.0 ms -> median 0.75
    assert harness.load_reader("replay_solve_ms")(ctx) == pytest.approx(0.75)
    # per tick host: 0.5 ms and 2.0 ms -> median 1.25
    assert harness.load_reader("replay_host_ms")(ctx) == pytest.approx(1.25)
    assert harness.load_reader("serve_tick_ms")(ctx) == pytest.approx(3.0)
    assert harness.load_reader("serve_cold_join_ms")(ctx) is None


def _recorded():
    from bench.tracing import DeviceTrace

    raw = json.loads((DATA / "trace_v5e.json").read_text())
    return DeviceTrace(ops={k: [tuple(e) for e in v]
                            for k, v in raw["ops"].items()},
                       host=[tuple(h) for h in raw["host"]],
                       window=tuple(raw["window"]))


def _sweep_busy_ns(events, lo, hi):
    """Busy time by a sweep over start/end points, counting open events."""
    points = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_chip_trace_reduces_like_a_sweep():
    t = _recorded()
    ops = t.ops["/device:TPU:0"]
    lo, hi = t.window
    assert len(ops) == 400
    assert t.busy_s() == pytest.approx(_sweep_busy_ns(ops, lo, hi) / 1e9)
    assert 0.0 < t.idle_share() < 1.0
    assert t.idle_share() == pytest.approx(1 - t.busy_s() / t.window_s)
    copies = t.kernel_events(lambda n: n.startswith("%copy-start"))
    assert copies and sum(d for _, _, d in copies) == sum(
        d for n, _, d in ops if n.startswith("%copy-start"))
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    # every gap of this window falls inside the harness's "segment"
    assert {label for label, _ in b["idle_gaps"]} == {"segment"}
