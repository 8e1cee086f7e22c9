"""Program spans on the trace's clock (``bench/spanclock.py``) and the
per-layer readers of the serve and replay ticks' parts, on hand-built
spans and traces."""
from types import SimpleNamespace

import pytest


def S(name, ts, dur, **tags):
    return SimpleNamespace(name=name, ts_us=ts, dur_us=dur, tags=tags)


def _trace():
    from bench.tracing import DeviceTrace

    # window [1000, 2000) ns; chip 0 busy [1100,1200) and [1150,1300)
    # (overlapping: one stretch [1100,1300)), [1500,1600), and [1950,2100)
    # which runs past the window's end
    ops = {"/device:TPU:0": [("a", 1100, 100), ("b", 1150, 150),
                             ("c", 1500, 100), ("d", 1950, 150),
                             ("early", 900, 50)]}
    return DeviceTrace(ops=ops, host=[("window", 1000, 1000)],
                       window=(1000, 2000))


def _read(metric, **ctx):
    from bench import harness

    return harness.load_reader(metric)(SimpleNamespace(**ctx))


def test_span_maps_onto_the_window_start():
    from bench import spanclock

    t = _trace()
    # 0.25 us after the recorder's start, 0.5 us long
    assert spanclock.interval_ns(t, S("x", 0.25, 0.5)) == (1250.0, 1750.0)


def test_idle_inside_intervals():
    from bench import spanclock

    t = _trace()
    # [1000,1400): busy [1100,1300) -> idle 200 of 400
    assert spanclock.idle_inside(t, [(1000, 1400)]) == (200.0, 400.0)
    # two intervals pool: [1250,1550) idle 200 of 300 (busy [1250,1300)
    # and [1500,1550)), [1700,1800) idle 100 of 100
    assert spanclock.idle_inside(t, [(1250, 1550), (1700, 1800)]) == \
        (300.0, 400.0)
    # crossing the window's end: cut to [1900,2000), busy [1950,2000)
    assert spanclock.idle_inside(t, [(1900, 2300)]) == (50.0, 100.0)
    # crossing its start: cut to [1000,1150), busy [1100,1150); the op
    # before the window does not count
    assert spanclock.idle_inside(t, [(800, 1150)]) == (100.0, 150.0)
    # wholly outside: nothing
    assert spanclock.idle_inside(t, [(2100, 2200)]) == (0.0, 0.0)


def test_idle_inside_is_averaged_over_chips():
    from bench import spanclock
    from bench.tracing import DeviceTrace

    t = DeviceTrace(ops={"/device:TPU:0": [("a", 0, 10)],
                         "/device:TPU:1": [("a", 0, 30)]}, window=(0, 40))
    assert spanclock.idle_inside(t, [(0, 40)]) == (20.0, 40.0)


def test_idle_share_of_named_spans():
    from bench import spanclock

    t = _trace()
    spans = [S("fleet/anytime", 0.0, 0.4), S("other", 0.0, 1.0),
             S("fleet/anytime", 0.7, 0.1)]
    # [1000,1400) idle 200 and [1700,1800) idle 100, of 500 ns
    assert spanclock.idle_share(t, spans, "fleet/anytime") == \
        pytest.approx(60.0)
    assert spanclock.idle_share(None, spans, "fleet/anytime") is None
    assert spanclock.idle_share(t, spans, "missing") is None


def _serve_spans():
    return [
        S("serve/tick", 0, 1000, lanes=2, cold=0, iters=10, chunks=1,
          truncated=False, queue_wait_ms=[5.0, 1.0]),
        S("serve/build", 10, 100), S("serve/stack", 120, 50),
        S("fleet/anytime", 200, 400), S("fleet/round", 610, 30),
        S("serve/solve", 190, 460), S("serve/apply", 700, 200),
        S("serve/tick", 2000, 3000, lanes=3, cold=0, iters=40, chunks=2,
          truncated=True, queue_wait_ms=[2.0, 3.0, 4.0]),
        S("serve/build", 2010, 300), S("serve/stack", 2320, 150),
        S("fleet/anytime", 2500, 1600), S("fleet/round", 4110, 90),
        S("serve/solve", 2490, 1720), S("serve/apply", 4300, 600),
        # a cold-only tick: no warm parts, no iterations
        S("serve/tick", 6000, 500, lanes=1, cold=1,
          queue_wait_ms=[100.0]),
        S("serve/cold", 6010, 480),
    ]


@pytest.mark.parametrize("metric,want", [
    ("serve_queue_wait_ms", 3.5),        # of 5, 1, 2, 3, 4, 100
    ("serve_build_ms", 0.2),             # 0.1 and 0.3
    ("serve_stack_ms", 0.1),             # 0.05 and 0.15
    ("serve_apply_ms", 0.4),             # 0.2 and 0.6
    ("serve_round_ms", 0.06),            # 0.03 and 0.09
    ("serve_pgd_iter_ms", 0.04),         # 0.4 / 10 and 1.6 / 40
])
def test_serve_part_readers(metric, want):
    assert _read(metric, spans=_serve_spans(), trace=None) == \
        pytest.approx(want)
    assert _read(metric, spans=[], trace=None) is None


def test_serve_anytime_idle_share_reader():
    spans = [S("serve/tick", 0.0, 1.0, iters=1),
             S("fleet/anytime", 0.0, 0.4)]
    # [1000,1400) on the trace's clock: idle 200 of 400
    assert _read("serve_anytime_idle_share", spans=spans,
                 trace=_trace()) == pytest.approx(50.0)
    assert _read("serve_anytime_idle_share", spans=spans,
                 trace=None) is None
    assert _read("serve_anytime_idle_share", spans=[],
                 trace=_trace()) is None


def _replay_spans():
    return [
        S("replay/tick", 0, 1000), S("replay/build", 0, 100),
        S("replay/stack", 100, 20), S("replay/solve", 120, 500),
        S("replay/round", 620, 30), S("replay/stack", 650, 40),
        S("replay/solve", 690, 200), S("replay/round", 890, 50),
        S("replay/tick", 2000, 1000), S("replay/build", 2000, 300),
        S("replay/stack", 2300, 100), S("replay/solve", 2400, 500),
        S("replay/round", 2900, 70),
    ]


@pytest.mark.parametrize("metric,want", [
    ("replay_build_ms", 0.2),            # 0.1 and 0.3
    ("replay_stack_ms", 0.08),           # 0.02 + 0.04, and 0.1
    ("replay_round_ms", 0.075),          # 0.03 + 0.05, and 0.07
])
def test_replay_part_readers(metric, want):
    assert _read(metric, spans=_replay_spans(), trace=None) == \
        pytest.approx(want)
    assert _read(metric, spans=[], trace=None) is None


def test_replay_solve_idle_share_reader():
    spans = [S("replay/tick", 0.0, 1.0), S("replay/solve", 0.1, 0.2),
             S("replay/solve", 0.9, 0.3)]
    # [1100,1300) all busy; [1900,2200) cut to [1900,2000), busy
    # [1950,2000): idle 50 of 300
    assert _read("replay_solve_idle_share", spans=spans,
                 trace=_trace()) == pytest.approx(100 * 50 / 300)
    assert _read("replay_solve_idle_share", spans=spans,
                 trace=None) is None
