"""The traffic generator: the same seed gives the same inputs, every seed
the same set of sizes and arrival times, and the churn schedule keeps the
roster at the lane count."""
import json

import numpy as np
import pytest

from conftest import ROOT

TRAFFIC = ROOT / "bench" / "traffic"


def _load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_same_seed_same_inputs(seed):
    from bench.traffic import open_loop, replay_traces

    a = open_loop(_load("churn16-scan10s"), 256, 30.0, seed)
    b = open_loop(_load("churn16-scan10s"), 256, 30.0, seed)
    assert [(e.t, e.kind, e.tenant) for e in a[1]] == \
        [(e.t, e.kind, e.tenant) for e in b[1]]
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a[0], b[0]))
    r1 = replay_traces(_load("mixed-seg4"), 8, seed)
    r2 = replay_traces(_load("mixed-seg4"), 8, seed)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(r1, r2))


def test_seeds_share_sizes_and_arrival_times():
    from bench.traffic import open_loop, tenant_demands

    s1 = np.sort(np.exp(np.log(tenant_demands(
        64, np.random.default_rng(1), jitter=0.0)[:, 0] / 8.0)))
    s2 = np.sort(np.exp(np.log(tenant_demands(
        64, np.random.default_rng(2), jitter=0.0)[:, 0] / 8.0)))
    np.testing.assert_allclose(s1, s2)
    t1 = sorted(e.t for e in open_loop(_load("scan10s"), 256, 30.0, 1)[1])
    t2 = sorted(e.t for e in open_loop(_load("scan10s"), 256, 30.0, 2)[1])
    np.testing.assert_allclose(t1, t2)
    assert len(t1) == 768     # 25.6 updates/s for 30 s


def test_churn_keeps_the_roster_full():
    from bench.traffic import open_loop

    initial, events = open_loop(_load("churn16-scan10s"), 256, 35.0, 7)
    roster = {name for name, _ in initial}
    for e in events:
        if e.kind == "depart":
            roster.remove(e.tenant)
        elif e.kind == "join":
            assert e.tenant not in roster
            roster.add(e.tenant)
        else:
            assert e.tenant in roster, e
    assert len(roster) == 256
    # churn at 5, 15 and 25 s
    assert sum(e.kind == "join" for e in events) == 16 * 3
    assert all(np.all(e.demand > 0) for e in events if e.kind != "depart")


def test_open_loop_population_is_the_same_for_every_seed():
    from bench.traffic import open_loop

    for name in ("scan10s", "churn16-scan10s"):
        a_init, a_ev = open_loop(_load(name), 256, 51.0, 1)
        b_init, b_ev = open_loop(_load(name), 256, 51.0, 2)
        assert [d.tolist() for _, d in a_init] == \
            [d.tolist() for _, d in b_init]
        assert [(e.t, e.tenant) for e in a_ev] != \
            [(e.t, e.tenant) for e in b_ev]
        for kind in ("join", "depart"):
            assert sum(e.kind == kind for e in a_ev) == \
                sum(e.kind == kind for e in b_ev)
    # without churn, every seed offers updates at the same times
    times = [sorted(e.t for e in open_loop(_load("scan10s"), 256, 51.0,
                                           seed)[1]) for seed in (1, 2)]
    assert times[0] == times[1]


def test_replay_mix_alternates():
    from bench.traffic import replay_traces

    traces = replay_traces(_load("mixed-seg4"), 4, 0)
    assert [tr.shape for _, tr in traces] == [(8, 4)] * 4


def test_serve_bursts_fall_inside_the_window():
    """A trace tick lasts one scan, so the flash crowds of nearly every
    tenant reach it within a 51 s window."""
    from bench.traffic import open_loop

    initial, events = open_loop(_load("scan10s"), 256, 51.0, 2**35 + 1)
    first = {name: d for name, d in initial}
    peak = {}
    for e in events:
        peak[e.tenant] = max(peak.get(e.tenant, 0.0),
                             e.demand[0] / first[e.tenant][0])
    assert np.mean([v > 1.5 for v in peak.values()]) > 0.9


def test_replay_window_covers_the_whole_trace():
    """Two segments replay every tick, and the diurnal trace runs a whole
    period in it."""
    from bench.traffic import replay_traces

    params = _load("mixed-seg4")
    assert params["ticks"] == 2 * params["segment_ticks"]
    traces = replay_traces(params, 64, 2**33 + 7)
    diurnal = np.array([tr[:, 0] / tr[0, 0] for _, tr in traces[0::2]])
    crowd = np.array([tr[:, 0] / tr[0, 0] for _, tr in traces[1::2]])
    assert np.median(diurnal.max(1) - diurnal.min(1)) > 0.5
    assert np.mean(crowd.max(1) > 1.5) > 0.8
