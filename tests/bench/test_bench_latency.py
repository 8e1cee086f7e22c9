"""Open-loop latency arithmetic from due times, under a fake clock."""
import json

import numpy as np
import pytest

from conftest import DATA


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Record:
    def __init__(self, tenant, cold=False):
        self.tenant, self.cold = tenant, cold


class FakeEngine:
    """Ticks take ``tick_s``; decides every tenant with pending demand
    except those in ``ignore``."""

    def __init__(self, clock, tick_s=0.5, ignore=()):
        self.clock, self.tick_s, self.ignore = clock, tick_s, set(ignore)
        self.pending, self.alloc = {}, {}
        self.last_anytime = None

    def register(self, name, demand):
        self.pending[name] = demand

    def submit(self, name, demand):
        self.pending[name] = demand

    def depart(self, name):
        self.pending.pop(name, None)

    def tick(self):
        self.clock.t += self.tick_s
        out = []
        for name in sorted(self.pending):
            if name not in self.ignore:
                self.alloc[name] = np.ones(4)
                out.append(Record(name))
        self.pending = {n: d for n, d in self.pending.items()
                        if n in self.ignore}
        return out

    def allocation(self, name):
        return self.alloc[name]


def make_driver(events, ignore=()):
    from bench.drivers.serve import Driver
    from bench.traffic import Event

    clock = FakeClock()
    cfg = json.loads((DATA / "configs" / "serve-tiny.json").read_text())
    traffic = json.loads((DATA / "traffic" / "scan1s.json").read_text())
    d = Driver(cfg, traffic, seed=0, clock=clock, sleep=clock.sleep)
    d.engine = FakeEngine(clock, ignore=ignore)
    d.events = [Event(t, kind, name, None if kind == "depart" else
                      np.full(4, t)) for t, kind, name in events]
    d.setup_ticks = 0
    d.annotate = lambda name: _Null()
    return d, clock


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_latency_counts_from_due_with_coalescing():
    d, clock = make_driver([(0.1, "update", "A"), (0.2, "update", "A"),
                            (0.25, "update", "A"), (0.3, "update", "B")])
    d.window(1.0)
    d.finish()
    lat, undecided = d.book.latencies(d.t0, d.t0 + 1.0)
    # tick 1 starts when A@0.1 is handed over and returns at 0.6; A@0.2,
    # A@0.25 and B@0.3 wait for it and the next tick returns at 1.1
    np.testing.assert_allclose(sorted(lat), sorted([0.5, 0.9, 0.85, 0.8]))
    assert undecided == 0
    assert d.book.counts()["coalesced"] == 1
    e2e = d.end_to_end()
    assert e2e["decision_p50_ms"] == pytest.approx(
        np.percentile([500, 900, 850, 800], 50))
    assert d.attempted_failed() == (4, 0)
    # the decision for A answers its newest demand
    demands = [dec[0][0] for dec in d.decisions()["window"]]
    assert demands == pytest.approx([0.1, 0.25, 0.3])


def test_undecided_requests_fail_and_withdrawn_do_not_count():
    d, clock = make_driver([(0.1, "update", "A"), (0.2, "update", "C"),
                            (0.3, "update", "D"), (0.4, "depart", "D")],
                           ignore=("C",))
    d.window(1.0)
    d.finish()
    assert d.attempted_failed() == (2, 1)
    assert d.unanswered() == 1
    assert d.book.counts()["withdrawn"] == 1
    # the drain gave up a minute past the window's close
    assert clock.t >= d.t0 + 1.0 + 60.0


def test_requests_due_after_the_close_are_not_counted():
    d, clock = make_driver([(0.1, "update", "A"), (0.9, "update", "B")])
    d.window(0.5)
    d.finish()
    lat, undecided = d.book.latencies(d.t0, d.t0 + 0.5)
    assert len(lat) == 1 and undecided == 0


def test_request_book_by_hand():
    from bench.latency import RequestBook

    book = RequestBook()
    a = book.submit("t", due=1.0, at=1.0, demand=[1])
    b = book.submit("t", due=2.0, at=2.5, demand=[2])
    open_ = book.open_requests()
    assert open_ == {"t": [a, b]}
    assert book.decide("t", open_["t"], at=4.0) == b
    assert not book.has_open()
    lat, undecided = book.latencies(0.0, 10.0)
    np.testing.assert_allclose(lat, [3.0, 2.0])
    assert undecided == 0 and book.coalesced == [True, False]
