"""Driver for a live ``ServeEngine`` under open-loop traffic.

Set-up registers every tenant of the traffic, runs the fill tick (each
lane's cold join) and one warm tick in which every lane updates, so every
program the window uses is compiled. The window submits each request when
it is due (the engine coalesces, latest demand wins) and ticks back to back
while any request is open; it sleeps only when none is. After the window
closes, the requests due in it are waited for, ticking on, for at most a
minute.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from bench import tracing
from bench.catalog import capacity_matrix, catalog_rows
from bench.latency import RequestBook
from bench.reference.check import sparse
from bench.traffic import open_loop

DRAIN_S = 60.0


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 clock=time.perf_counter, sleep=time.sleep):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.clock = clock
        self.sleep = sleep
        if traffic["kind"] != "open_loop":
            raise ValueError(f"the serve driver runs open_loop traffic, "
                             f"not {traffic['kind']!r}")
        self.rows = catalog_rows(**config["catalog"])
        self.capacities = capacity_matrix(self.rows)
        self.book = RequestBook()
        # allocations committed in set-up and from the window on
        self._decisions: Dict[str, List[Tuple]] = {"setup": [], "window": []}
        self._phase = "setup"
        self.ticks: List[Dict] = []
        self.annotate = tracing.annotate

    # -- set-up ---------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from repro.core.catalog import Catalog, InstanceType
        from repro.serve import ServeEngine

        cfg = self.config
        self.catalog = Catalog([InstanceType(*r) for r in self.rows])
        self.engine = ServeEngine(self.catalog, int(cfg["lanes"]),
                                  **cfg.get("engine_args", {}),
                                  clock=self.clock)
        self.initial, self.events = open_loop(self.traffic, int(cfg["lanes"]),
                                              seconds, self.seed)
        now = self.clock()
        for name, demand in self.initial:
            self.engine.register(name, demand=demand)
            self.book.submit(name, now, now, demand)
        self._tick()
        for name, demand in self.initial:     # every lane's warm solve
            self.engine.submit(name, demand)
            self.book.submit(name, self.clock(), self.clock(), demand)
        self._tick()
        self.setup_ticks = len(self.ticks)

    # -- the window -----------------------------------------------------------

    def _tick(self) -> None:
        open_ = self.book.open_requests()
        t0 = self.clock()
        with self.annotate("tick"):
            records = self.engine.tick()
        end = self.clock()
        cold = 0
        for rec in records:
            ids = open_.get(rec.tenant)
            if not ids:
                continue
            rid = self.book.decide(rec.tenant, ids, end)
            idx, vals = sparse(self.engine.allocation(rec.tenant))
            self._decisions[self._phase].append((self.book.demand[rid], idx,
                                                 vals))
            cold += rec.cold
        rep = self.engine.last_anytime
        self.ticks.append({
            "start": t0, "end": end, "lanes": len(records), "cold": cold,
            "chunks": None if rep is None else rep.chunks,
            "truncated": None if rep is None else bool(rep.deadline_hit)})

    def _submit_due(self, now: float) -> None:
        """Hand over every event due by ``now`` (seconds into the window)."""
        due = []
        while self._next < len(self.events) and \
                self.events[self._next].t <= now:
            due.append(self.events[self._next])
            self._next += 1
        if not due:
            return
        with self.annotate("submit"):
            for ev in due:
                at = self.clock()
                if ev.kind == "depart":
                    with self.annotate("depart"):
                        self.engine.depart(ev.tenant)
                    self.book.withdraw(ev.tenant)
                elif ev.kind == "join":
                    with self.annotate("register"):
                        self.engine.register(ev.tenant, demand=ev.demand)
                    self.book.submit(ev.tenant, self.t0 + ev.t, at, ev.demand)
                else:
                    self.engine.submit(ev.tenant, ev.demand)
                    self.book.submit(ev.tenant, self.t0 + ev.t, at, ev.demand)

    def window(self, seconds: float) -> None:
        self.seconds = seconds
        self._next = 0
        self._phase = "window"
        self.t0 = self.clock()
        while True:
            now = self.clock() - self.t0
            if now >= seconds:
                break
            self._submit_due(now)
            if self.book.has_open():
                self._tick()
            else:
                nxt = (self.events[self._next].t
                       if self._next < len(self.events) else seconds)
                self.sleep(max(0.0, min(nxt, seconds) - now))
        self.window_ticks = len(self.ticks)

    def finish(self) -> None:
        """Wait for every request due in the window, ticking on."""
        self._submit_due(self.seconds)
        limit = self.t0 + self.seconds + DRAIN_S
        while self.book.has_open() and self.clock() < limit:
            self._tick()

    def release(self) -> None:
        self.engine = None

    # -- reading back ---------------------------------------------------------

    def _window_latencies(self):
        return self.book.latencies(self.t0, self.t0 + self.seconds)

    def end_to_end(self) -> Dict[str, float]:
        lat, _ = self._window_latencies()
        return {"decision_p50_ms": float(np.percentile(lat, 50) * 1e3),
                "decision_p95_ms": float(np.percentile(lat, 95) * 1e3)}

    def attempted_failed(self):
        lat, undecided = self._window_latencies()
        return int(len(lat)), int(undecided)

    def unanswered(self) -> int:
        return self._window_latencies()[1]

    def decisions(self):
        """Every allocation committed, by phase: ``setup`` (the fill's cold
        joins and the warm tick of every lane) and ``window`` (from the
        window's start on: the answers to the requests due in it)."""
        return self._decisions

    def report(self) -> List[str]:
        ticks = self.ticks[self.setup_ticks:]
        win = self.ticks[self.setup_ticks:self.window_ticks]
        lat, undecided = self._window_latencies()
        due = np.asarray(self.book.due)
        sub = np.asarray(self.book.submitted)
        in_win = (due >= self.t0) & (due < self.t0 + self.seconds)
        lag = (sub - due)[in_win]
        dur = [t["end"] - t["start"] for t in ticks]
        chunks = [t["chunks"] for t in ticks if t["chunks"] is not None]
        trunc = [t["truncated"] for t in ticks if t["truncated"] is not None]
        counts = self.book.counts()
        lines = [
            f"requests due in the window {len(lat)}, undecided {undecided}, "
            f"coalesced (run) {counts['coalesced']}, withdrawn "
            f"{counts['withdrawn']}",
            f"hand-over wait (submit - due; the engine ticks in the "
            f"submitting thread) p50 "
            f"{np.percentile(lag, 50) * 1e3:.3f} ms, max "
            f"{lag.max() * 1e3:.3f} ms" if len(lag) else
            "hand-over wait: no requests",
            f"ticks in the window {len(win)}, after it {len(ticks) - len(win)}"
            f"; tick ms p50 {np.percentile(dur, 50) * 1e3:.3f} max "
            f"{max(dur) * 1e3:.3f}" if dur else "no ticks",
            f"lanes updated per tick {[t['lanes'] for t in ticks]}",
            f"cold joins per tick {[t['cold'] for t in ticks]}",
            f"anytime chunks per tick {chunks}, truncated share "
            f"{np.mean(trunc) if trunc else float('nan'):.3f}",
        ]
        if len(lat):
            lines.append(f"decision latency ms p50 "
                         f"{np.percentile(lat, 50) * 1e3:.3f} p95 "
                         f"{np.percentile(lat, 95) * 1e3:.3f} max "
                         f"{lat.max() * 1e3:.3f}")
        return lines
