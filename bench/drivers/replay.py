"""Driver for the offline what-if replay, ``replay_fleet`` in segments.

Set-up makes every tenant's trace and replays a short warm-up segment
(the cold fleet solve and the warm step, so both programs are compiled).
The window replays segment after segment, each started cold: segment k
replays ticks [j s, (j + 1) s) of the traces, s = ``segment_ticks`` and
j = k mod (``ticks`` / s), so the segments walk the whole trace and start
over. The segment in flight when the window closes finishes and counts,
so the rate is all tenant-ticks committed over all the time the segments
took. Every tenant runs ``n_starts`` cold multistart points, as the
configuration states.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple


from bench import tracing
from bench.catalog import capacity_matrix, catalog_rows
from bench.reference.check import sparse
from bench.traffic import replay_traces


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 clock=time.perf_counter):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.clock = clock
        if traffic["kind"] != "replay_segments":
            raise ValueError(f"the replay driver runs replay_segments "
                             f"traffic, not {traffic['kind']!r}")
        self.rows = catalog_rows(**config["catalog"])
        self.capacities = capacity_matrix(self.rows)
        self._decisions: List[Tuple] = []
        self.segments: List[Dict] = []
        self.missing = 0
        self.annotate = tracing.annotate

    @property
    def kernel_shapes(self) -> Dict[str, int]:
        """True shapes of the cold fleet solve's per-iterate kernel call:
        every tenant at each of its ``n_starts`` multistart points."""
        providers = len({r[1] for r in self.rows})
        m, n = self.capacities.shape
        return {"B": int(self.config["tenants"]),
                "T": int(self.config["n_starts"]), "n": n, "m": m,
                "p": providers}

    def setup(self, seconds: float) -> None:
        from repro.core.catalog import Catalog, InstanceType

        self.catalog = Catalog([InstanceType(*r) for r in self.rows])
        self.traces = replay_traces(self.traffic, int(self.config["tenants"]),
                                    self.seed)
        ticks = int(self.traffic["ticks"])
        warm = int(self.traffic["warmup_ticks"])
        self._segment(ticks - warm, ticks, record=False)

    def _segment(self, lo: int, hi: int, record: bool = True) -> None:
        from repro.fleet.replay import TenantSpec, replay_fleet

        specs = [TenantSpec(name, tr[lo:hi],
                            n_starts=int(self.config["n_starts"]))
                 for name, tr in self.traces]
        t0 = self.clock()
        with self.annotate("segment"):
            res = replay_fleet(self.catalog, specs,
                               **self.config["replay_args"])
        end = self.clock()
        if not record:
            return
        committed = 0
        for (name, tr), tenant in zip(self.traces, res.tenants):
            steps = tenant.steps
            committed += len(steps)
            self.missing += max(0, (hi - lo) - len(steps))
            for t, step in enumerate(steps[:hi - lo]):
                idx, vals = sparse(step.counts)
                self._decisions.append((tr[lo + t], idx, vals))
        self.segments.append({"ticks": (lo, hi), "start": t0, "end": end,
                              "tenant_ticks": committed,
                              "attempted": len(specs) * (hi - lo)})

    def window(self, seconds: float) -> None:
        seg = int(self.traffic["segment_ticks"])
        n_seg = int(self.traffic["ticks"]) // seg
        t0 = self.clock()
        k = 0
        while self.clock() - t0 < seconds:
            lo = (k % n_seg) * seg
            self._segment(lo, lo + seg)
            k += 1

    def finish(self) -> None:
        pass

    def release(self) -> None:
        self.catalog = None

    def end_to_end(self) -> Dict[str, float]:
        ticks = sum(s["tenant_ticks"] for s in self.segments)
        wall = sum(s["end"] - s["start"] for s in self.segments)
        return {"replay_tenant_ticks_per_s": ticks / wall}

    def attempted_failed(self):
        return (sum(s["attempted"] for s in self.segments), self.missing)

    def unanswered(self) -> int:
        return self.missing

    def decisions(self):
        """Every allocation committed in the window, by phase (the warm-up
        segment's are not kept)."""
        return {"window": self._decisions}

    def report(self) -> List[str]:
        walls = [s["end"] - s["start"] for s in self.segments]
        return [f"segments {len(self.segments)}: ticks "
                f"{[s['ticks'] for s in self.segments]}, seconds "
                f"{[round(w, 3) for w in walls]}",
                f"tenant-ticks committed "
                f"{sum(s['tenant_ticks'] for s in self.segments)}, missing "
                f"{self.missing}"]
