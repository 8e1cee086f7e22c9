"""Open-loop request bookkeeping: when each request was due, when a
decision answered it.

A request is one demand a tenant submits (an update, or the first demand of
a join). The engine coalesces: a tenant that submits twice before a tick is
served its newest demand, and that decision answers both requests. A
request's latency runs from when it was due to when the tick that committed
a decision for it, or for a newer demand of the same tenant, returned.
Requests of a tenant that departs before a decision are withdrawn and not
counted. Times are host-clock seconds.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


class RequestBook:
    """Every request of a run and the decision that answered it."""

    def __init__(self) -> None:
        self.due: List[float] = []
        self.submitted: List[float] = []
        self.decided: List[float] = []       # nan until decided
        self.coalesced: List[bool] = []
        self.withdrawn: List[bool] = []
        self.demand: List[np.ndarray] = []
        self._open: Dict[str, List[int]] = {}

    def submit(self, tenant: str, due: float, at: float,
               demand: np.ndarray) -> int:
        """Record a request due at ``due`` and handed over at ``at``."""
        self.due.append(due)
        self.submitted.append(at)
        self.decided.append(float("nan"))
        self.coalesced.append(False)
        self.withdrawn.append(False)
        self.demand.append(np.asarray(demand, np.float64))
        rid = len(self.due) - 1
        self._open.setdefault(tenant, []).append(rid)
        return rid

    def withdraw(self, tenant: str) -> None:
        """The tenant departed: its open requests will never be decided."""
        for rid in self._open.pop(tenant, []):
            self.withdrawn[rid] = True

    def has_open(self) -> bool:
        return any(self._open.values())

    def open_requests(self) -> Dict[str, List[int]]:
        """Per tenant, the requests an engine tick started now decides."""
        return {t: list(ids) for t, ids in self._open.items() if ids}

    def decide(self, tenant: str, ids: List[int], at: float) -> int:
        """A decision for the newest of ``ids`` returned at ``at``; returns
        the request it was computed for."""
        for rid in ids:
            self.decided[rid] = at
        for rid in ids[:-1]:
            self.coalesced[rid] = True
        done = set(ids)
        self._open[tenant] = [r for r in self._open.get(tenant, [])
                              if r not in done]
        return ids[-1]

    def latencies(self, t_lo: float, t_hi: float):
        """(latency seconds per request due in ``[t_lo, t_hi)``, number of
        those never decided). An undecided request counts with the time
        from its due to the latest decision, a lower bound on its
        latency."""
        due = np.asarray(self.due)
        dec = np.asarray(self.decided)
        keep = (due >= t_lo) & (due < t_hi) & ~np.asarray(self.withdrawn,
                                                          bool)
        if not keep.any():
            return np.zeros(0), 0
        undecided = keep & np.isnan(dec)
        until = np.nanmax(dec) if np.isfinite(dec).any() else t_hi
        lat = np.where(np.isnan(dec), until, dec) - due
        return lat[keep], int(undecided.sum())

    def counts(self) -> Dict[str, int]:
        return {"requests": len(self.due),
                "coalesced": int(np.sum(self.coalesced)),
                "withdrawn": int(np.sum(self.withdrawn))}
