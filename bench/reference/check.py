"""Plain float64 reference for committed allocations.

Imports nothing of the system under test. It judges each committed
allocation against the demand it answers, with the catalog's capacities
that the benchmark generated itself (``bench.catalog``):

* ``shortfall_raw`` — the largest amount, in raw resource units, by which
  any allocation leaves any resource of its demand uncovered
  (``demand - K x``, float64). The configuration's guarantee is that every
  committed allocation covers its demand to 1e-6 raw units.
* ``removable_share`` — the share of allocations that keep a node they do
  not need: one whose removal still leaves every resource of the demand
  covered (``K (x - e_j) >= demand`` in float64, for some type ``j`` the
  allocation uses). Rounding scales every allocation down until no node
  can go, so sound runs read 0 or close to it; an allocation that keeps
  nodes it does not need costs its tenant for nothing.
* ``bad_counts`` — allocations with a count that is negative, not whole or
  not finite.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

Decision = Tuple[np.ndarray, np.ndarray, np.ndarray]   # demand, idx, counts


def sparse(counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(indices, values) of the nonzero counts of a dense allocation."""
    counts = np.asarray(counts, np.float64)
    idx = np.flatnonzero(counts)
    return idx, counts[idx]


def judge(K: np.ndarray, decisions: Iterable[Decision]) -> Dict[str, float]:
    """The reference's readings over ``decisions`` (see module docstring);
    ``K`` is the (m, n) float64 capacity matrix."""
    K = np.asarray(K, np.float64)
    shortfall, removable, bad, count = -np.inf, 0, 0, 0
    for demand, idx, vals in decisions:
        count += 1
        demand = np.asarray(demand, np.float64)
        vals = np.asarray(vals, np.float64)
        if (not np.all(np.isfinite(vals)) or np.any(vals < 0)
                or np.any(vals != np.round(vals))):
            bad += 1
            continue
        Ks = K[:, idx]
        slack = Ks @ vals - demand                          # (m,)
        shortfall = max(shortfall, float(np.max(-slack)))
        used = vals >= 1.0
        if np.any(np.all(slack[:, None] - Ks[:, used] >= 0, axis=0)):
            removable += 1
    return {"decisions": count, "shortfall_raw": shortfall,
            "removable_share": removable / count if count else 0.0,
            "bad_counts": bad}
