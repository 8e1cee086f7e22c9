"""The one traffic generator: reads a traffic file's parameters and a seed.

A traffic file (``bench/traffic/<name>.json``) names its ``kind`` and its
parameters; nothing else about a mix lives in code.

* ``open_loop`` — live tenants of a ``ServeEngine``. Every tenant submits a
  new demand every ``scan_s`` seconds at a phase of its own: its j-th scan
  brings tick j of its ``trace_len``-tick trace (tick 0 is its demand when
  the window opens), so a trace tick lasts one scan; with ``churn``,
  every ``churn.every_s`` seconds ``churn.count`` tenants depart and as many
  new tenants register (the first time half an interval into the window),
  who then update every ``scan_s`` seconds too.
* ``replay_segments`` — recorded traces for an offline replay: every tenant
  gets a ``ticks``-long trace of a kind from ``mix`` (alternating).

``trace_args`` maps a trace kind to keyword arguments of its generator
(say a diurnal ``period`` in ticks). The flash-crowd generator starts its
bursts between 10% and 90% of the trace, so a trace as long as the window
has its bursts inside the window.

Demand sizes follow the serve demo's tenants: ``BASE_DEMAND`` scaled by a
size from 1x to 50x on a log scale, jittered +-50% per resource. Demand
steps follow the flash-crowd and diurnal generators of
``repro.fleet.traces``, copied here so that later changes to the program
cannot move the traffic.

Seeds change which tenant does what, not how much work there is. In
``open_loop`` the tenants themselves (sizes, jitter, demand steps, and the
pool that joins) are drawn once from ``POPULATION_SEED``; the run's seed
deals them to the arrival phases, picks who departs and deals the
joiners. In ``replay_segments`` every seed gets the same set of sizes,
permuted.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

# base demand per resource (cpu, mem_gb, net_units, storage_gb)
BASE_DEMAND = np.array([8.0, 16.0, 4.0, 100.0])
# the open-loop tenant population is the same for every run seed
POPULATION_SEED = 0


def _noise(rng, T: int, m: int, level: float) -> np.ndarray:
    return np.exp(level * rng.standard_normal((T, m)))


def _positive(trace: np.ndarray, base: np.ndarray) -> np.ndarray:
    return np.maximum(trace, 0.05 * base[None, :])


def diurnal_trace(base, T: int, *, amplitude: float = 0.4,
                  period: float = 24.0, noise: float = 0.03,
                  seed: int = 0) -> np.ndarray:
    """Day/night sinusoid: base * (1 + amplitude * sin(2 pi t / period))."""
    base = np.asarray(base, np.float64)
    rng = np.random.default_rng(seed)
    t = np.arange(T, dtype=np.float64)
    wave = 1.0 + amplitude * np.sin(2 * np.pi * t / period)
    return _positive(base[None, :] * wave[:, None]
                     * _noise(rng, T, len(base), noise), base)


def flash_crowd_trace(base, T: int, *, n_bursts: int = 2,
                      burst_scale: float = 3.0, decay: float = 6.0,
                      noise: float = 0.03, seed: int = 0) -> np.ndarray:
    """Baseline demand with sudden spikes that decay exponentially."""
    base = np.asarray(base, np.float64)
    rng = np.random.default_rng(seed)
    t = np.arange(T, dtype=np.float64)
    mult = np.ones(T)
    for start in sorted(rng.uniform(0.1 * T, 0.9 * T, size=n_bursts)):
        scale = burst_scale * rng.uniform(0.6, 1.4)
        after = t >= start
        mult = mult + after * (scale - 1.0) * np.exp(-(t - start) / decay)
    return _positive(base[None, :] * mult[:, None]
                     * _noise(rng, T, len(base), noise), base)


TRACE_KINDS = {"diurnal": diurnal_trace, "flash_crowd": flash_crowd_trace}


def tenant_demands(n: int, rng: np.random.Generator,
                   size_range=(1.0, 50.0), jitter: float = 0.5) -> np.ndarray:
    """(n, 4) base demands: sizes spread evenly over ``size_range`` on a
    log scale (the same set for every seed, permuted), each resource
    jittered by up to ``jitter`` either way."""
    lo, hi = np.log(size_range[0]), np.log(size_range[1])
    size = np.exp(lo + (np.arange(n) + 0.5) / n * (hi - lo))
    size = rng.permutation(size)
    return BASE_DEMAND * size[:, None] * rng.uniform(1 - jitter, 1 + jitter,
                                                    (n, 4))


def _tenant_trace(kind: str, base: np.ndarray, T: int,
                  rng: np.random.Generator, params: Dict) -> np.ndarray:
    args = params.get("trace_args", {}).get(kind, {})
    return TRACE_KINDS[kind](base, T, seed=int(rng.integers(2**31)), **args)


@dataclass
class Event:
    """One scheduled arrival, ``t`` seconds after the window opens:
    ``update`` (a new demand), ``join`` (register with a first demand) or
    ``depart``."""

    t: float
    kind: str
    tenant: str
    demand: Optional[np.ndarray] = None


def open_loop(params: Dict, lanes: int, seconds: float, seed: int
              ) -> Tuple[List[Tuple[str, np.ndarray]], List[Event]]:
    """The tenants live before the window opens (name, first demand) and
    every event due in ``[0, seconds)``, in time order."""
    pop = np.random.default_rng(POPULATION_SEED)
    rng = np.random.default_rng(seed)
    scan = float(params["scan_s"])
    T = int(params["trace_len"])
    kind = params.get("trace", "flash_crowd")
    size_range = params.get("size_range", (1.0, 50.0))
    jitter = params.get("jitter", 0.5)
    traces = [_tenant_trace(kind, b, T, pop, params)
              for b in tenant_demands(lanes, pop, size_range, jitter)]
    phases = rng.permutation((np.arange(lanes) + 0.5) / lanes * scan)
    initial, events = [], []
    for k, tr in enumerate(traces):
        name = f"tenant-{k}"
        initial.append((name, tr[0]))
        events += [Event(t, "update", name, tr[(j + 1) % T])
                   for j, t in enumerate(np.arange(phases[k], seconds, scan))]
    churn = params.get("churn")
    if churn:
        every, count = float(churn["every_s"]), int(churn["count"])
        times = np.arange(every / 2, seconds, every)
        pool = [_tenant_trace(kind, b, T, pop, params) for b in tenant_demands(
            count * len(times), pop, size_range, jitter)]
        deal = rng.permutation(len(pool))
        roster = [name for name, _ in initial]
        joined = 0
        for t in times:
            gone = set(rng.choice(sorted(roster), count, replace=False))
            roster = [n for n in roster if n not in gone]
            events = [e for e in events
                      if not (e.tenant in gone and e.t >= t)]
            events += [Event(t, "depart", n) for n in sorted(gone)]
            for _ in range(count):
                name, tr = f"joiner-{joined}", pool[deal[joined]]
                joined += 1
                roster.append(name)
                events.append(Event(t, "join", name, tr[0]))
                events += [Event(u, "update", name, tr[j % T])
                           for j, u in enumerate(
                               np.arange(t + scan, seconds, scan), start=1)]
    order = {"depart": 0, "join": 1, "update": 2}
    events.sort(key=lambda e: (e.t, order[e.kind], e.tenant))
    return initial, events


def replay_traces(params: Dict, tenants: int, seed: int
                  ) -> List[Tuple[str, np.ndarray]]:
    """(name, (ticks, 4) trace) per tenant, kinds alternating over
    ``params["mix"]``."""
    rng = np.random.default_rng(seed)
    bases = tenant_demands(tenants, rng, params.get("size_range", (1, 50)),
                           params.get("jitter", 0.5))
    mix = params["mix"]
    return [(f"tenant-{k}", _tenant_trace(mix[k % len(mix)], bases[k],
                                          int(params["ticks"]), rng, params))
            for k in range(tenants)]
