"""The program's own spans on the device trace's clock.

The harness installs the ``repro.obs`` recorder and opens the ``window``
annotation in one ``with`` statement, the recorder first. A span's
``ts_us`` counts microseconds from the recorder's installation, so on the
trace's clock it starts at ``window[0] + ts_us * 1e3`` nanoseconds, to
within the microseconds between the two.

On that clock a span's interval can be laid over the device operations:
the time inside it in which no operation ran on the chip is the time the
chip waited for the host there.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Iterable, List, Optional, Sequence, Tuple

from bench.tracing import DeviceTrace, merged


def interval_ns(trace: DeviceTrace, span) -> Tuple[float, float]:
    """``(start, end)`` of a recorded span on the trace's clock, in ns."""
    start = trace.window[0] + span.ts_us * 1e3
    return start, start + span.dur_us * 1e3


def _covered(busy: List[Tuple[float, float]], starts: List[float],
             lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the sorted disjoint ``busy`` covers."""
    total = 0.0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(busy) and busy[i][0] < hi:
        s, e = max(busy[i][0], lo), min(busy[i][1], hi)
        if e > s:
            total += e - s
        i += 1
    return total


def idle_inside(trace: DeviceTrace,
                intervals: Iterable[Tuple[float, float]]
                ) -> Tuple[float, float]:
    """``(idle, total)`` ns over ``(start, end)`` intervals on the trace's
    clock, each cut to the traced window: the time in them in which no
    operation ran, averaged over the chips, and their summed length."""
    lo, hi = trace.window
    chips = [merged(ev) for ev in trace.ops.values()]
    starts = [[s for s, _ in busy] for busy in chips]
    idle = total = 0.0
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        total += e - s
        busy = sum(_covered(b, st, s, e) for b, st in zip(chips, starts))
        idle += (e - s) - (busy / len(chips) if chips else 0.0)
    return idle, total


def idle_share(trace: Optional[DeviceTrace], spans: Sequence,
               name: str) -> Optional[float]:
    """Share, in %, of the time inside the spans called ``name`` in which
    no operation ran on the chip; None without a trace, its operations or
    such a span."""
    if trace is None or not trace.ops:
        return None
    idle, total = idle_inside(trace, [interval_ns(trace, s) for s in spans
                                      if s.name == name])
    return 100.0 * idle / total if total > 0 else None


def inside(outer, spans: Sequence, name: str) -> List:
    """The spans called ``name`` that lie within ``outer``'s interval."""
    end = outer.ts_us + outer.dur_us
    return [s for s in spans if s.name == name
            and outer.ts_us <= s.ts_us and s.ts_us + s.dur_us <= end]


def median_per_tick(spans: Sequence, tick: str, name: str
                    ) -> Optional[float]:
    """Median over the ``tick`` spans of the summed ``name`` spans inside
    each, in ms, over the ticks that hold one; None where none does."""
    per = [sum(s.dur_us for s in inside(t, spans, name)) / 1e3
           for t in spans if t.name == tick and inside(t, spans, name)]
    return statistics.median(per) if per else None
