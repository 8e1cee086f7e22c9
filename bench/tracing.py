"""Profiler trace of the measured window, reduced to what the metrics read.

``capture`` runs the window under ``jax.profiler`` and keeps, from the
written trace, the device operations of each chip and the harness's own
host annotations. The reduction is plain arithmetic on those intervals:

* busy time — the length of the union of a chip's operation intervals,
  averaged over the chips used;
* idle share — one minus busy time over the traced window;
* kernel time — the summed durations of the operations whose name matches;
* breakdown — the operations that took most device time, and the longest
  gaps between busy intervals, labelled with the innermost harness
  annotation open at the gap's middle.

Times are nanoseconds on the trace's own clock, which the profiler shares
between host and device events.
"""
from __future__ import annotations

import glob
import os
import tempfile
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]   # name, start_ns, duration_ns

# the device planes' line that holds one event per operation run
OPS_LINE = "XLA Ops"
# the harness's own host annotations; "window" spans the traced window
HOST_LABELS = ("window", "tick", "submit", "register", "depart", "segment")


@dataclass
class DeviceTrace:
    """Device operations per chip and harness annotations, on one clock."""

    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Union of each chip's operation intervals inside the window,
        averaged over the chips."""
        if not self.ops:
            return 0.0
        lo, hi = self.window
        per = [union_ns(clip(ev, lo, hi)) for ev in self.ops.values()]
        return sum(per) / len(per) / 1e9

    def idle_share(self) -> Optional[float]:
        """Share of the window in which no operation ran (0-1)."""
        if self.window_s <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def kernel_events(self, match: Callable[[str], bool]) -> List[Interval]:
        lo, hi = self.window
        return [e for ev in self.ops.values() for e in clip(ev, lo, hi)
                if match(e[0])]

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        """Top device operations by time and longest idle gaps by label."""
        lo, hi = self.window
        total: Dict[str, float] = {}
        for ev in self.ops.values():
            for name, _, dur in clip(ev, lo, hi):
                total[name] = total.get(name, 0.0) + dur
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        gaps = [g for ev in self.ops.values()
                for g in idle_gaps(clip(ev, lo, hi), lo, hi)]
        gaps.sort(key=lambda g: g[0] - g[1])
        return {"device_ops": [[n, d / 1e9] for n, d in ops],
                "idle_gaps": [[self.label_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in gaps[:top]]}

    def label_at(self, t: float) -> str:
        """The innermost harness annotation open at ``t``."""
        best, depth = "none", None
        for name, start, dur in self.host:
            if start <= t < start + dur and (depth is None or dur < depth):
                best, depth = name, dur
        return best


def clip(events: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Events cut to ``[lo, hi)``; those wholly outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def merged(events: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Union of the events' intervals as sorted disjoint (start, end)."""
    out: List[Tuple[float, float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def union_ns(events: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged(events))


def idle_gaps(events: Sequence[Interval], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The (start, end) stretches of ``[lo, hi)`` no event covers."""
    gaps, reach = [], lo
    for s, e in merged(events):
        if s > reach:
            gaps.append((reach, s))
        reach = max(reach, e)
    if hi > reach:
        gaps.append((reach, hi))
    return gaps


def op_name(hlo: str) -> str:
    """An operation's event name is its whole HLO instruction; keep the
    instruction's name, and mark custom calls (Pallas kernels), e.g.
    ``%fleet_value_and_grad.24 custom-call``."""
    name = hlo.split(" = ", 1)[0]
    return f"{name} custom-call" if " custom-call(" in hlo else name


def read_profile(path: str) -> DeviceTrace:
    """Reduce one ``.xplane.pb`` to device operations and host labels."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = DeviceTrace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[plane.name] = [
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host += [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events
                               if e.name in HOST_LABELS]
    windows = [e for e in trace.host if e[0] == "window"]
    if windows:
        _, start, dur = windows[0]
        trace.window = (start, start + dur)
    return trace


class Profile:
    """The profiler around the window: :meth:`start` and :meth:`stop`
    bracket what is traced; :meth:`read` reduces the written trace later,
    so that reading it does not delay what follows the window."""

    def __init__(self):
        self._tmp = tempfile.TemporaryDirectory()

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def read(self) -> Optional[DeviceTrace]:
        """The reduced trace (None if none was written); removes the
        written files."""
        try:
            files = glob.glob(os.path.join(self._tmp.name, "**",
                                           "*.xplane.pb"), recursive=True)
            return (read_profile(max(files, key=os.path.getmtime))
                    if files else None)
        finally:
            self._tmp.cleanup()


def annotate(name: str):
    """A host annotation the trace keeps (see :data:`HOST_LABELS`)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
