"""Median over the traced window's replay ticks of the fenced
``replay/solve`` spans inside a tick, in ms: the device solve (cold fleet
solve or warm step) as the host waits for it."""
import statistics


def per_tick(spans):
    """(tick span, summed replay/solve ms inside it) per replay tick."""
    ticks = [s for s in spans if s.name == "replay/tick"]
    solves = [s for s in spans if s.name == "replay/solve"]
    out = []
    for t in ticks:
        inside = sum(s.dur_us for s in solves
                     if t.ts_us <= s.ts_us and s.ts_us + s.dur_us
                     <= t.ts_us + t.dur_us)
        out.append((t, inside / 1e3))
    return out


def read(ctx):
    rows = per_tick(ctx.spans)
    return statistics.median(ms for _, ms in rows) if rows else None
