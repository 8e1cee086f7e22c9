"""Median ``serve/tick`` span of the traced window, in ms: one engine tick,
cold joins, problem builds, stacking, the warm solve and the commits."""
import statistics


def read(ctx):
    ticks = [s.dur_us / 1e3 for s in ctx.spans if s.name == "serve/tick"]
    return statistics.median(ticks) if ticks else None
