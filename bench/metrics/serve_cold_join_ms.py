"""Median ``serve/cold`` span of the traced window, in ms: one joining
tenant's cold multistart solve and rounding inside ``ServeEngine.tick``."""
import statistics


def read(ctx):
    joins = [s.dur_us / 1e3 for s in ctx.spans if s.name == "serve/cold"]
    return statistics.median(joins) if joins else None
