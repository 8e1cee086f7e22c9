"""Median over the traced window's serve ticks of the ``serve/stack`` span
inside each, in ms: ``stack_problems`` over every lane and the previous
allocations' assembly."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "serve/tick", "serve/stack")
