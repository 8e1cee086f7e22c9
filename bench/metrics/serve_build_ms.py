"""Median over the traced window's serve ticks of the ``serve/build`` span
inside each, in ms: ``make_problem`` for every lane the tick takes."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "serve/tick", "serve/build")
