"""Median over the traced window's replay ticks of the ``replay/build``
span inside each, in ms: ``make_problem`` for every tenant."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "replay/tick",
                                     "replay/build")
