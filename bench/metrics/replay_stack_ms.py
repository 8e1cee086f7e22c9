"""Median over the traced window's replay ticks of the ``replay/stack``
spans inside each, summed, in ms: stacking every bucket, and on warm
ticks the previous allocations and churn bounds."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "replay/tick",
                                     "replay/stack")
