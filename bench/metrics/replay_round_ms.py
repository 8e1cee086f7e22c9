"""Median over the traced window's replay ticks of the ``replay/round``
spans inside each, summed, in ms: ``apply_counts`` and the health and
metric bookkeeping of every tenant."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "replay/tick",
                                     "replay/round")
