"""Median over the traced window's serve ticks of the ``serve/apply`` span
inside each, in ms: the copies of the answers to the host, then
``apply_counts`` and the decision record of every lane decided."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "serve/tick", "serve/apply")
