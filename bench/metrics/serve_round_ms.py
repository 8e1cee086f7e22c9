"""Median over the traced window's serve ticks of the ``fleet/round`` span
inside each, in ms: the anytime solve's finalize program (rounding,
masking and scoring of every lane), fenced."""
from bench import spanclock


def read(ctx):
    return spanclock.median_per_tick(ctx.spans, "serve/tick", "fleet/round")
