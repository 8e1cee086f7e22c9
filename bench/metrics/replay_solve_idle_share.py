"""Share of the traced replay window's fenced ``replay/solve`` time in
which no operation ran on the chip, in %: dispatch and host work inside
the cold and warm fleet solves, placed on the trace's clock by
``bench/spanclock.py``."""
from bench import spanclock


def read(ctx):
    return spanclock.idle_share(ctx.trace, ctx.spans, "replay/solve")
