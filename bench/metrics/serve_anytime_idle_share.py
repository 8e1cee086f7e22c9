"""Share of the traced serve window's ``fleet/anytime`` time in which no
operation ran on the chip, in %: the host's round trip between anytime
chunks (the ``done`` copy and the clock read), placed on the trace's
clock by ``bench/spanclock.py``."""
from bench import spanclock


def read(ctx):
    return spanclock.idle_share(ctx.trace, ctx.spans, "fleet/anytime")
