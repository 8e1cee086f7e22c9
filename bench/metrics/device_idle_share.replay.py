"""Share of the traced replay window in which no operation ran on the
chip, in %."""


def read(ctx):
    idle = None if ctx.trace is None else ctx.trace.idle_share()
    return None if idle is None else 100.0 * idle
