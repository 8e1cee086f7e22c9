"""Median over the traced window's serve ticks of the ``fleet/anytime``
span inside each over the tick's ``iters`` tag, in ms: the chunked warm
solve's time per PGD iteration. ``iters`` is the most iterations of a lane
the tick decided; lanes riding along undecided may run more, so this is
an upper bound."""
import statistics

from bench import spanclock


def read(ctx):
    per = []
    for t in ctx.spans:
        if t.name != "serve/tick" or not t.tags.get("iters"):
            continue
        runs = spanclock.inside(t, ctx.spans, "fleet/anytime")
        if runs:
            per.append(sum(s.dur_us for s in runs) / 1e3 / t.tags["iters"])
    return statistics.median(per) if per else None
