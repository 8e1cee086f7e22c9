"""Median over every lane a traced window's serve tick took of its
``queue_wait_ms`` tag on ``serve/tick``, in ms: the tick's start minus the
time the lane's pending demand arrived, so the wait for the tick in
flight to finish."""
import statistics


def read(ctx):
    waits = [w for s in ctx.spans if s.name == "serve/tick"
             for w in s.tags.get("queue_wait_ms", ())]
    return statistics.median(waits) if waits else None
