"""Median over the traced window's replay ticks of ``replay/tick`` minus
the ``replay/solve`` spans inside it, in ms: problem builds, stacking,
start points, rounding commits and metrics on the host."""
import importlib.util
import statistics
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_replay_solve_ms",
    Path(__file__).with_name("replay_solve_ms.py"))
_solve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_solve)


def read(ctx):
    rows = _solve.per_tick(ctx.spans)
    return (statistics.median(t.dur_us / 1e3 - ms for t, ms in rows)
            if rows else None)
