"""Share of the fleet ``alloc_objective`` kernel's roofline, in %: the
least time its calls could take on this chip (larger of operations over
peak FLOP/s and bytes over peak bandwidth, counted at the true shapes by
``bench/rooflines/alloc_objective.py``) over the device time of its events
in the trace."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_roofline_alloc_objective",
    Path(__file__).resolve().parent.parent / "rooflines"
    / "alloc_objective.py")
_roof = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_roof)

# the Pallas fleet kernel in a TPU trace: a custom call that XLA names
# after the jitted wrapper, ``%fleet_value_and_grad.<n>``
KERNEL_PREFIX = "%fleet_value_and_grad"


def is_kernel(name):
    return name.startswith(KERNEL_PREFIX) and name.endswith(" custom-call")


def read(ctx):
    if ctx.trace is None:
        return None
    events = ctx.trace.kernel_events(is_kernel)
    if not events:
        return None
    least, _ = _roof.least_seconds(**ctx.driver.kernel_shapes,
                                   peak_flops=ctx.peaks["flops_per_s"],
                                   peak_bytes=ctx.peaks["bytes_per_s"])
    device_s = sum(dur for _, _, dur in events) / 1e9
    return 100.0 * least * len(events) / device_s
