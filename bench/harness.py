"""The benchmark harness: one cell, one seed, one measured window.

Everything a cell is made of is found by name from files:

* ``BENCHMARK.json`` names the cell's configuration, traffic and metrics;
* ``bench/configs/<config>.json`` (the ``file`` of the configuration) holds
  the deployment, and its ``engine`` picks the driver in ``bench/drivers``;
* ``bench/traffic/<traffic>.json`` holds the mix's parameters, which the
  one generator in ``bench/traffic.py`` reads;
* ``bench/metrics/<metric>.py`` reads one per-layer metric;
* ``bench/reference/limits.json`` holds the limit of each number the float64
  reference compares.

A run: set-up (build the system, warm up the cell's shapes) -> the window,
``--seconds`` long, with the profiler on when ``--trace 1`` -> answers due
in the window are waited for (the profiler stops after that wait, and only
the window is read from its trace) -> device memory is read -> the
reference judges every committed allocation -> one JSON line.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _for_cell(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Optional[Path] = None,
              traffic_dir: Optional[Path] = None) -> Cell:
    """Resolve cell -> configuration -> traffic -> metrics by name."""
    benchmark = benchmark or ROOT / "BENCHMARK.json"
    spec = json.loads(Path(benchmark).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((Path(benchmark).parent
                         / configs[w["config"]]["file"]).read_text())
    traffic_dir = traffic_dir or BENCH / "traffic"
    traffic = json.loads((traffic_dir / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _for_cell(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _for_cell(m, name)])


def load_driver(engine: str):
    """The driver class of a configuration's ``engine``."""
    return importlib.import_module(f"bench.drivers.{engine}").Driver


def load_reader(metric: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_limits() -> Dict:
    """The limit of each number the reference compares."""
    path = BENCH / "reference" / "limits.json"
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()}


class CompileClock:
    """Host intervals JAX spends tracing, lowering and compiling (or
    reading the persistent cache), through ``jax.monitoring``. Nested
    events overlap, so time is the length of the union of the intervals."""

    def __init__(self):
        import jax

        self.intervals: List = []
        self.compiles = 0
        self.cache_hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()
            self.intervals.append((end - secs, end))
            if event == _COMPILE_EVENTS[0]:
                self.traces += 1
            elif event == _COMPILE_EVENTS[-1]:
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds_between(self, t0: float, t1: float) -> float:
        """Length of the union of compile intervals inside [t0, t1]."""
        total, reach = 0.0, t0
        for lo, hi in sorted(self.intervals):
            lo, hi = max(lo, reach), min(hi, t1)
            if hi > lo:
                total += hi - lo
                reach = hi
        return total

    def snapshot(self):
        return self.compiles, self.cache_hits, self.traces


def require_devices(chips: int):
    """The first JAX device must be a TPU and there must be ``chips`` of
    them; exits non-zero naming what was found otherwise (no fallback)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's first device is "
                         f"platform {devs[0].platform!r} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


def _info(*parts) -> None:
    print("bench:", *parts, flush=True)


@dataclass
class Judgement:
    numbers: Dict[str, float] = field(default_factory=dict)
    limits: Dict[str, float] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(np.isfinite(self.numbers[k])
                   and self.numbers[k] <= self.limits[k]
                   for k in self.limits)

    def as_dict(self) -> Dict:
        return {k: {"value": self.numbers[k], "limit": self.limits[k]}
                for k in self.limits}


def judge(driver, limits: Dict[str, float]) -> Judgement:
    """The reference's readings of every allocation the run committed, in
    set-up and in the window, plus the requests that never got an answer,
    each beside its limit."""
    from bench.reference.check import judge as reference

    every = [d for phase in driver.decisions().values() for d in phase]
    numbers = reference(driver.capacities, every)
    numbers["unanswered"] = driver.unanswered()
    if numbers["decisions"] == 0:
        numbers["shortfall_raw"] = numbers["removable_share"] = float("inf")
    return Judgement(numbers={k: float(v) for k, v in numbers.items()},
                     limits={k: float(limits[k]) for k in limits})


def judge_phases(driver) -> Dict[str, Dict[str, float]]:
    """The same readings phase by phase (``setup``, ``window``), so that a
    failure shows where it came from."""
    from bench.reference.check import judge as reference

    return {phase: reference(driver.capacities, decisions)
            for phase, decisions in driver.decisions().items() if decisions}


def device_block(devs, memory_peak: Optional[int]) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak}


def memory_peak(devs) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devs, clock: CompileClock,
             peaks: Optional[Dict] = None) -> Dict:
    """Run one cell once; returns the result line as a dict (``checks``
    last). Info goes to stdout as it comes, the checks to stderr last."""
    from bench import tracing

    driver = load_driver(cell.config["engine"])(cell.config, cell.traffic,
                                                seed)
    driver.setup(seconds)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    _info(f"setup {setup_s:.3f} s, of which compile "
          f"{clock.seconds_between(t_start, t_window):.3f} s "
          f"({clock.compiles} programs compiled, {clock.cache_hits} read "
          f"from the persistent cache)")
    before = clock.snapshot()
    recorder = profile = None
    if trace:
        from repro.obs.telemetry import telemetry
        profile = tracing.Profile()
        profile.start()
        try:
            with telemetry() as recorder, tracing.annotate("window"):
                driver.window(seconds)
            after = clock.snapshot()
            driver.finish()
        finally:
            profile.stop()
    else:
        driver.window(seconds)
        after = clock.snapshot()
        driver.finish()
    in_window = [a - b for a, b in zip(after, before)]
    _info(f"programs compiled inside the window: {in_window[0]} "
          f"(read from cache {in_window[1]}, traced {in_window[2]})")
    for line in driver.report():
        _info(line)
    peak = memory_peak(devs)
    driver.release()
    verdict = judge(driver, load_limits())
    for phase, numbers in judge_phases(driver).items():
        _info(f"{phase}'s allocations: "
              + ", ".join(f"{k} {v!r}" for k, v in numbers.items()))
    device_trace = profile.read() if profile is not None else None
    _info(f"reference judged {int(verdict.numbers['decisions'])} "
          f"allocations")

    metrics: Dict[str, Dict] = {}
    device = device_block(devs, peak)
    breakdown = None
    if trace:
        if device_trace is not None:
            device["busy_s"] = device_trace.busy_s()
            device["window_s"] = device_trace.window_s
            breakdown = device_trace.breakdown()
        ctx = SimpleNamespace(spans=list(recorder.events),
                              trace=device_trace, peaks=peaks, driver=driver)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = driver.end_to_end()
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    attempted, failed = driver.attempted_failed()
    result = {"correct": verdict.correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict.as_dict()
    for name, entry in result["checks"].items():
        print(f"check {name}: {entry['value']!r} (limit {entry['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def parse_args(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    """Entry point of ``bench/run.py``."""
    args = parse_args(argv)
    cell = load_cell(args.workload)
    devs = require_devices(cell.chips)
    import jax
    from repro.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks_table = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks_table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    clock = CompileClock()
    _info(f"device {devs[0].platform} {kind} x{len(devs)}; jax "
          f"{jax.__version__}; compile cache {cache_dir}; cell {cell.name} "
          f"(config {cell.config_name}, traffic {cell.traffic_name}); "
          f"seed {args.seed}; {args.seconds} s; trace {args.trace}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devs, clock, peaks=peaks_table[kind])
    print(json.dumps(result), flush=True)
    return 0
