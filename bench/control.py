"""The control the correctness check has to fail.

The configuration guarantees that every committed allocation covers its
demand in float64, with capacities stated in float32. The program
evaluates every constraint contraction of ``K`` at ``Precision.HIGHEST``
for that reason (``CONSTRAINT_PRECISION`` in ``repro.core.objective``, read
by ``repro.fleet.solver`` too): at a TPU's default precision the batched
contractions round their operands to bfloat16, ``K x`` errs by ~0.4%, and
rounding commits allocations short of demand (37 of 256 cold tenants at the
full catalog on a v5e). The control is that path of the program switched
to ``Precision.DEFAULT`` (:func:`default_precision`). A run of it must come
out not correct.

On a CPU the switch changes no bit, so the CPU tests take
:func:`bf16_capacities` in its place: every constraint contraction with its
capacity matrix rounded to bfloat16, the rounding the default precision
applies on the chip, and the rest in float32.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

runs the cell once per seed in one process with the control on (and with
``--sound`` first once per seed with it off), and prints each run's checks
as one JSON line. With ``--fault <name>`` (repeatable) it runs the first
seed once with each fault of ``bench/faults.py`` planted instead of the
control. The benchmark's own runs never use it.
"""
from __future__ import annotations

import json
import sys
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path


def _bf16(a):
    import jax.numpy as jnp

    return a.astype(jnp.bfloat16).astype(jnp.float32)


@contextmanager
def default_precision():
    """The program's constraint contractions at ``Precision.DEFAULT`` for
    as long as the block runs. Compiled programs are dropped on the way in
    and out, so none built under one setting serves the other."""
    import jax
    import repro.core.objective as obj
    import repro.fleet.solver as fleet

    saved = obj.CONSTRAINT_PRECISION, fleet.CONSTRAINT_PRECISION
    jax.clear_caches()
    obj.CONSTRAINT_PRECISION = fleet.CONSTRAINT_PRECISION = \
        jax.lax.Precision.DEFAULT
    try:
        yield
    finally:
        obj.CONSTRAINT_PRECISION, fleet.CONSTRAINT_PRECISION = saved
        jax.clear_caches()


@contextmanager
def bf16_capacities():
    """Every constraint contraction of the program with its capacity
    matrix in bfloat16, for as long as the block runs (caches dropped as
    in :func:`default_precision`)."""
    import jax
    import jax.numpy as jnp
    import repro.core.objective as obj
    import repro.fleet.solver as fleet

    hi = jax.lax.Precision.HIGHEST

    def matvec(K, v):
        return jnp.matmul(_bf16(K), v, precision=hi)

    def residuals(prob, X):
        KX = jnp.einsum("bmn,b...n->b...m", _bf16(prob.K), X,
                        precision=hi)
        lo = KX - fleet._bcast(prob.d - prob.mu, X)
        hi_ = fleet._bcast(prob.d + prob.g, X) - KX
        return lo, hi_

    def constraint_grads(prob, X, barrier_t, penalty_w):
        lo, hi_ = residuals(prob, X)
        lo_c, hi_c = jnp.maximum(lo, 1e-9), jnp.maximum(hi_, 1e-9)
        KT = lambda v: jnp.einsum("bmn,btm->btn", _bf16(prob.K), v,
                                  precision=hi)
        bgrad = (1.0 / barrier_t) * (KT(1.0 / hi_c) - KT(1.0 / lo_c))
        qgrad = penalty_w * 2.0 * (KT(jnp.maximum(-hi_, 0.0))
                                   - KT(jnp.maximum(-lo, 0.0)))
        return bgrad, qgrad

    saved = (obj.constraint_matvec, fleet._residuals,
             fleet._constraint_grads)
    jax.clear_caches()
    obj.constraint_matvec = matvec
    fleet._residuals = residuals
    fleet._constraint_grads = constraint_grads
    try:
        yield
    finally:
        (obj.constraint_matvec, fleet._residuals,
         fleet._constraint_grads) = saved
        jax.clear_caches()


def main(argv) -> int:
    import argparse

    from bench import faults, harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", action="store_true")
    ap.add_argument("--fault", action="append", default=[],
                    choices=faults.FAULTS)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devs = harness.require_devices(cell.chips)
    import jax
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = harness.CompileClock()
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = ([("sound", s) for s in seeds] if args.sound else [])
    runs += ([(f, seeds[0]) for f in args.fault] if args.fault
             else [("control", s) for s in seeds])
    engine = cell.config["engine"]
    for kind, seed in runs:
        if kind == "control":
            ctx = default_precision()
        elif kind == "sound":
            ctx = nullcontext()
        else:
            ctx = faults.planted(kind, engine)
        with ctx:
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(), devs, clock)
        print(json.dumps({"run": kind, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
    raise SystemExit(main(sys.argv[1:]))
