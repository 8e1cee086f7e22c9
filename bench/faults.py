"""Faults planted in the timed path, to show that the check catches them.

Each is a context manager that breaks the program for as long as the block
runs and restores it after. ``bench/control.py --fault <name>`` runs a cell
with one planted, and the CPU tests plant each under a tiny run. The
benchmark's own runs never plant one.

* ``unchanged`` — the warm step returns its state unchanged;
* ``half_left_out`` — half of the batch left out: odd lanes keep their
  state;
* ``answer_altered`` — one answer altered where it is produced: the first
  live lane loses every node of the type it holds most of;
* ``scale_down_skipped`` — rounding keeps every node it added, without its
  scale-down pass (``repro.core.rounding.scale_down``).

There is no exchange between chips to leave out: every cell runs on one.
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np

# where each engine's driver reaches the batched warm step
_WARM_STEP_USERS = {"serve": "repro.serve.engine",
                    "replay": "repro.fleet.replay"}


def _unchanged(res, X_cur, active):
    return res._replace(x_int=X_cur, x=X_cur)


def _half_left_out(res, X_cur, active):
    keep = np.arange(X_cur.shape[0]) % 2 == 1
    x_int = np.where(keep[:, None], X_cur, np.asarray(res.x_int))
    return res._replace(x_int=x_int)


def _answer_altered(res, X_cur, active):
    x_int = np.array(res.x_int, np.float32)
    lane = int(np.flatnonzero(active)[0]) if active.any() else 0
    x_int[lane, int(np.argmax(x_int[lane]))] = 0.0
    return res._replace(x_int=x_int)


WARM_STEP_FAULTS = {"unchanged": _unchanged,
                    "half_left_out": _half_left_out,
                    "answer_altered": _answer_altered}
FAULTS = sorted(WARM_STEP_FAULTS) + ["scale_down_skipped"]


@contextmanager
def _swapped(module, name, value):
    import jax

    saved = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, saved)
        jax.clear_caches()


@contextmanager
def planted(fault: str, engine: str):
    """The program with ``fault`` planted, for a cell of ``engine``."""
    import importlib

    if fault == "scale_down_skipped":
        import repro.core.rounding as rounding

        with _swapped(rounding, "scale_down",
                      lambda prob, x, max_removes=4096: x):
            yield
        return
    if fault not in WARM_STEP_FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    import repro.fleet.solver as fleet

    real, alter = fleet.solve_fleet_step, WARM_STEP_FAULTS[fault]

    def broken(batch, X_cur, *args, **kwargs):
        res = real(batch, X_cur, *args, **kwargs)
        X_cur = np.asarray(X_cur, np.float32)
        active = getattr(batch, "active_mask", None)
        active = (np.ones(X_cur.shape[0], bool) if active is None
                  else np.asarray(active, bool))
        return alter(res, X_cur, active)

    module = importlib.import_module(_WARM_STEP_USERS[engine])
    with _swapped(module, "solve_fleet_step", broken):
        yield
