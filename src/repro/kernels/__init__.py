"""Pallas TPU kernels for the perf-critical layers. Each kernel package has:
  kernel.py — pl.pallas_call + BlockSpec VMEM tiling (TPU target),
  ops.py    — jit'd public wrapper,
  ref.py    — pure-jnp oracle the kernel is tested against.

Every entry point takes ``interpret=None`` and settles it with
:func:`resolve_interpret`: compiled on TPU, the Pallas interpreter on every
other backend. An explicit ``True``/``False`` always wins.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The Pallas mode of a kernel call: ``interpret`` when given, else
    compiled (False) on TPU and interpreted (True) off it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
