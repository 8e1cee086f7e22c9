"""Public wrapper: pads n to the 128-lane boundary and the start batch to the
block size, dispatches to the Pallas kernel, slices back. ``interpret=None``
compiles the kernel on TPU and interprets it elsewhere
(:func:`repro.kernels.resolve_interpret`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.problem import AllocationProblem
from .kernel import alloc_objective_fleet_pallas, alloc_objective_pallas
from .ref import alloc_objective_fleet_ref


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def batched_value_and_grad(prob: AllocationProblem, X: jnp.ndarray,
                           block_s: int = 128,
                           interpret: Optional[bool] = None):
    """(f (S,), grad (S, n)) for a batch of allocations X (S, n)."""
    S, n = X.shape
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), 128, 1), block_s, 0)
    Kp = _pad_to(prob.K.astype(jnp.float32), 128, 1)
    Ep = _pad_to(prob.E.astype(jnp.float32), 128, 1)
    cp = _pad_to(prob.c.astype(jnp.float32), 128, 0)
    P = prob.params
    scalars = jnp.stack([P.alpha, P.beta1, P.beta2, P.beta3, P.gamma,
                         jnp.float32(prob.p), jnp.float32(0), jnp.float32(0)])
    f, g = alloc_objective_pallas(Xp, Kp, Ep, cp, prob.d.astype(jnp.float32),
                                  scalars.astype(jnp.float32),
                                  block_s=block_s, interpret=interpret)
    return f[:S], g[:S, :n]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret", "use_kernel"))
def fleet_value_and_grad(prob: AllocationProblem, X: jnp.ndarray,
                         block_t: int = 128, interpret: Optional[bool] = None,
                         use_kernel: bool = True):
    """(f (B, T), grad (B, T, n)) for a fleet batch.

    ``prob`` is a STACKED AllocationProblem (leaves carry a leading (B,) axis,
    see repro.fleet.batching.stack_problems); X is (B, T, n) — T candidate
    allocations per tenant. With ``use_kernel`` the evaluation dispatches to
    the batched Pallas kernel (grid over tenants x candidate blocks); without
    it, to the einsum oracle (the faster path on CPU where Pallas runs in
    interpret mode).
    """
    B, T, n = X.shape
    P = prob.params
    if not use_kernel:
        return alloc_objective_fleet_ref(
            X.astype(jnp.float32), prob.K, prob.E, prob.c, prob.d,
            P.alpha, P.beta1, P.beta2, P.beta3, P.gamma)
    # don't inflate a short candidate axis (e.g. T = n_starts = 4 at the
    # per-iterate gradient call) to a full 128-row block — shrink the block
    # to the next sublane multiple of 8 instead
    block_t = min(block_t, max(8, -(-T // 8) * 8))
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), 128, 2), block_t, 1)
    Kp = _pad_to(prob.K.astype(jnp.float32), 128, 2)
    Ep = _pad_to(prob.E.astype(jnp.float32), 128, 2)
    cp = _pad_to(prob.c.astype(jnp.float32), 128, 1)
    # padded (all-zero) E rows contribute exp(0)=1 each; passing the PADDED
    # provider count makes p_cnt - sum(exp) telescope to the true term
    p_pad = jnp.full((B,), float(prob.E.shape[1]), jnp.float32)
    zeros = jnp.zeros((B,), jnp.float32)
    scalars = jnp.stack([P.alpha, P.beta1, P.beta2, P.beta3, P.gamma,
                         p_pad, zeros, zeros], axis=1)
    f, g = alloc_objective_fleet_pallas(Xp, Kp, Ep, cp,
                                        prob.d.astype(jnp.float32),
                                        scalars.astype(jnp.float32),
                                        block_t=block_t, interpret=interpret)
    return f[:, :T], g[:, :T, :n]
