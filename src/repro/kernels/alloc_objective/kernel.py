"""Fused multi-start objective+gradient Pallas TPU kernel.

The solver's hot loop evaluates f(x) and grad f(x) for a BATCH of starts every
PGD iteration. The jnp path materializes ~8 (S, n)/(S, m) intermediates in
HBM; this kernel keeps everything for a block of starts resident in VMEM and
writes only (f_block, grad_block) back.

TPU adaptation (vs the paper's CPU/GLPK setting):
  * n (instance types, ~1.9k) padded to the 128-lane boundary, resident as a
    (block_s, n) VMEM tile — 128 x 2048 f32 = 1MB, well under VMEM.
  * K (m, n) and E (p, n) are small (m=4, p=2) and broadcast to every block.
  * grid over the start dimension only: one program computes a whole block's
    objective terms AND the analytic gradient in registers/VMEM.

Two entry points share the same math:
  * ``alloc_objective_pallas``       — one problem, (S, n) starts (multistart).
  * ``alloc_objective_fleet_pallas`` — B problems with per-problem K/E/c/d,
    (B, T, n) candidates; the grid grows a leading batch axis and the problem
    data blocks are indexed by it. This is the fleet solver's hot loop: the
    whole multi-tenant batch is one pallas_call.

Masking: padded columns carry K=E=c=0 so they contribute nothing; padded
E rows are all-zero so their exp(-b1*0)=1 cancels against the padded p_count
(the caller passes the PADDED provider count); the caller slices the padded
gradient back to n columns.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _objective_math(x, K, E, c, d, scal):
    """Shared eq.(1) objective + analytic gradient for one block.

    x (bs, n), K (m, n), E (p, n), c (1, n), d (1, m), scal (1, 8) =
    [alpha, beta1, beta2, beta3, gamma, p_count, 0, 0].
    Returns f (bs,), g (bs, n).
    """
    alpha = scal[0, 0]
    beta1 = scal[0, 1]
    beta2 = scal[0, 2]
    beta3 = scal[0, 3]
    gamma = scal[0, 4]
    p_cnt = scal[0, 5]

    # contractions against the small K/E matrices use the MXU via dot_general
    KX = jax.lax.dot_general(x, K, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (bs, m)
    EX = jax.lax.dot_general(x, E, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (bs, p)

    base = jnp.sum(x * c, axis=1)                                   # (bs,)
    exp_term = jnp.exp(-beta1 * EX)                                 # (bs, p)
    consol = alpha * (p_cnt - jnp.sum(exp_term, axis=1))
    volume = -gamma * jnp.sum(jnp.log1p(beta2 * EX), axis=1)
    short = jnp.maximum(d - KX, 0.0)                                # (bs, m)
    shortage = beta3 * jnp.sum(short * short, axis=1)
    f = base + consol + volume + shortage

    g_consol = alpha * beta1 * jax.lax.dot_general(
        exp_term, E, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                         # (bs, n)
    g_volume = -gamma * beta2 * jax.lax.dot_general(
        1.0 / (1.0 + beta2 * EX), E, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    g_short = -2.0 * beta3 * jax.lax.dot_general(
        short, K, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    g = c + g_consol + g_volume + g_short
    return f, g


def _kernel(x_ref, k_ref, e_ref, c_ref, d_ref, scal_ref, f_ref, g_ref):
    """Block shapes: x (bs, n), k (m, n), e (p, n), c (1, n), d (1, m),
    scal (1, 8); outputs f (bs, 1), g (bs, n)."""
    f, g = _objective_math(x_ref[...].astype(jnp.float32),
                           k_ref[...].astype(jnp.float32),
                           e_ref[...].astype(jnp.float32),
                           c_ref[...].astype(jnp.float32),
                           d_ref[...].astype(jnp.float32),
                           scal_ref[...])
    f_ref[...] = f[:, None]
    g_ref[...] = g


def _fleet_kernel(x_ref, k_ref, e_ref, c_ref, d_ref, scal_ref, f_ref, g_ref):
    """Same math with a leading singleton batch-block axis on every ref."""
    f, g = _objective_math(x_ref[0].astype(jnp.float32),
                           k_ref[0].astype(jnp.float32),
                           e_ref[0].astype(jnp.float32),
                           c_ref[0].astype(jnp.float32),
                           d_ref[0].astype(jnp.float32),
                           scal_ref[0])
    f_ref[0] = f[:, None]
    g_ref[0] = g


def alloc_objective_pallas(X, K, E, c, d, scalars, *, block_s: int = 128,
                           interpret: Optional[bool] = None):
    """X (S, n_pad); K (m, n_pad); E (p, n_pad); c (n_pad,); d (m,);
    scalars (8,) f32. Returns (f (S,), grad (S, n_pad))."""
    S, n = X.shape
    m, p = K.shape[0], E.shape[0]
    assert S % block_s == 0, (S, block_s)
    grid = (S // block_s,)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, n), lambda i: (i, 0)),    # x block
            pl.BlockSpec((m, n), lambda i: (0, 0)),          # K broadcast
            pl.BlockSpec((p, n), lambda i: (0, 0)),          # E broadcast
            pl.BlockSpec((1, n), lambda i: (0, 0)),          # c
            pl.BlockSpec((1, m), lambda i: (0, 0)),          # d
            pl.BlockSpec((1, 8), lambda i: (0, 0)),          # scalars
        ],
        out_specs=[
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),    # f
            pl.BlockSpec((block_s, n), lambda i: (i, 0)),    # grad
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, n), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(X, K, E, c[None, :], d[None, :], scalars[None, :])
    f, g = out
    return f[:, 0], g


def alloc_objective_fleet_pallas(X, K, E, c, d, scalars, *,
                                 block_t: int = 128,
                                 interpret: Optional[bool] = None):
    """Fleet (multi-tenant) batch: per-problem matrices indexed by the grid.

    X (B, T, n_pad); K (B, m, n_pad); E (B, p, n_pad); c (B, n_pad);
    d (B, m); scalars (B, 8) with scalars[:, 5] the PADDED provider count.
    Returns (f (B, T), grad (B, T, n_pad)).
    """
    B, T, n = X.shape
    m, p = K.shape[1], E.shape[1]
    assert T % block_t == 0, (T, block_t)
    grid = (B, T // block_t)

    out = pl.pallas_call(
        _fleet_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, n), lambda b, i: (b, i, 0)),  # x block
            pl.BlockSpec((1, m, n), lambda b, i: (b, 0, 0)),        # K[b]
            pl.BlockSpec((1, p, n), lambda b, i: (b, 0, 0)),        # E[b]
            pl.BlockSpec((1, 1, n), lambda b, i: (b, 0, 0)),        # c[b]
            pl.BlockSpec((1, 1, m), lambda b, i: (b, 0, 0)),        # d[b]
            pl.BlockSpec((1, 1, 8), lambda b, i: (b, 0, 0)),        # scalars[b]
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, 1), lambda b, i: (b, i, 0)),  # f
            pl.BlockSpec((1, block_t, n), lambda b, i: (b, i, 0)),  # grad
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, T, n), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(X, K, E, c[:, None, :], d[:, None, :], scalars[:, None, :])
    f, g = out
    return f[:, :, 0], g
