"""RWKV6 WKV chunked-scan Pallas TPU kernel.

Grid (B, H, n_chunks), chunk axis innermost/sequential: the (hs, hs) state
matrix lives in VMEM scratch across chunks. Within a chunk the GLA-style
closed form turns the recurrence into two small MXU matmuls plus the
decay-weighted intra-chunk attention matrix (c x c) — TPU-native (systolic
matmuls over hs=64..128-wide tiles) instead of the CUDA per-timestep loop.

  y_t = (r_t . W_{t-1}) S_0
      + sum_{i<t} [(r_t . W_{t-1}) . (k_i / W_i)] v_i
      + (r_t . u . k_t) v_t
  S'  = diag(W_c) S_0 + sum_i (k_i . W_c/W_i) v_i^T

W_t = prod_{j<=t} w_j (cumprod in log space; the k/W ratio is clamped to
exp(60) — contributions beyond that decay window are below f32 resolution).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref,
            state_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    rr = r_ref[0, :, 0, :].astype(jnp.float32)            # (c, hs)
    kk = k_ref[0, :, 0, :].astype(jnp.float32)
    vv = v_ref[0, :, 0, :].astype(jnp.float32)
    ww = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :].astype(jnp.float32)                   # (hs,)
    S0 = state_ref[...]                                   # (hs, hs)

    logw = jnp.log(ww)
    cum = jnp.cumsum(logw, axis=0)                        # (c, hs)
    Wm1 = jnp.exp(cum - logw)                             # W_{t-1}
    r_dec = rr * Wm1
    k_dec = kk * jnp.exp(-jnp.clip(cum, -60.0, 0.0))

    att = jax.lax.dot_general(r_dec, k_dec, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (c, c)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(tj < ti, att, 0.0)                    # strict lower
    bonus = jnp.sum(rr * u[None, :] * kk, axis=1)         # (c,)

    y = jax.lax.dot_general(att, vv, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + bonus[:, None] * vv
    y = y + jax.lax.dot_general(r_dec, S0, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    Wc = jnp.exp(cum[-1])                                 # (hs,)
    k_tail = kk * jnp.exp(cum[-1][None, :] - cum)
    S_new = (Wc[:, None] * S0
             + jax.lax.dot_general(k_tail, vv, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    state_ref[...] = S_new

    @pl.when(ic == n_chunks - 1)
    def _final():
        sf_ref[0, 0] = S_new.astype(sf_ref.dtype)


def rwkv6_scan_pallas(r, k, v, w, u, s0, *, chunk: int = 64,
                      interpret: Optional[bool] = None):
    """r,k,v,w (B, S, H, hs); u (H, hs); s0 (B, H, hs, hs).
    Returns (y (B, S, H, hs), s_final (B, H, hs, hs))."""
    B, S, H, hs = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk

    from jax.experimental.pallas import tpu as pltpu
    kern = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    y, sf = pl.pallas_call(
        kern,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, hs), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1, hs), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1, hs), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1, hs), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, hs), lambda b, h, ic: (h, 0)),
            pl.BlockSpec((1, 1, hs, hs), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, hs), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, 1, hs, hs), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, hs), r.dtype),
            jax.ShapeDtypeStruct((B, H, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u, s0)
    return y, sf
