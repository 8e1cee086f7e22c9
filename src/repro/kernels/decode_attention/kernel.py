"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Grid (B, H, n_kv) with the KV-block axis innermost (sequential on-core):
the online-softmax accumulator lives in VMEM scratch across KV blocks —
the classic memory-bound decode shape, where the KV cache stream IS the
roofline. Validity masking (cache may be part-filled / ring-buffered)
comes in as an int32 vector blocked alongside KV.

Block tiling: k/v (B, G, S, dh) -> (1, 1, bk, dh) @ (b, h // R, ik, 0);
VMEM per program ~ 2*bk*dh f32 + bk scores: bk=512, dh=128 -> ~0.6MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref,
            *, n_kv: int, scale: float):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, 0, :].astype(jnp.float32)            # (dh,)
    k = k_ref[0, 0, :, :].astype(jnp.float32)            # (bk, dh)
    v = v_ref[0, 0, :, :]                                # (bk, dh)
    ok = valid_ref[0, :] > 0                             # (bk,)

    s = jnp.sum(k * q[None, :], axis=1) * scale          # (bk,)
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.exp(s - m_new)                               # (bk,)
    corr = jnp.exp(m_prev - m_new)
    l_ref[0, 0] = l_ref[0, 0] * corr + jnp.sum(p)
    pv = jnp.sum(p[:, None].astype(jnp.float32) * v.astype(jnp.float32),
                 axis=0)                                 # (dh,)
    acc_ref[0, :] = acc_ref[0, :] * corr + pv
    m_ref[0, 0] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0, 0, :] = (acc_ref[0, :]
                             / jnp.maximum(l_ref[0, 0], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, valid, *, block_k: int = 512,
                            interpret: Optional[bool] = None):
    """q (B, 1, H, dh); k/v (B, G, S, dh); valid (S,) bool/int.
    Returns (B, 1, H, dh)."""
    B, _, H, dh = q.shape
    G, S = k_cache.shape[1], k_cache.shape[2]
    R = H // G
    bk = min(block_k, S)
    assert S % bk == 0
    n_kv = S // bk
    scale = 1.0 / (dh ** 0.5)
    valid_i = valid.astype(jnp.int32)[None, :]           # (1, S)

    from jax.experimental.pallas import tpu as pltpu
    kern = functools.partial(_kernel, n_kv=n_kv, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, H, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, ik: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, ik, R=R: (b, h // R, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, ik, R=R: (b, h // R, ik, 0)),
            pl.BlockSpec((1, bk), lambda b, h, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dh), lambda b, h, ik: (b, 0, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, H, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k_cache, v_cache, valid_i)
