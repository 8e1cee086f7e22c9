"""Causal GQA flash attention — Pallas TPU kernel.

Canonical TPU pattern: grid (B, H, n_q, n_kv) with the KV block axis
INNERMOST (TPU grid iterates the last axis sequentially on-core), so the
online-softmax accumulators live in VMEM scratch across KV steps and the
output block is written once on the final KV step.

BlockSpec tiling:
  q   (B, S, H, dh)  -> block (1, bq, 1, dh)   @ (b, iq, h, 0)
  k/v (B, S, G, dh)  -> block (1, bk, 1, dh)   @ (b, ik, h // R, 0)   (GQA)
  o   (B, S, H, dh)  -> block (1, bq, 1, dh)   @ (b, iq, h, 0)

VMEM per program: bq*dh + 2*bk*dh + bq*bk scores (f32) — e.g. bq=bk=512,
dh=128: ~1.8MB, comfortably inside the ~16MB VMEM budget, MXU-aligned
(dims multiples of 128).

Causal + sliding-window masking is applied in-kernel; fully-masked KV blocks
are skipped via @pl.when (the TPU grid still visits them, but no MXU work is
issued).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, bq: int, bk: int, n_kv: int, window: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = iq * bq
    k0 = ik * bk
    # block-level skip: the whole KV block is in the future (strictly above
    # the causal diagonal) or entirely left of the window.
    live = (k0 <= q0 + bq - 1)
    if window > 0:
        live = jnp.logical_and(live, k0 + bk - 1 > q0 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0, :, 0, :]                              # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos <= qpos
        if window > 0:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[:, 0] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, :, 0, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, window: int = 0, block_q: int = 128,
                           block_k: int = 128,
                           interpret: Optional[bool] = None):
    """q (B, S, H, dh); k/v (B, S, G, dh) -> (B, S, H, dh)."""
    B, S, H, dh = q.shape
    G = k.shape[2]
    R = H // G
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0
    n_q, n_kv = S // bq, S // bk
    scale = 1.0 / (dh ** 0.5)

    from jax.experimental.pallas import tpu as pltpu
    kern = functools.partial(_kernel, bq=bq, bk=bk, n_kv=n_kv,
                             window=window, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, 1, dh), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, bk, 1, dh),
                         lambda b, h, iq, ik, R=R: (b, ik, h // R, 0)),
            pl.BlockSpec((1, bk, 1, dh),
                         lambda b, h, iq, ik, R=R: (b, ik, h // R, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dh),
                               lambda b, h, iq, ik: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
