"""jit'd public wrapper for the flash-decode kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import decode_attention_pallas


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, valid, block_k: int = 512,
                     interpret: Optional[bool] = None):
    """One-token decode attention. q (B,1,H,dh); caches (B,G,S,dh);
    valid (S,)."""
    return decode_attention_pallas(q, k_cache, v_cache, valid,
                                   block_k=block_k, interpret=interpret)
