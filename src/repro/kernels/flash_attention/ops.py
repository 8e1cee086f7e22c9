"""jit'd public wrapper for the flash-attention kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, window: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """Causal GQA flash attention. q (B,S,H,dh); k/v (B,S,G,dh)."""
    return flash_attention_pallas(q, k, v, window=window, block_q=block_q,
                                  block_k=block_k, interpret=interpret)
