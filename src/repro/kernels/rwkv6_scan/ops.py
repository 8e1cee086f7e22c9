"""jit'd public wrapper for the RWKV6 WKV chunk kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import rwkv6_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0, chunk: int = 64,
               interpret: Optional[bool] = None):
    """RWKV6 WKV: r,k,v,w (B,S,H,hs); u (H,hs); s0 (B,H,hs,hs)."""
    return rwkv6_scan_pallas(r, k, v, w, u, s0, chunk=chunk,
                             interpret=interpret)
