"""Operations and bytes of one fleet ``alloc_objective`` call.

Counted at the algorithm's true shapes: ``B`` tenants, ``T`` points per
tenant, ``n`` instance types, ``m`` resources, ``p`` providers, float32.
Padding that an implementation adds (T to a sublane block, n to 128 lanes)
is not work, so it is not counted: it shows as a lost share.

Per point (eq. 1 of arXiv 2503.21096 and its analytic gradient):

* ``K x`` and ``E x``: 2mn + 2pn
* price term ``c . x``: 2n
* shortage ``s = max(d - Kx, 0)`` and ``beta3 sum s^2``: 4m + 1
* consolidation ``alpha (p - sum exp(-beta1 Ex))``: 3p + 2
* volume ``-gamma sum log1p(beta2 Ex)``: 3p + 1
* the value, four terms summed: 3
* the gradient's small vectors, scaled before they meet K and E:
  ``alpha beta1 exp(-beta1 Ex)`` p, ``-gamma beta2 / (1 + beta2 Ex)`` 3p,
  ``-2 beta3 s`` m
* gradient contractions: ``s @ K`` 2mn, two ``(p,) @ E`` 4pn
* the gradient, four (n,) terms summed: 3n

In all 4mn + 6pn + 5n + 5m + 10p + 7 per point.

Bytes: every input read once and every output written once — the points
X (B, T, n), per tenant K (m, n), E (p, n), c (n,), d (m,) and the five
penalty weights, and out the values (B, T) and gradients (B, T, n).
"""
from __future__ import annotations

F32 = 4


def flops(B: int, T: int, n: int, m: int, p: int) -> float:
    per_point = 4 * m * n + 6 * p * n + 5 * n + 5 * m + 10 * p + 7
    return float(B * T * per_point)


def bytes_moved(B: int, T: int, n: int, m: int, p: int) -> float:
    reads = B * T * n + B * (m * n + p * n + n + m + 5)
    writes = B * T + B * T * n
    return float(F32 * (reads + writes))


def least_seconds(B: int, T: int, n: int, m: int, p: int,
                  peak_flops: float, peak_bytes: float):
    """(least time in seconds, the bound: "compute" or "memory")."""
    tc = flops(B, T, n, m, p) / peak_flops
    tm = bytes_moved(B, T, n, m, p) / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")
