"""The paper's instance catalog, generated by the benchmark itself.

arXiv 2503.21096 §IV.A collected 940 Azure and 940 Linode instance types
through live APIs; offline, the system under test generates a catalog of the
same scale and family/price structure (``repro.core.catalog``). This module
copies that generator, so the reference checks and the traffic never read a
table the program made. Capacities are stored as the float32 values the
program computes with, so the float64 reference and the program agree on
every capacity bit for bit.

Rows are ``(name, provider, family, cpu, mem_gb, net_units, storage_gb,
hourly_price)``; resources are ordered cpu, mem_gb, net_units, storage_gb.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

Row = Tuple[str, str, str, float, float, float, float, float]

# family spec: (name, ram_per_cpu, storage_per_cpu, net_per_cpu,
#               price_per_cpu_hr, storage_price_per_gb_hr)
_AZURE_FAMILIES = [
    ("B", 4.0, 8.0, 0.25, 0.0104, 0.00005),
    ("D", 4.0, 16.0, 0.50, 0.0480, 0.00005),
    ("F", 2.0, 8.0, 0.50, 0.0425, 0.00005),
    ("E", 8.0, 32.0, 0.50, 0.0630, 0.00005),
    ("M", 16.0, 64.0, 0.75, 0.1070, 0.00005),
    ("L", 8.0, 340.0, 0.75, 0.0860, 0.00002),
    ("DC", 4.0, 16.0, 0.50, 0.0980, 0.00005),
    ("NV", 8.0, 48.0, 1.00, 0.1900, 0.00005),
]
_AZURE_SIZES = [1, 2, 4, 8, 12, 16, 20, 24, 32, 48, 64, 96]
_AZURE_GENS = [("v3", 1.00), ("v4", 0.97), ("v5", 0.94), ("sv5", 0.99),
               ("av4", 0.90), ("av5", 0.87), ("dv4", 1.02), ("dv5", 0.98),
               ("ev4", 1.05), ("ev5", 1.01)]
_LINODE_FAMILIES = [
    ("standard", 2.0, 26.0, 0.40, 0.0270, 0.0),
    ("dedicated", 2.0, 25.0, 0.55, 0.0540, 0.0),
    ("highmem", 8.0, 20.0, 0.40, 0.0600, 0.0),
    ("premium", 2.0, 32.0, 0.80, 0.0650, 0.0),
    ("gpu-host", 6.0, 80.0, 1.00, 0.1500, 0.0),
    ("nanode", 1.0, 25.0, 0.20, 0.0075, 0.0),
]
_LINODE_SIZES = [1, 2, 4, 6, 8, 12, 16, 20, 24, 32, 40, 48, 56, 64, 80, 96]


def _f32(v: float) -> float:
    return float(np.float32(v))


def _row(rng, provider, fam, size, gen_name, gen_factor, ram_per_cpu,
         st_per_cpu, net_per_cpu, ppc, spg) -> Row:
    jitter = float(1.0 + 0.03 * rng.standard_normal())
    cpu = float(size)
    mem = cpu * ram_per_cpu
    storage = cpu * st_per_cpu
    net = max(0.25, cpu * net_per_cpu)
    price = (ppc * cpu * gen_factor * size ** -0.03 + spg * storage) * jitter
    return (f"{provider}-{fam}{size}{gen_name}", provider, fam, _f32(cpu),
            _f32(mem), _f32(net), _f32(storage),
            round(max(price, 0.003), 5))


def catalog_rows(seed: int = 0, n_per_provider: int = 940,
                 stride: int = 1) -> List[Row]:
    """The two-provider catalog, every ``stride``-th type of it."""
    rng = np.random.default_rng(seed)
    azure = [_row(rng, "azure", fam, size, gen, gf, rpc, spc, npc, ppc, spg)
             for fam, rpc, spc, npc, ppc, spg in _AZURE_FAMILIES
             for size in _AZURE_SIZES for gen, gf in _AZURE_GENS]
    linode = [_row(rng, "linode", fam, size, f"r{rep}", 1.0 + 0.01 * rep,
                   rpc, spc, npc, ppc, spg)
              for fam, rpc, spc, npc, ppc, spg in _LINODE_FAMILIES
              for size in _LINODE_SIZES for rep in range(10)]
    rows = azure[:n_per_provider] + linode[:n_per_provider]
    return rows[::stride]


def capacity_matrix(rows: List[Row]) -> np.ndarray:
    """(4, n) float64 capacities, one column per type."""
    return np.asarray([r[3:7] for r in rows], np.float64).T

