"""The kernel's operation and byte count against a hand count, and the
peaks table."""
import importlib.util
import json

import pytest

from conftest import ROOT


def _roofline():
    spec = importlib.util.spec_from_file_location(
        "alloc_objective_roofline", ROOT / "bench/rooflines/alloc_objective.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_count_by_hand_at_a_small_shape():
    r = _roofline()
    # B=2 tenants, T=3 points, n=5 types, m=4 resources, p=2 providers:
    # per point 4*4*5 + 6*2*5 + 5*5 + 5*4 + 10*2 + 7 = 80+60+25+20+20+7
    assert r.flops(2, 3, 5, 4, 2) == 2 * 3 * 212
    # reads: X 2*3*5=30, per tenant K 20 + E 10 + c 5 + d 4 + 5 weights
    # = 44 -> 88; writes: values 6, gradients 30 -> 4 bytes each
    assert r.bytes_moved(2, 3, 5, 4, 2) == 4 * (30 + 88 + 6 + 30)


def test_full_catalog_call_is_memory_bound_on_v5e():
    r = _roofline()
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())["TPU v5 lite"]
    t, bound = r.least_seconds(256, 4, 1880, 4, 2, peaks["flops_per_s"],
                               peaks["bytes_per_s"])
    assert bound == "memory"
    # ~29 MB at 819 GB/s
    assert r.bytes_moved(256, 4, 1880, 4, 2) == pytest.approx(28.89e6,
                                                               rel=1e-3)
    assert t == pytest.approx(r.bytes_moved(256, 4, 1880, 4, 2) / 819e9)


def test_padding_is_not_counted():
    r = _roofline()
    assert r.flops(256, 8, 1920, 4, 2) > r.flops(256, 4, 1880, 4, 2)


def test_peaks_have_a_source():
    for kind, row in json.loads((ROOT / "bench/peaks.json").read_text()
                                ).items():
        assert row["flops_per_s"] > 0 and row["bytes_per_s"] > 0
        assert "TPU v5e" in row["source"]
