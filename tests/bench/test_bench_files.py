"""Cell -> configuration -> traffic -> metric reader, each found by name
from files; ``BENCHMARK.json`` within the limits its readers hold it to."""
import json
import re

import pytest

from conftest import DATA, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["per_layer"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for path in SPEC["paths"]:
        assert (ROOT / path).is_dir() and not path.startswith("/")
    assert (ROOT / SPEC["command"][1]).is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    from bench import harness

    c = harness.load_cell(cell)
    assert c.config["name"] == c.config_name
    assert c.traffic["kind"] in ("open_loop", "replay_segments")
    driver = harness.load_driver(c.config["engine"])
    assert hasattr(driver, "window")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_found_by_name(metric):
    from bench import harness

    read = harness.load_reader(metric)
    assert callable(read)
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)


def test_names_units_and_keys():
    names = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound",
                                        "source", "workloads"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"})):
        for entry in SPEC[group]:
            assert set(entry) <= keys, (group, entry)
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in names
            names.add((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"])
                assert entry["better"] in ("lower", "higher")
            if "why" in entry:
                assert 1 <= len(entry["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in SPEC["workloads"]:
        assert w["chips"] == 1


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_file_states_source_and_guarantee(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == config
    assert cfg["reduced"] == entry["reduced"] == []
    assert "covers" in cfg["guarantee"] and cfg["assumed"]
    assert cfg["catalog"] == {"seed": 0, "n_per_provider": 940, "stride": 1}


def test_tiny_cells_resolve_from_test_data():
    from bench import harness

    for cell in ("serve-tiny-scan", "serve-tiny-churn", "replay-tiny-mixed"):
        c = harness.load_cell(cell, DATA / "benchmark.json",
                              DATA / "traffic")
        assert c.config["catalog"]["stride"] == 40


def test_churn_cell_differs_only_in_traffic():
    # the churn mix, kept for a later cell on the steady cell's
    # configuration, is the steady mix plus its churn
    a = json.loads((ROOT / "bench/traffic/scan10s.json").read_text())
    b = json.loads((ROOT / "bench/traffic/churn16-scan10s.json").read_text())
    b.pop("churn")
    assert a == b


def test_bench_catalog_is_the_programs_catalog():
    import numpy as np

    from bench.catalog import capacity_matrix, catalog_rows
    from repro.core.catalog import make_cloud_catalog

    rows = catalog_rows()
    K, _, c = make_cloud_catalog().matrices()
    assert len(rows) == 1880
    np.testing.assert_array_equal(capacity_matrix(rows), K.astype(np.float64))
    np.testing.assert_array_equal(np.float32([r[7] for r in rows]), c)
