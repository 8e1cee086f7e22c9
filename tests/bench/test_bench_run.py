"""``bench/run.py`` on a machine with no TPU, and the last line's schema
of tiny runs with the device check skipped."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT


def test_run_exits_non_zero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve-b256-scan10s",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout


def _check_schema(res, trace):
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert isinstance(res["correct"], bool)
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    for name, m in res["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    for name, c in res["checks"].items():
        assert set(c) == {"value", "limit"}, name
    json.dumps(res)


@pytest.mark.parametrize("cell,seconds", [("serve-tiny-scan", 2.0),
                                          ("replay-tiny-mixed", 1.0)])
def test_untraced_line(cell, seconds, run_tiny, capsys):
    res = run_tiny(cell, seed=2**33 + 1, seconds=seconds)
    _check_schema(res, trace=False)
    assert res["correct"] is True
    want = {"serve-tiny-scan": {"decision_p50_ms", "decision_p95_ms",
                                "setup_s"},
            "replay-tiny-mixed": {"replay_tenant_ticks_per_s", "setup_s"}}
    assert set(res["metrics"]) == want[cell]
    captured = capsys.readouterr()
    assert captured.err.strip().splitlines()[-1].startswith(
        "check unanswered:")
    assert "programs compiled inside the window: 0 " in captured.out
    assert "window's allocations: decisions " in captured.out


def test_traced_line_reports_per_layer_metrics(run_tiny):
    res = run_tiny("serve-tiny-churn", seed=3, seconds=3.0, trace=True)
    _check_schema(res, trace=True)
    # spans are there on any backend; the device metrics need a TPU trace
    assert {"serve_tick_ms", "serve_cold_join_ms"} <= set(res["metrics"])
    assert "device_idle_share.serve" not in res["metrics"]
