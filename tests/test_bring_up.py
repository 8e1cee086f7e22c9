"""No hidden fallback off the chip: ``chip_smoke.py`` refuses a CPU, the
compile cache is placed from outside, and the Pallas kernels interpret only
where they cannot compile."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import resolve_interpret

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_device_check_refuses_cpu(chip_smoke):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.require_tpu()
    assert "'cpu'" in str(exc.value.code)


def test_smoke_script_fails_at_phase_0_on_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok": true' not in proc.stdout
    assert "phase 1" not in proc.stdout


def test_cache_helper_leaves_env_dir_alone(monkeypatch, restore_cache_dir,
                                           tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_helper_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.setup_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("given,expect", [(None, True), (True, True),
                                          (False, False)])
def test_interpret_resolves_from_backend(given, expect):
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(given) is expect
