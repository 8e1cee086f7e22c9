"""Shared fixtures. NOTE: XLA_FLAGS device-count forcing is deliberately NOT
set here — tests run with the single real CPU device; only launch/dryrun.py
forces 512 placeholder devices (and a few subprocess-based tests set it in
their own child process environment)."""
import pytest

import jax

from repro.testing import make_toy_problem  # canonical home (rootdir-safe)

jax.config.update("jax_enable_x64", False)

# One hypothesis profile for the whole suite: no per-example deadline (the
# first example of a property test pays its JIT compile), derandomized
# draws so a run reproduces, and no example database on disk. Per-test
# @settings only choose max_examples. Without hypothesis the tests fall
# back to the deterministic shim in repro.testing.
try:
    from hypothesis import settings as _hypothesis_settings
except ImportError:
    pass
else:
    _hypothesis_settings.register_profile(
        "repro", deadline=None, derandomize=True, database=None)
    _hypothesis_settings.load_profile("repro")


@pytest.fixture(scope="session")
def toy_problem():
    return make_toy_problem()


@pytest.fixture(scope="session")
def cloud_catalog():
    from repro.core import make_cloud_catalog
    return make_cloud_catalog()


@pytest.fixture(scope="session")
def small_catalog():
    """A trimmed catalog (every 20th instance) keeping both providers —
    scenario-scale tests stay fast on one CPU core."""
    from repro.core import Catalog, make_cloud_catalog
    cat = make_cloud_catalog()
    return Catalog(cat.instances[::20])
