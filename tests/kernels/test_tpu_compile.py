"""Compile the alloc_objective kernels for a described TPU v5e, no chip.

The TPU compiler ships with jaxlib and compiles for a topology that is
described, not attached, so these tests run on CPU hosts. They catch what
interpret mode cannot (tiling, VMEM limits, lowering) at the real catalog
width: n = 1,880 instance types padded to 1,920. The topology is described
inside a module fixture, never at import, and the tests skip where it
cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.alloc_objective.kernel import (alloc_objective_fleet_pallas,
                                                  alloc_objective_pallas)

N_PAD = 1920        # make_cloud_catalog(): n = 1,880 padded to 128 lanes
M, P = 4, 2         # resources, providers


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one: keep
    # the persistent cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_text(fn, shapes, sharding, **static):
    args = [_spec(s, sharding) for s in shapes]
    return jax.jit(lambda *a: fn(*a, interpret=False, **static)
                   ).lower(*args).compile().as_text()


@pytest.mark.parametrize("B,T,block_t", [(256, 8, 8)])
def test_fleet_kernel_compiles_for_v5e(one_chip, B, T, block_t):
    """The fleet hot-loop entry at B=256 tenants, T=8 points per tenant."""
    text = _compiled_text(
        alloc_objective_fleet_pallas,
        [(B, T, N_PAD), (B, M, N_PAD), (B, P, N_PAD), (B, N_PAD), (B, M),
         (B, 8)], one_chip, block_t=block_t)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("S,block_s", [(128, 128)])
def test_multistart_kernel_compiles_for_v5e(one_chip, S, block_s):
    """The single-problem multistart entry at S=128 starts."""
    text = _compiled_text(
        alloc_objective_pallas,
        [(S, N_PAD), (M, N_PAD), (P, N_PAD), (N_PAD,), (M,), (8,)],
        one_chip, block_s=block_s)
    assert "tpu_custom_call" in text
