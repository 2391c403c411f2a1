"""Compile the fused placement kernel for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e
2x2 host and the TPU compiler (libtpu) compiles for one of its chips.
That catches what interpret mode cannot — block shapes the chip's tiling
refuses, shape casts Mosaic cannot lower, too much VMEM.  The topology is
described inside a fixture (never at import), so every xdist worker
collects the same tests and only the one given this file loads libtpu;
without libtpu the tests skip.  The persistent compilation cache is off
around these compiles: an entry written for a described chip cannot be
read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.placement.placement import fused_place

DEV, CFG, T, W = 4, 3, 2, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_args(B, sharding):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    win = (B, DEV, CFG, T, W)
    return (s(win, jnp.float32), s(win, jnp.float32), s(win, jnp.bool_),
            s((B, CFG), jnp.float32), s((B, DEV), jnp.float32),
            s((B, DEV), jnp.float32), s((B,), jnp.int32), s((B,), jnp.bool_))


@pytest.mark.parametrize("B", [4096, 1024, 2, 1],
                         ids=["sweep_batch", "mesh_shard", "calib_quick",
                              "calib_b1"])
def test_placement_kernel_compiles_for_v5e(one_chip, B):
    """The kernel at the fleet's local batch sizes: a 4,096-replica sweep
    batch on one chip, its 1,024-replica shard on a 4-chip mesh, and the
    calibration batches of 2 and 1 replicas."""
    compiled = jax.jit(fused_place).lower(*_kernel_args(B, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_placement_scopes_on_v5e(one_chip):
    """Under a profiler the launch is charged to ``placement/kernel`` and
    the boundary transposes to ``placement/layout``."""
    import re

    text = jax.jit(fused_place).lower(
        *_kernel_args(4096, one_chip)).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernels and all("/placement/kernel/" in ln for ln in kernels)
    names = re.findall(r'op_name="([^"]*)"', text)
    transposes = [n for n in names if n.endswith("/transpose")]
    assert transposes and all("/placement/layout/" in n for n in transposes)
