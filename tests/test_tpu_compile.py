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


def _kernel_args(B, sharding, dev=DEV):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    win = (B, dev, CFG, T, W)
    return (s(win, jnp.float32), s(win, jnp.float32), s(win, jnp.bool_),
            s((B, CFG), jnp.float32), s((B, dev), jnp.float32),
            s((B, dev), jnp.float32), s((B,), jnp.int32), s((B,), jnp.bool_))


@pytest.mark.parametrize("B, dev", [(4096, DEV), (1024, DEV), (2, DEV),
                                    (1, DEV), (128, 50)],
                         ids=["sweep_batch", "mesh_shard", "calib_quick",
                              "calib_b1", "site50"])
def test_placement_kernel_compiles_for_v5e(one_chip, B, dev):
    """The kernel at the fleet's local batch sizes: a 4,096-replica sweep
    batch on one chip, its 1,024-replica shard on a 4-chip mesh, the
    calibration batches of 2 and 1 replicas, and one 128-replica tile of
    a 50-device site (its blocks grow with the device count)."""
    compiled = jax.jit(fused_place).lower(
        *_kernel_args(B, one_chip, dev)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def segment_hlo(one_chip):
    """``segment_hlo(B, n_dev)``: the compiled v5e HLO text of the whole
    40-tick segment program with the compiled kernel, compiled once per
    shape for the module.  The compile time is printed, for the record,
    and not asserted."""
    import time

    from repro.fleet import FleetParams, engine, make_fleet
    from repro.kernels.placement import ops

    texts = {}

    def compile_segment(B, n_dev):
        if (B, n_dev) in texts:
            return texts[B, n_dev]
        S = 40
        params = FleetParams(n_devices=n_dev, placement_backend="kernel")
        shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=one_chip)
        carry = jax.tree_util.tree_map(
            shape, engine.initial_carry(make_fleet(B, n_dev)))
        args = (carry, shape(jnp.zeros((S, B, n_dev), jnp.int32)),
                shape(jnp.zeros((S, B), jnp.float32)),
                shape(jnp.int32(0)), shape(jnp.int32(S)))
        # on a CPU host "kernel" means interpret mode; compile the kernel
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ops, "resolve_backend", lambda backend: (True, False))
            t = time.perf_counter()
            compiled = engine._run_segment.lower(*args, params=params).compile()
        print(f"v5e segment compile, B={B}, {n_dev} devices: "
              f"{time.perf_counter() - t:.1f} s")
        texts[B, n_dev] = compiled.as_text()
        return texts[B, n_dev]

    return compile_segment


@pytest.mark.parametrize("n_dev", [4, 50], ids=["paper_site", "site50"])
def test_segment_program_compiles_for_v5e(segment_hlo, n_dev):
    """The whole 40-tick segment program with the compiled kernel, at 128
    replicas, for the paper's 4 devices and a 50-device site.  The device
    loop writes each placement launch once, so both hold 6 kernel calls."""
    from repro.fleet import engine

    calls = [ln for ln in segment_hlo(128, n_dev).splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1 + 1 + engine.MAX_LP


@pytest.mark.parametrize("B, n_dev", [(4096, DEV), (128, 50)],
                         ids=["paper_site", "site50"])
def test_hp_commit_has_no_window_scatter_or_flat_relayout_on_v5e(
        segment_hlo, B, n_dev):
    """The HP commit writes one device's row back in place: the segment
    program holds no scatter into the windows and no copy to a flattened
    ``[B x Dev, CFG, T, W]`` view of them, which is how a per-replica
    row scatter lowers on a v5e (a relayout of the whole state)."""
    lines = segment_hlo(B, n_dev).splitlines()
    win = f",{CFG},{T},{W}]"
    assert not [ln for ln in lines
                if " scatter(" in ln and win in ln.split(" scatter(")[0]]
    flat = f"[{B * n_dev},{CFG},{T},{W}]"
    assert not [ln for ln in lines
                if " copy(" in ln and flat in ln.split(" copy(")[0]]


@pytest.mark.parametrize("B, n_dev", [(4096, DEV), (128, 50)],
                         ids=["paper_site", "site50"])
def test_requeue_has_no_gather_or_scatter_on_v5e(segment_hlo, B, n_dev):
    """The re-queue buffers (``[B, R]``, R = 4) are read and written by
    selects over R, so the compiled segment holds no gather or scatter.
    A per-row index into them lowers on a v5e to gathers of ``[B]`` and
    scatters into ``[B, R]`` or, at B=4096, into a flattened ``[B x R]``
    view that carries no op name: hence no shape or scope filter."""
    lines = [ln.strip()[:160] for ln in segment_hlo(B, n_dev).splitlines()
             if " gather(" in ln or " scatter(" in ln]
    assert not lines, lines


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
