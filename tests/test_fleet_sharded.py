"""Sharded fleet engine tests: `shard_map` over the batch axis is pure
data parallelism (replicas never interact), so every sharded run must be
**bit-identical** to the unsharded engine — stats, final state, telemetry
and the sanitized leg alike.

Mesh sizes beyond the local device count skip, so the tier-1 suite (one
CPU device) exercises the single-shard mesh machinery and the CI `mesh`
leg (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) covers the
multi-device cases.  Everything shares one (B=16, F=8, DEV=4) compile
signature per params variant; the B=12 pad case reuses the B=16 program
(12 pads up to 16 on an 8-way mesh).
"""

import dataclasses
import os

import numpy as np
import pytest

import jax

from repro.fleet import (
    FleetParams, SweepConfig, fleet_mesh, fleet_run, make_fleet,
    make_workload, run_sweep, shard_pad,
)

B, F, DEV = 16, 8, 4
PARAMS = FleetParams(n_devices=DEV)


def _needs(shards: int):
    if shards > jax.device_count():
        pytest.skip(
            f"needs {shards} devices (have {jax.device_count()}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={shards})"
        )


def _wl(batch=B, seed=3, congestion=0.3, scenario="uniform"):
    return make_workload(scenario, batch, F, DEV, seed=seed,
                         congestion=congestion)


def _run(params, wl, batch=B):
    fleet = make_fleet(batch, DEV, requeue_slots=params.requeue_slots)
    return fleet_run(fleet, wl.values, wl.bw_scale, params=params)


def _assert_stats_equal(a, b, ctx=""):
    for f in a._fields:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f"{ctx}{f}"


def _assert_state_equal(a, b):
    for f in a._fields:
        x, y = getattr(a, f), getattr(b, f)
        if hasattr(x, "_fields"):        # nested SchedState
            for g in x._fields:
                assert np.array_equal(np.asarray(getattr(x, g)),
                                      np.asarray(getattr(y, g))), f"{f}.{g}"
        else:
            assert np.array_equal(np.asarray(x), np.asarray(y)), f


@pytest.fixture(scope="module")
def reference():
    """Unsharded (state, stats) at the shared signature."""
    return _run(PARAMS, _wl())


@pytest.mark.parametrize("shards", [1, 2, 8])
def test_sharded_bit_identical(reference, shards):
    _needs(shards)
    st, stats = _run(
        dataclasses.replace(PARAMS, mesh_shards=shards), _wl()
    )
    _assert_stats_equal(reference[1], stats, ctx=f"shards={shards}: ")
    _assert_state_equal(reference[0], st)


def test_batch_pad_bit_identical():
    """B=12 does not divide an 8-way mesh: the engine pads to 16 with
    no-op replicas and trims them from every output."""
    _needs(8)
    wl = _wl(batch=12, seed=5, congestion=0.0)
    ref_st, ref_stats = _run(PARAMS, wl, batch=12)
    st, stats = _run(
        dataclasses.replace(PARAMS, mesh_shards=8), wl, batch=12
    )
    assert stats.hp_completed.shape == (12,)
    _assert_stats_equal(ref_stats, stats)
    _assert_state_equal(ref_st, st)


@pytest.mark.parametrize("shards", [1, 8])
def test_telemetry_composes_with_sharding(shards):
    """In-scan telemetry under shard_map: identical record AND identical
    stats (capture stays read-only), with padded replicas trimmed."""
    _needs(shards)
    pt = dataclasses.replace(PARAMS, telemetry=True, telemetry_every=2)
    wl = _wl(seed=7, scenario="weighted2")
    _, ref_stats, ref_rec = _run(pt, wl)
    _, stats, rec = _run(
        dataclasses.replace(pt, mesh_shards=shards), wl
    )
    _assert_stats_equal(ref_stats, stats)
    assert rec.n_replicas == B
    assert np.array_equal(ref_rec.ticks, rec.ticks)
    for f in ref_rec.series._fields:
        assert np.array_equal(getattr(ref_rec.series, f),
                              getattr(rec.series, f)), f


@pytest.mark.parametrize("shards", [1, 8])
def test_sanitize_composes_with_sharding(monkeypatch, shards):
    """REPRO_SANITIZE=1 discharges checkify *outside* shard_map; the
    checked sharded leg must agree with the unchecked unsharded one."""
    _needs(shards)
    ref = _run(PARAMS, _wl())[1]
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    stats = _run(
        dataclasses.replace(PARAMS, mesh_shards=shards), _wl()
    )[1]
    _assert_stats_equal(ref, stats)


@pytest.mark.parametrize("shards", [1, 8])
def test_sharded_sweep_matches_host_reduction(shards):
    """The on-device per-cell moment reduction reproduces the host-side
    summarize() means, and the conservation residual stays exactly 0."""
    _needs(shards)
    cfg = SweepConfig(
        scenarios=("uniform",), congestion_levels=(0.0, 0.3),
        n_seeds=8, n_frames=F, n_devices=DEV, batch_size=B,
    )
    ref = run_sweep(cfg)
    out = run_sweep(dataclasses.replace(cfg, mesh_shards=shards))
    assert out["_sweep"]["mesh"]["shards"] == shards
    for cell, summary in ref.items():
        if cell.startswith("_"):
            continue
        assert out[cell]["conservation_residual"]["max_abs"] == 0
        for key, val in summary.items():
            if not (isinstance(val, dict) and "mean" in val):
                continue
            got = out[cell][key]["mean"]
            assert got == pytest.approx(val["mean"], rel=1e-5, abs=1e-5), (
                cell, key
            )


def test_mesh_oversubscription_raises():
    with pytest.raises(ValueError, match="device"):
        fleet_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError):
        fleet_mesh(0)


def test_shard_pad():
    assert shard_pad(16, 8) == 0
    assert shard_pad(12, 8) == 4
    assert shard_pad(1, 8) == 7
    assert shard_pad(12, 1) == 0


def test_cell_moments_matmuls_run_at_full_precision():
    """The per-cell sums are matmuls; at the TPU's default precision their
    f32 operands round to bf16 and a cell mean moves by ~1e-4 relative,
    so every dot in the reduction must ask for full precision."""
    from repro.fleet import cell_moments, init_stats

    b = 8
    jaxpr = jax.make_jaxpr(
        lambda s, rq, own: cell_moments(s, rq, own, n_cells=2, n_frames=F)
    )(init_stats(b), np.zeros((b, 4), bool), np.zeros((b,), np.int32))
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
