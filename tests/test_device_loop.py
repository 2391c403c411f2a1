"""The fleet engine's device loop (fleet/engine.py ``frame_step``): one
rolled loop over the site's devices, so the segment program's structure
does not depend on the device count.

- at 4 devices the engine reproduces, bit for bit, what the per-device
  Python unroll it replaced produced on a pinned trace
  (``testdata/fleet_dev4_pinned.npz``): final state, stats and
  telemetry, with telemetry on and off, on the mesh and off it, and
  under the sanitizer;
- the lowered segment program holds as many placement call sites at 50
  devices as at 4.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import FleetParams, fleet_run, make_fleet, make_workload
from repro.fleet import engine

HERE = os.path.dirname(os.path.abspath(__file__))
PINNED = os.path.join(HERE, "testdata", "fleet_dev4_pinned.npz")
#: the pinned trace: weighted4 at congestion 0.3 (preemption, re-queue
#: and offload all happen), 20 frames in segments of 8 (the last one
#: padded), 8 replicas of the paper's 4-device site.
B, F, DEV, SEED, SEGMENT = 8, 20, 4, 20240611, 8


def pinned_run(params: FleetParams):
    """``fleet_run`` on the pinned trace, flattened to ``{key: array}``
    (telemetry keys only when it is on)."""
    wl = make_workload("weighted4", B, F, DEV, seed=SEED, congestion=0.3)
    out = fleet_run(make_fleet(B, DEV, requeue_slots=params.requeue_slots),
                    wl.values, wl.bw_scale, params=params)
    flat = {}
    for part, tree in zip(("state", "stats"), out[:2]):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            flat[part + jax.tree_util.keystr(path)] = np.asarray(leaf)
    if params.telemetry:
        rec = out[2]
        flat["telemetry.ticks"] = np.asarray(rec.ticks)
        for name, leaf in rec.series._asdict().items():
            flat[f"telemetry.{name}"] = np.asarray(leaf)
    return flat


@pytest.fixture(scope="module")
def pinned():
    with np.load(PINNED) as z:
        return dict(z)


def test_pinned_trace_exercises_every_decision(pinned):
    for k in ("hp_preempted", "lp_requeued", "lp_offloaded", "lp_four_core",
              "hp_failed", "lp_failed"):
        assert pinned[f"stats.{k}"].sum() > 0, k


@pytest.mark.parametrize("telemetry", [False, True], ids=["telem0", "telem1"])
@pytest.mark.parametrize("mesh_shards", [0, 1], ids=["mesh0", "mesh1"])
def test_engine_matches_the_unrolled_engine_at_4_devices(pinned, telemetry,
                                                         mesh_shards):
    got = pinned_run(FleetParams(n_devices=DEV, segment_frames=SEGMENT,
                                 telemetry=telemetry,
                                 mesh_shards=mesh_shards))
    want = {k: v for k, v in pinned.items()
            if telemetry or not k.startswith("telemetry.")}
    assert sorted(got) == sorted(want)
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], v, err_msg=k)


def test_engine_matches_the_unrolled_engine_under_sanitize(pinned,
                                                           monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    got = pinned_run(FleetParams(n_devices=DEV, segment_frames=SEGMENT))
    for k, v in pinned.items():
        if not k.startswith("telemetry."):
            np.testing.assert_array_equal(got[k], v, err_msg=k)


def _placement_call_sites(n_dev: int, batch: int = 8) -> int:
    """``fused_place`` call sites in the lowered segment program."""
    params = FleetParams(n_devices=n_dev, placement_backend="ref")
    wl = make_workload("weighted1", batch, 4, n_dev, seed=1, congestion=0.3)
    args = (engine.initial_carry(make_fleet(batch, n_dev)),
            jnp.asarray(wl.values, jnp.int32),
            jnp.asarray(wl.bw_scale, jnp.float32),
            jnp.int32(0), jnp.int32(4))
    text = engine._run_segment.lower(*args, params=params).as_text()
    return len(re.findall(r"call @fused_place_ref\b", text))


def test_segment_program_does_not_grow_with_the_device_count():
    four = _placement_call_sites(4)
    # one re-queue attempt, then per device one re-placement and MAX_LP
    # placements, each written once in the device loop's body
    assert four == 1 + 1 + engine.MAX_LP
    assert _placement_call_sites(50) == four
