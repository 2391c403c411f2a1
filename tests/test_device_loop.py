"""The fleet engine's device loop (fleet/engine.py ``frame_step``): one
rolled loop over the site's devices, so the segment program's structure
does not depend on the device count.

- at 4 devices the engine reproduces, bit for bit, what the per-device
  Python unroll it replaced produced on a pinned trace
  (``testdata/fleet_dev4_pinned.npz``): final state, stats and
  telemetry, with telemetry on and off, on the mesh and off it, and
  under the sanitizer;
- the lowered segment program holds as many placement call sites at 50
  devices as at 4;
- the HP commit trims the device's row on a dynamic slice and writes it
  back in place, bit-identical to the gather/scatter ``fanout_commit``;
- the re-queue buffer is read and written at a per-row slot by selects
  over its R slots, bit-identical to the row gather and scatter, so the
  segment program holds no gather or scatter at all.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.jax_state import fanout_commit
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


def _random_commit(n_dev: int, mask: str, n: int = 16, seed: int = 7):
    """A fleet of ``n`` replicas with random windows (invalid slots hold
    junk, not ``BIG``) and one random HP commit per replica: ``(st, s,
    e, do)``, ``do`` mixed, all True or all False."""
    rng = np.random.default_rng(seed + n_dev)
    st = make_fleet(n, n_dev).sched
    shape = st.win_t1.shape                        # [N, Dev, CFG, T, W]
    # disjoint windows per track: sorted cut points, paired up
    cuts = np.sort(rng.uniform(0.0, 100.0, shape[:-1] + (2 * shape[-1],)),
                   axis=-1).astype(np.float32)
    t1, t2 = cuts[..., 0::2], cuts[..., 1::2]
    valid = rng.random(shape) < 0.7
    t1 = np.where(valid, t1, rng.uniform(-50.0, 150.0, shape))
    t2 = np.where(valid, t2, rng.uniform(-50.0, 150.0, shape))
    md = rng.uniform(0.05, 2.0, st.min_dur.shape)
    s = rng.uniform(0.0, 90.0, n).astype(np.float32)
    e = s + rng.uniform(0.5, 20.0, n).astype(np.float32)
    do = {"mixed": rng.random(n) < 0.5, "all": np.ones(n, bool),
          "none": np.zeros(n, bool)}[mask]
    st = st._replace(win_t1=jnp.asarray(t1, jnp.float32),
                     win_t2=jnp.asarray(t2, jnp.float32),
                     win_valid=jnp.asarray(valid),
                     min_dur=jnp.asarray(md, jnp.float32))
    return st, jnp.asarray(s), jnp.asarray(e), jnp.asarray(do)


@pytest.mark.parametrize("mask", ["mixed", "all", "none"])
@pytest.mark.parametrize("n_dev, d", [(4, 0), (4, 2), (4, 3),
                                      (50, 0), (50, 25), (50, 49)])
def test_hp_commit_matches_fanout_commit_bit_for_bit(n_dev, d, mask):
    st, s, e, do = _random_commit(n_dev, mask)
    n = s.shape[0]
    got_st, got_nd = jax.jit(engine._hp_commit)(st, jnp.int32(d), s, e, do)
    t1, t2, valid, want_nd, _ = jax.jit(fanout_commit)(
        st.win_t1, st.win_t2, st.win_valid, st.min_dur,
        jnp.full((n,), d, jnp.int32),
        jnp.full((n,), engine.HP_IDX, jnp.int32), s, e, do)
    for name, want in (("win_t1", t1), ("win_t2", t2), ("win_valid", valid)):
        np.testing.assert_array_equal(np.asarray(getattr(got_st, name)),
                                      np.asarray(want), err_msg=name)
    np.testing.assert_array_equal(np.asarray(got_nd), np.asarray(want_nd))
    changed = np.asarray(got_st.win_t1 != st.win_t1).any(axis=(2, 3, 4))
    # only device d's row of a committing replica may change, and the
    # random commits do trim something
    assert not changed[:, np.arange(n_dev) != d].any()
    assert not changed[~np.asarray(do)].any()
    if mask != "none":
        assert changed[:, d].any()


def _primitives(jaxpr) -> set[str]:
    """Every primitive name in ``jaxpr`` and the jaxprs nested in it."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def test_hp_commit_lowers_without_gather_or_scatter():
    st, s, e, do = _random_commit(4, "mixed", n=8)
    prims = _primitives(jax.make_jaxpr(engine._hp_commit)(
        st, jnp.int32(1), s, e, do).jaxpr)
    assert not {p for p in prims if "gather" in p or "scatter" in p}, prims
    assert {"dynamic_slice", "dynamic_update_slice"} <= prims
    # the gather/scatter form it replaced, for contrast
    n = s.shape[0]
    old = _primitives(jax.make_jaxpr(fanout_commit)(
        st.win_t1, st.win_t2, st.win_valid, st.min_dur,
        jnp.full((n,), 1, jnp.int32), jnp.zeros((n,), jnp.int32),
        s, e, do).jaxpr)
    assert {"gather", "scatter"} <= old


def _requeue_case(dtype, R: int, pattern: str, n: int = 16, seed: int = 3):
    """A ``[n, R]`` re-queue buffer of ``dtype``, one slot index per row
    and one value per row.  The f32 buffer holds -0.0, ``BIG`` and
    negative values, where a pick that sums masked zeros would differ."""
    rng = np.random.default_rng(seed + R)
    if dtype == np.float32:
        pool = np.array([-0.0, 0.0, engine.BIG, -engine.BIG, -3.5, -1e-30,
                         7.25, np.inf, -np.inf], np.float32)
        x, val = rng.choice(pool, (n, R)), rng.choice(pool, n)
    elif dtype == np.int32:
        lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
        x = rng.integers(lo, hi, (n, R), dtype=np.int32, endpoint=True)
        val = rng.integers(lo, hi, n, dtype=np.int32, endpoint=True)
    else:
        x, val = rng.random((n, R)) < 0.5, rng.random(n) < 0.5
    x, val = x.astype(dtype), val.astype(dtype)
    # the slots the engine indexes: the first free one (all free, all
    # full, mixed), slot 0 everywhere, or any slot
    ok = {"all_free": np.zeros((n, R), bool), "all_full": np.ones((n, R), bool),
          "mixed": rng.random((n, R)) < 0.5}.get(pattern)
    if ok is not None:
        idx = np.argmin(ok, axis=1)
    elif pattern == "zero":
        idx = np.zeros(n, np.int64)
    else:
        idx = rng.integers(0, R, n)
    return jnp.asarray(x), jnp.asarray(idx, jnp.int32), jnp.asarray(val)


@pytest.mark.parametrize("pattern",
                         ["all_free", "all_full", "mixed", "zero", "any"])
@pytest.mark.parametrize("R", [1, 4, 7])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.bool_],
                         ids=["f32", "i32", "bool"])
def test_requeue_select_matches_indexed_form_bit_for_bit(dtype, R, pattern):
    x, idx, val = _requeue_case(dtype, R, pattern)
    rows = jnp.arange(x.shape[0])
    bits = lambda a: np.asarray(a).view(np.uint8)
    got = jax.jit(engine._pick)(x, idx)
    np.testing.assert_array_equal(bits(got), bits(x[rows, idx]))
    got = jax.jit(engine._put)(x, idx, val)
    np.testing.assert_array_equal(bits(got), bits(x.at[rows, idx].set(val)))
    # the engine's "no slot" index R writes nothing
    skip = jnp.where(jnp.arange(x.shape[0]) % 2 == 0, idx, R)
    got = jax.jit(engine._put)(x, skip, val)
    want = x.at[rows, skip].set(val, mode="drop")
    np.testing.assert_array_equal(bits(got), bits(want))


@pytest.mark.parametrize("n_dev", [4, 50])
def test_segment_lowers_without_gather_or_scatter(n_dev):
    """Every read and write of the re-queue buffers is a select over its
    R slots, and the HP commit a dynamic slice, so no phase of the tick
    indexes rows one at a time."""
    batch = 8
    params = FleetParams(n_devices=n_dev, placement_backend="ref")
    wl = make_workload("weighted4", batch, 4, n_dev, seed=1, congestion=0.3)
    args = (engine.initial_carry(make_fleet(batch, n_dev)),
            jnp.asarray(wl.values, jnp.int32),
            jnp.asarray(wl.bw_scale, jnp.float32),
            jnp.int32(0), jnp.int32(4))
    seg = lambda *a: engine._segment_impl(*a, params=params)
    prims = _primitives(jax.make_jaxpr(seg)(*args).jaxpr)
    assert "scan" in prims
    assert not {p for p in prims if "gather" in p or "scatter" in p}, prims
