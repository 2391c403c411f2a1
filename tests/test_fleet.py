"""Fleet subsystem tests: engine invariants, scenario registry and sweep
plumbing.

All `fleet_run` invocations share one shape/params signature so the
whole module pays for a single XLA compilation.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st

from repro.core.jax_state import export_state
from repro.core.scheduler import RASScheduler
from repro.fleet import (
    FleetParams,
    fleet_run,
    make_fleet,
    make_workload,
    run_sweep,
    scenario_names,
    summarize,
    SweepConfig,
)

# One signature for every engine call in this module (single compile).
B, F, DEV = 8, 8, 4
PARAMS = FleetParams(n_devices=DEV)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_result():
    wl = make_workload("uniform", B, F, DEV, seed=0, congestion=0.1)
    fleet = make_fleet(B, DEV)
    out, stats = fleet_run(fleet, wl.values, wl.bw_scale, params=PARAMS)
    return wl, out, stats


def test_fleet_run_invariants(fleet_result):
    wl, out, stats = fleet_result
    s = {k: np.asarray(v) for k, v in stats._asdict().items()}
    frames = s["frames"]
    assert (frames == (wl.values >= 0).sum(axis=(0, 2))).all()
    # victim conservation: every spawned LP task is completed, failed,
    # missed by preemption, or still pending in the re-queue buffer
    pending = np.asarray(out.rq_valid).sum(axis=1)
    assert (s["lp_spawned"] == s["lp_completed"] + s["lp_failed"]
            + s["missed_by_preemption"] + pending).all()
    assert (s["frames_completed"] <= frames).all()
    # HP either runs (with or without preemption) or fails admission
    assert (s["hp_completed"] + s["hp_failed"] == frames).all()
    assert (s["hp_preempted"] <= s["hp_completed"]).all()
    # committed preemptions evict exactly one victim each, and every
    # victim resolves to re-placed, missed, or still-pending — never lost
    assert (s["lp_requeued"] + s["missed_by_preemption"] + pending
            == s["hp_preempted"]).all()
    assert (s["lp_offloaded"] <= s["lp_spawned"] + s["lp_requeued"]).all()
    # link FIFO time never decreases from its start
    assert (np.asarray(out.link_free) >= 0).all()


def test_fleet_run_deterministic(fleet_result):
    wl, _, stats = fleet_result
    fleet = make_fleet(B, DEV)
    _, stats2 = fleet_run(fleet, wl.values, wl.bw_scale, params=PARAMS)
    for a, b in zip(stats, stats2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_summary_fields(fleet_result):
    _, _, stats = fleet_result
    s = summarize(stats, F)
    assert s["replicas"] == B
    for key in ("frame_completion_rate", "lp_violation_rate",
                "lp_throughput_per_s"):
        assert set(s[key]) == {"mean", "ci95"}
        assert s[key]["mean"] >= 0.0


# ---------------------------------------------------------------------------
# preemption fidelity: victim capture, reallocation, expiry
# ---------------------------------------------------------------------------
#
# These tests inject a synthetic committed-LP victim through the per-device
# victim cache and force an HP containment miss by invalidating the HP
# windows of device 0 — the (B, F, DEV, PARAMS) signature matches the rest
# of the module, so no extra XLA compilation is paid.  The injected victim
# has no spawn credit, so assertions are on the preemption counters, not
# the spawn-conservation identity (covered by the property test below).

def _preemption_fixture(vc_deadline: float, lp_open: bool):
    """A fleet whose first frame (device 0, HP-only) must preempt an
    injected victim with the given deadline.  ``lp_open`` keeps device 0's
    LP windows available (immediate reallocation possible)."""
    fleet = make_fleet(B, DEV)
    wv = fleet.sched.win_valid.at[:, 0, 0].set(False)  # no HP gap on dev 0
    if not lp_open:
        wv = wv.at[:, 0, 1].set(False).at[:, 0, 2].set(False)
    fleet = fleet._replace(
        sched=fleet.sched._replace(win_valid=wv),
        vc_valid=fleet.vc_valid.at[:, 0].set(True),
        vc_end=fleet.vc_end.at[:, 0].set(30.0),
        vc_deadline=fleet.vc_deadline.at[:, 0].set(vc_deadline),
    )
    values = np.full((F, B, DEV), -1, np.int8)
    values[0, :, 0] = 0  # HP-only frame at t=0 on the loaded device
    return fleet, values


def _stats_np(stats):
    return {k: np.asarray(v) for k, v in stats._asdict().items()}


def test_victim_requeued_immediately_when_capacity_exists():
    fleet, values = _preemption_fixture(vc_deadline=32.0, lp_open=True)
    bw = np.ones((F, B), np.float32)
    out, stats = fleet_run(fleet, jnp.asarray(values), jnp.asarray(bw),
                           params=PARAMS)
    s = _stats_np(stats)
    assert (s["hp_preempted"] == 1).all()
    assert (s["hp_failed"] == 0).all()
    assert (s["lp_requeued"] == 1).all()          # §VI.A reallocation path
    assert (s["missed_by_preemption"] == 0).all()
    assert (np.asarray(out.rq_valid).sum(axis=1) == 0).all()


def test_victim_with_live_deadline_survives_via_buffer():
    """Immediate reallocation is infeasible on tick 0 (local LP windows
    gone, link too slow for a transfer) but the congestion burst clears on
    tick 1 — the buffered victim must be re-placed, never silently lost."""
    fleet, values = _preemption_fixture(vc_deadline=32.0, lp_open=False)
    bw = np.ones((F, B), np.float32)
    bw[0, :] = 1e-3  # saturated link: remote placement infeasible at t=0
    out, stats = fleet_run(fleet, jnp.asarray(values), jnp.asarray(bw),
                           params=PARAMS)
    s = _stats_np(stats)
    assert (s["hp_preempted"] == 1).all()
    assert (s["lp_requeued"] == 1).all()          # placed from the buffer
    assert (s["missed_by_preemption"] == 0).all()
    assert (np.asarray(out.rq_valid).sum(axis=1) == 0).all()


def test_victim_with_expired_deadline_counted_missed():
    fleet, values = _preemption_fixture(vc_deadline=10.0, lp_open=False)
    bw = np.full((F, B), 1e-3, np.float32)  # link saturated throughout
    out, stats = fleet_run(fleet, jnp.asarray(values), jnp.asarray(bw),
                           params=PARAMS)
    s = _stats_np(stats)
    assert (s["hp_preempted"] == 1).all()
    assert (s["lp_requeued"] == 0).all()
    assert (s["missed_by_preemption"] == 1).all()  # dropped loudly, not lost
    assert (np.asarray(out.rq_valid).sum(axis=1) == 0).all()


def test_no_preemptable_victim_fails_hp_admission():
    """HP containment miss with an empty victim cache is the serial
    engine's ``no-preemptable`` admission failure, not a preemption."""
    fleet = make_fleet(B, DEV)
    fleet = fleet._replace(sched=fleet.sched._replace(
        win_valid=fleet.sched.win_valid.at[:, 0, 0].set(False)
    ))
    values = np.full((F, B, DEV), -1, np.int8)
    values[0, :, 0] = 2
    bw = np.ones((F, B), np.float32)
    _, stats = fleet_run(fleet, jnp.asarray(values), jnp.asarray(bw),
                         params=PARAMS)
    s = _stats_np(stats)
    assert (s["hp_failed"] == 1).all()
    assert (s["hp_preempted"] == 0).all()   # nothing evicted => no count
    assert (s["hp_completed"] == 0).all()
    assert (s["lp_spawned"] == 0).all()     # the frame dies with its HP
    assert (s["frames_completed"] == 0).all()


@given(hyp_seed=st.integers(0, 999))
@settings(max_examples=8, deadline=None)
def test_victim_conservation_property(hyp_seed):
    """A victim re-queued with a live deadline is never silently dropped:
    under arbitrary bursty workloads every spawned LP task resolves to
    completed / failed / missed_by_preemption / pending, and every
    committed preemption's victim resolves to requeued / missed / pending.
    (Shares the module's compiled engine signature.)"""
    wl = make_workload("poisson_burst", B, F, DEV, seed=hyp_seed,
                       congestion=0.4, lam=3.0)
    fleet = make_fleet(B, DEV)
    out, stats = fleet_run(fleet, wl.values, wl.bw_scale, params=PARAMS)
    s = _stats_np(stats)
    pending = np.asarray(out.rq_valid).sum(axis=1)
    np.testing.assert_array_equal(
        s["lp_spawned"],
        s["lp_completed"] + s["lp_failed"] + s["missed_by_preemption"]
        + pending,
    )
    np.testing.assert_array_equal(
        s["hp_preempted"],
        s["lp_requeued"] + s["missed_by_preemption"] + pending,
    )
    np.testing.assert_array_equal(s["hp_completed"] + s["hp_failed"],
                                  s["frames"])
    for key in ("lp_completed", "lp_requeued", "missed_by_preemption"):
        assert (s[key] >= 0).all()


def test_empty_workload_places_nothing():
    values = np.full((F, B, DEV), -1, np.int8)
    bw = np.ones((F, B), np.float32)
    fleet = make_fleet(B, DEV)
    _, stats = fleet_run(fleet, jnp.asarray(values), jnp.asarray(bw),
                         params=PARAMS)
    assert int(np.asarray(stats.frames).sum()) == 0
    assert int(np.asarray(stats.lp_spawned).sum()) == 0


# ---------------------------------------------------------------------------
# scan segmenting, carry donation, in-scan compaction
# ---------------------------------------------------------------------------

def _assert_runs_equal(res_a, res_b):
    out_a, stats_a = res_a
    out_b, stats_b = res_b
    for a, b in zip(stats_a, stats_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(out_a, out_b):
        for xa, xb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_segmented_run_matches_unsegmented():
    """F=8 split into 3-tick segments (last segment padded with one empty
    tick) must be bit-identical to a single-segment run — padded ticks are
    exact no-ops."""
    wl = make_workload("uniform", B, F, DEV, seed=4, congestion=0.3)
    whole = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                      params=dataclasses.replace(PARAMS, segment_frames=0))
    split = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                      params=dataclasses.replace(PARAMS, segment_frames=3))
    _assert_runs_equal(whole, split)


def test_donated_carry_leaves_input_fleet_valid():
    """_run_segment donates its carry buffers; fleet_run must copy first
    so the caller can reuse the same fleet (benchmarks run it twice)."""
    wl = make_workload("uniform", B, F, DEV, seed=2, congestion=0.2)
    fleet = make_fleet(B, DEV)
    first = fleet_run(fleet, wl.values, wl.bw_scale, params=PARAMS)
    again = fleet_run(fleet, wl.values, wl.bw_scale, params=PARAMS)
    _assert_runs_equal(first, again)


@pytest.mark.parametrize("batch, n_dev, slots", [(8, 4, 4), (3, 50, 2)])
def test_make_fleet_broadcasts_the_exported_scheduler(batch, n_dev, slots):
    """One device program builds the fleet: every replica holds the
    exported pristine scheduler, every other buffer is zero, with the
    shapes and dtypes the carry expects."""
    fleet = make_fleet(batch, n_dev, requeue_slots=slots)
    base = export_state(RASScheduler(n_dev, 20e6))
    for got, want in zip(fleet.sched, base):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(got), np.broadcast_to(np.asarray(want),
                                             (batch,) + want.shape))
    shapes = {"link_free": (batch,),
              "rq_deadline": (batch, slots), "rq_src": (batch, slots),
              "rq_valid": (batch, slots), "vc_start": (batch, n_dev),
              "vc_end": (batch, n_dev), "vc_deadline": (batch, n_dev),
              "vc_src": (batch, n_dev), "vc_valid": (batch, n_dev)}
    for name, shape in shapes.items():
        x = getattr(fleet, name)
        assert x.shape == shape and not np.asarray(x).any(), name
    assert fleet.rq_src.dtype == fleet.vc_src.dtype == jnp.int32
    assert fleet.rq_valid.dtype == fleet.vc_valid.dtype == jnp.bool_


def test_initial_carry_gives_every_leaf_a_buffer_of_its_own():
    """The segment runners donate the carry, so each leaf needs its own
    buffer, shared neither with the caller's fleet nor with another leaf
    (the zero stats all hold one value)."""
    from repro.fleet import engine

    fleet = make_fleet(B, DEV)
    carry = engine.initial_carry(fleet)
    ptrs = [x.unsafe_buffer_pointer() for x in jax.tree_util.tree_leaves(carry)]
    theirs = {x.unsafe_buffer_pointer()
              for x in jax.tree_util.tree_leaves(fleet)}
    assert len(set(ptrs)) == len(ptrs)
    assert not set(ptrs) & theirs


def test_per_tick_compaction_preserves_invariants():
    """compact_every=1 (a compaction pass before every tick) must keep
    the conservation identities intact and never decrease completions —
    compaction only merges abutting windows, it cannot lose capacity."""
    wl = make_workload("poisson_burst", B, F, DEV, seed=6, congestion=0.4,
                       lam=3.0)
    base = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                     params=PARAMS)
    out, stats = fleet_run(
        make_fleet(B, DEV), wl.values, wl.bw_scale,
        params=dataclasses.replace(PARAMS, compact_every=1),
    )
    s = _stats_np(stats)
    pending = np.asarray(out.rq_valid).sum(axis=1)
    np.testing.assert_array_equal(
        s["lp_spawned"],
        s["lp_completed"] + s["lp_failed"] + s["missed_by_preemption"]
        + pending,
    )
    np.testing.assert_array_equal(s["hp_completed"] + s["hp_failed"],
                                  s["frames"])
    # compaction frees W slots: fragmentation drops can only shrink
    assert (s["remainders_dropped"]
            <= _stats_np(base[1])["remainders_dropped"]).all()


def test_remainders_dropped_counter_in_stats():
    """The fragmentation counter is carried per replica and is
    non-negative under a congested workload."""
    wl = make_workload("poisson_burst", B, F, DEV, seed=8, congestion=0.5,
                       lam=3.0)
    _, stats = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                         params=PARAMS)
    rd = np.asarray(stats.remainders_dropped)
    assert rd.shape == (B,)
    assert (rd >= 0).all()


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def test_scenario_registry_contents():
    names = scenario_names()
    for expected in ("uniform", "weighted1", "weighted4", "poisson_burst",
                     "diurnal", "mobility"):
        assert expected in names


@pytest.mark.parametrize("name", sorted(scenario_names()))
def test_scenario_shapes_and_alphabet(name):
    wl = make_workload(name, 6, 12, DEV, seed=1, congestion=0.2)
    assert wl.values.shape == (12, 6, DEV)
    assert wl.values.dtype == np.int8
    assert wl.bw_scale.shape == (12, 6)
    assert wl.values.min() >= -1 and wl.values.max() <= 4
    assert (wl.bw_scale > 0).all() and (wl.bw_scale <= 1.2).all()


@pytest.mark.parametrize("table", ["uniform", "weighted1", "weighted2",
                                   "weighted3", "weighted4", "with_zeros"])
def test_paper_trace_draw_is_rng_choice(table):
    """The trace draw is ``rng.choice`` with ``p``, draw for draw, and
    leaves the generator where ``choice`` leaves it."""
    from repro.fleet.scenarios import _draw_from_probs
    from repro.sim.traces import VALUES, _uniform_probs, _weighted_probs

    if table == "uniform":
        probs = _uniform_probs()
    elif table == "with_zeros":
        probs = dict(zip(VALUES, (0.0, 0.3, 0.0, 0.0, 0.7, 0.0)))
    else:
        probs = _weighted_probs(int(table[-1]))
    p = np.array([probs[v] for v in VALUES], np.float64)
    for seed, shape in ((0, (95, 64, 4)), (1, (3, 5, 50)), (2, (1, 1, 1))):
        ours, theirs = np.random.default_rng(seed), np.random.default_rng(seed)
        got = _draw_from_probs(ours, probs, shape)
        want = theirs.choice(np.array(VALUES, np.int8), size=shape,
                             p=p / p.sum())
        assert got.dtype == want.dtype == np.int8
        np.testing.assert_array_equal(got, want)
        assert ours.random() == theirs.random()


def test_scenario_reproducible_and_seed_sensitive():
    a = make_workload("poisson_burst", 4, 10, DEV, seed=5)
    b = make_workload("poisson_burst", 4, 10, DEV, seed=5)
    c = make_workload("poisson_burst", 4, 10, DEV, seed=6)
    np.testing.assert_array_equal(a.values, b.values)
    assert not np.array_equal(a.values, c.values)


def test_congestion_scales_bandwidth_down():
    clean = make_workload("uniform", 16, 30, DEV, seed=2, congestion=0.0)
    busy = make_workload("uniform", 16, 30, DEV, seed=2, congestion=0.5)
    assert busy.bw_scale.mean() < clean.bw_scale.mean()


def test_unknown_scenario_raises():
    with pytest.raises(ValueError, match="unknown scenario"):
        make_workload("nope", 2, 4, DEV)


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------

def test_sweep_grid_and_batching():
    """2 scenarios × 2 congestion × 2 seeds = 8 replicas in one batch of 8
    (reuses the module's compiled engine signature)."""
    cfg = SweepConfig(
        scenarios=("uniform", "mobility"),
        congestion_levels=(0.0, 0.4),
        n_seeds=2, n_frames=F, n_devices=DEV, batch_size=B,
        params=PARAMS,
    )
    out = run_sweep(cfg)
    assert out["_sweep"]["total_replicas"] == 8
    cells = [k for k in out if k != "_sweep"]
    assert sorted(cells) == sorted(
        ["uniform@0", "uniform@0.4", "mobility@0", "mobility@0.4"]
    )
    for c in cells:
        assert out[c]["replicas"] == 2


def test_sweep_pads_ragged_tail():
    """5 seeds × 2 cells = 10 replicas > batch_size 8 -> two batches of 8
    with a 6-replica pad on the tail; padded replicas must not leak into
    the per-cell reduction (both batches reuse the module's compiled
    B=8 signature)."""
    cfg = SweepConfig(
        scenarios=("uniform",),
        congestion_levels=(0.0, 0.6),
        n_seeds=5, n_frames=F, n_devices=DEV, batch_size=B,
        params=PARAMS,
    )
    out = run_sweep(cfg)
    assert out["_sweep"]["total_replicas"] == 10
    assert out["_sweep"]["batch_size"] == B
    assert out["uniform@0"]["replicas"] == 5
    assert out["uniform@0.6"]["replicas"] == 5
