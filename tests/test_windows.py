"""Unit + property tests for the resource-availability model (§IV.A.1)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from _sched import loaded_scheduler

from repro.core.jax_state import export_state
from repro.core.tasks import (
    ALL_CONFIGS,
    HP_CONFIG,
    LP2_CONFIG,
    LP4_CONFIG,
    Priority,
    Task,
    TaskState,
)
from repro.core.windows import (
    AvailabilityList,
    DeviceAvailability,
    Window,
)
from repro.fleet.engine import HP_IDX, LP2_IDX, _hp_query
from repro.fleet.state import broadcast_state
from repro.kernels.placement.ops import fused_place_op


def make_task(cfg, start, device=0, source=0):
    t = Task(Priority.LOW, source, 0.0, 1e9, frame_id=0)
    t.config = cfg
    t.device = device
    t.start_time = start
    t.end_time = start + cfg.padded_time
    t.state = TaskState.ALLOCATED
    return t


class TestAvailabilityList:
    def test_track_count(self):
        assert AvailabilityList(HP_CONFIG).track_count == 2
        assert AvailabilityList(LP2_CONFIG).track_count == 2
        assert AvailabilityList(LP4_CONFIG).track_count == 1

    def test_find_slot_empty(self):
        al = AvailabilityList(LP2_CONFIG, horizon=(0.0, 1000.0))
        hit = al.find_slot(5.0, 100.0)
        assert hit is not None and hit[2] == 5.0

    def test_find_slot_respects_deadline(self):
        al = AvailabilityList(LP2_CONFIG, horizon=(0.0, 1000.0))
        assert al.find_slot(0.0, 10.0) is None  # cannot fit 17.2s before t=10

    def test_bisect_min_duration(self):
        al = AvailabilityList(LP2_CONFIG, horizon=(0.0, 40.0))
        al.bisect(0, 0, 10.0, 30.0)
        # left piece (0,10) < 17.2s dropped; right piece (30,40) dropped
        assert al.tracks[0] == []

    def test_bisect_keeps_long_remainders(self):
        al = AvailabilityList(LP2_CONFIG, horizon=(0.0, 100.0))
        al.bisect(0, 0, 20.0, 40.0)
        ws = al.tracks[0]
        assert [(w.t1, w.t2) for w in ws] == [(0.0, 20.0), (40.0, 100.0)]

    def test_subtract_consumes_most_overlapping_track(self):
        """Regression: consuming a barely-overlapping track instead of the
        fully-available one overcommits the device."""
        al = AvailabilityList(LP2_CONFIG, horizon=(0.0, math.inf))
        al.subtract(1.9, 19.1, 1)   # task A -> one track now [19.1, inf)
        al.subtract(2.3, 19.5, 1)   # task B: must consume the OTHER track
        # Now no track may advertise availability before 19.1.
        hit = al.find_slot(0.0, 25.0)
        assert hit is None or hit[2] >= 19.1


class TestDeviceAvailability:
    def test_write_fans_out_to_all_lists(self):
        dev = DeviceAvailability(0, horizon=(0.0, 1000.0))
        t = make_task(LP4_CONFIG, 0.0)
        dev.write_task(t)
        # a 4-core task blocks everything during its window
        for cfg in ALL_CONFIGS:
            hit = dev.list_for(cfg).find_slot(0.0, 1000.0, cfg.padded_time)
            assert hit is None or hit[2] >= t.end_time - 1e-9

    def test_remove_task_rebuilds(self):
        dev = DeviceAvailability(0, horizon=(0.0, 1000.0))
        t = make_task(LP4_CONFIG, 0.0)
        dev.write_task(t)
        dev.remove_task(t)
        hit = dev.list_for(LP4_CONFIG).find_slot(0.0, 1000.0)
        assert hit is not None and hit[2] == 0.0

    @given(
        starts=st.lists(
            st.floats(0.0, 300.0, allow_nan=False), min_size=1, max_size=12
        ),
        cfg_picks=st.lists(st.integers(0, 1), min_size=12, max_size=12),
    )
    @settings(max_examples=60, deadline=None)
    def test_no_overcommit_property(self, starts, cfg_picks):
        """INVARIANT: whatever write sequence happens, the bookkept workload
        never needs more cores than the device has, at any time, provided
        every allocation came from a containment query."""
        dev = DeviceAvailability(0, horizon=(0.0, 10_000.0))
        placed = []
        for i, s in enumerate(starts):
            cfg = (LP2_CONFIG, LP4_CONFIG)[cfg_picks[i % len(cfg_picks)]]
            al = dev.list_for(cfg)
            hit = al.find_slot(s, 10_000.0, cfg.padded_time)
            if hit is None:
                continue
            t = make_task(cfg, hit[2])
            dev.write_task(t)
            placed.append(t)
        events = []
        for t in placed:
            events.append((t.start_time, t.config.cores))
            events.append((t.end_time, -t.config.cores))
        events.sort()
        cur = peak = 0
        for _, d in events:
            cur += d
            peak = max(peak, cur)
        assert peak <= dev.device_cores, f"overcommitted: peak={peak}"

    @given(data=st.data())
    @settings(max_examples=40, deadline=None)
    def test_windows_stay_disjoint_sorted(self, data):
        al = AvailabilityList(LP2_CONFIG, horizon=(0.0, 2000.0))
        for _ in range(data.draw(st.integers(1, 10))):
            s = data.draw(st.floats(0.0, 1500.0, allow_nan=False))
            e = s + data.draw(st.floats(0.1, 100.0, allow_nan=False))
            occ = data.draw(st.integers(1, 2))
            al.subtract(s, e, occ)
            for track in al.tracks:
                for a, b in zip(track, track[1:]):
                    assert a.t2 <= b.t1 + 1e-9, "windows overlap or unsorted"
                for w in track:
                    assert w.duration >= al.min_duration - 1e-9


class TestJaxParity:
    """The serial lists round-trip through ``export_state`` into the
    jitted queries the fleet runs, which then agree with ``find_slot``
    on every device."""

    def test_hp_query_matches_python_on_every_device(self):
        for seed in (0, 7):
            s = loaded_scheduler(seed, n_req=4)
            st = broadcast_state(export_state(s), 1)
            dur = HP_CONFIG.padded_time
            for now in (2.0, 25.0, 40.0):
                found, start = jax.vmap(
                    lambda d: _hp_query(
                        st, d, jnp.full((1,), now, jnp.float32),
                        st.min_dur[:, HP_IDX], 0.0,
                    )
                )(jnp.arange(s.n_devices, dtype=jnp.int32))
                for d, dev in enumerate(s.devices):
                    py = dev.list_for(HP_CONFIG).find_slot(
                        now, now + dur + 1e-6, dur)
                    assert bool(found[d, 0]) == (py is not None), (now, d)
                    if py is not None:
                        assert abs(float(start[d, 0]) - py[2]) < 1e-3

    def test_placement_query_matches_python_on_every_device(self):
        for seed in (0, 7):
            s = loaded_scheduler(seed, n_req=4)
            n = s.n_devices
            st = broadcast_state(export_state(s), n)
            q1, dl = 35.0, 95.0
            # replica d may only place on device d: every other device's
            # deadline falls before its release
            own = np.eye(n, dtype=bool)
            _, _, _, ok, sel, start, *_ = fused_place_op(
                st.win_t1, st.win_t2, st.win_valid, st.min_dur,
                jnp.full((n, n), q1, jnp.float32),
                jnp.asarray(np.where(own, dl, q1 - 1.0), jnp.float32),
                jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), bool),
                backend="ref", cfg_pref=LP2_IDX, cfg_fallback=LP2_IDX,
            )
            for d, dev in enumerate(s.devices):
                py = dev.list_for(LP2_CONFIG).find_slot(
                    q1, dl, LP2_CONFIG.padded_time)
                assert bool(ok[d]) == (py is not None), d
                if py is not None:
                    assert int(sel[d]) == d
                    assert abs(float(start[d]) - py[2]) < 1e-3
