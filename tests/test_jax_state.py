"""Equivalence tests: the jitted placement path the fleet runs (the
engine's HP query and the placement oracle, over ``export_state``'s
arrays) vs the serial reference structures of core/windows.py."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _sched import loaded_scheduler

from repro.analysis import sanitize
from repro.core.jax_state import compact_tracks, export_state
from repro.core.scheduler import RASScheduler
from repro.core.tasks import HP_CONFIG, LP2_CONFIG, LP4_CONFIG, Priority, Task
from repro.fleet import FleetParams, fleet_run, make_fleet, make_workload
from repro.fleet import engine
from repro.fleet.engine import HP_IDX, LP2_IDX, LP4_IDX, _hp_query
from repro.fleet.state import broadcast_state
from repro.kernels.placement.ops import fused_place_op
from repro.kernels.placement.ref import SRC_PREF

BW = 20e6
N_DEV = 4


def _place(st, q1, dl, src):
    """One placement attempt of the fleet's LP configs through the
    oracle, for a one-replica state; ``q1``/``dl`` are per device."""
    t1, t2, valid, ok, sel, start, dur, use4, _ = fused_place_op(
        st.win_t1, st.win_t2, st.win_valid, st.min_dur,
        jnp.asarray(q1, jnp.float32)[None], jnp.asarray(dl, jnp.float32)[None],
        jnp.asarray([src], jnp.int32), jnp.asarray([True]),
        backend="ref", cfg_pref=LP2_IDX, cfg_fallback=LP4_IDX,
    )
    st = st._replace(win_t1=t1, win_t2=t2, win_valid=valid)
    return (st, bool(ok[0]), int(sel[0]), float(start[0]), float(dur[0]),
            bool(use4[0]))


def _windows(st):
    """Compacted (t1, t2, valid) as numpy, so layouts compare by value."""
    return [np.asarray(a) for a in compact_tracks(
        st.win_t1, st.win_t2, st.win_valid)]


def _avail(st, horizon=1e3):
    """Total window time before ``horizon``, in float64."""
    t1, t2, valid = (np.asarray(a, np.float64) for a in
                     (st.win_t1, st.win_t2, st.win_valid))
    return float(np.where(valid > 0, np.minimum(t2, horizon) - t1, 0).sum())


def test_export_shapes():
    s = loaded_scheduler(0)
    st = export_state(s)
    assert st.win_t1.shape[0] == 4                  # devices
    assert st.win_t1.shape[1] == 3                  # configs
    assert st.min_dur.shape == (3,)
    assert st._fields == ("win_t1", "win_t2", "win_valid", "min_dur")


@pytest.mark.parametrize("seed", [0, 3, 9])
def test_hp_query_matches_python(seed):
    """The engine's HP containment query on an exported scheduler finds
    the serial HP list's earliest slot, on every device and at several
    release times."""
    s = loaded_scheduler(seed)
    st = broadcast_state(export_state(s), 1)
    hp_deadline = FleetParams().hp_deadline
    dur = HP_CONFIG.padded_time
    for now in (5.0, 20.0, 35.0):
        deadline = now + max(hp_deadline, dur + 1e-6)
        for d in range(N_DEV):
            py = s.devices[d].list_for(HP_CONFIG).find_slot(
                now, deadline, dur)
            found, start = _hp_query(
                st, d, jnp.full((1,), now, jnp.float32),
                st.min_dur[:, HP_IDX], hp_deadline,
            )
            assert bool(found[0]) == (py is not None), (now, d)
            if py is not None:
                assert abs(float(start[0]) - py[2]) < 1e-3, (now, d)


@pytest.mark.parametrize("seed", [1, 5])
def test_lp_attempt_matches_python_slot(seed):
    """A single LP attempt lands where the serial lists say: the chosen
    device's earliest 2-core slot (4-core only when no device has a
    2-core one), and no device offers a slot earlier by more than the
    source preference."""
    s = loaded_scheduler(seed, n_req=4)
    st = broadcast_state(export_state(s), 1)
    now, deadline, src = 40.0, 75.0, 0
    _, ok, sel, start, dur, use4 = _place(
        st, [now] * N_DEV, [deadline] * N_DEV, src)
    slots = {cfg.name: [dev.list_for(cfg).find_slot(now, deadline,
                                                    cfg.padded_time)
                        for dev in s.devices]
             for cfg in (LP2_CONFIG, LP4_CONFIG)}
    any2 = any(p is not None for p in slots["lp2"])
    any4 = any(p is not None for p in slots["lp4"])
    assert ok == (any2 or any4)
    if not ok:
        return
    assert use4 == (not any2)
    cfg = LP4_CONFIG if use4 else LP2_CONFIG
    py = slots[cfg.name][sel]
    assert py is not None
    assert abs(start - py[2]) < 1e-3
    assert abs(dur - cfg.padded_time) < 1e-4
    for d, p in enumerate(slots[cfg.name]):
        if p is not None and d != sel:
            margin = SRC_PREF if sel == src else 0.0
            assert p[2] >= start - margin - 1e-3, d


def test_lp_attempts_consume_capacity():
    """Successive attempts consume capacity: with only the source device
    reachable, two 2-core tasks share t=0 on its two tracks and a third
    starts where they end; after every commit the windows equal the
    serial lists with the same task written into them."""
    s = RASScheduler(N_DEV, BW, seed=2)
    st = broadcast_state(export_state(s), 1)
    q1 = [0.0] + [1e6] * (N_DEV - 1)
    dl = [100.0] * N_DEV
    avail = [_avail(st)]
    starts = []
    for _ in range(3):
        st, ok, sel, start, dur, use4 = _place(st, q1, dl, 0)
        assert ok and sel == 0 and not use4
        starts.append(start)
        task = Task(Priority.LOW, 0, 0.0, 100.0, 0)
        task.config, task.device = LP2_CONFIG, 0
        task.start_time, task.end_time = start, start + dur
        s.devices[0].write_task(task)
        for got, want, name in zip(
                _windows(st), _windows(broadcast_state(export_state(s), 1)),
                ("t1", "t2", "valid")):
            np.testing.assert_allclose(got, want, rtol=1e-6, err_msg=name)
        avail.append(_avail(st))
    np.testing.assert_allclose(
        starts, [0.0, 0.0, LP2_CONFIG.padded_time], atol=1e-4)
    assert all(b < a for a, b in zip(avail, avail[1:])), avail


def test_segment_program_compiles_once():
    """``fleet_run`` over three segments of one shape compiles one
    segment program and runs it three times."""
    b, f = 3, 6
    p = FleetParams(n_devices=N_DEV, segment_frames=2, compact_every=3)
    runner = (engine._run_segment_checked(p) if sanitize.enabled()
              else engine._run_segment)
    wl = make_workload("uniform", b, f, N_DEV, seed=1)
    before = runner._cache_size()
    fleet_run(make_fleet(b, N_DEV), wl.values, wl.bw_scale, params=p)
    assert runner._cache_size() == before + 1
    fleet_run(make_fleet(b, N_DEV), wl.values, wl.bw_scale,
              params=dataclasses.replace(p))
    assert runner._cache_size() == before + 1
