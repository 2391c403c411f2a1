"""Checkify sanitizer tests (REPRO_SANITIZE=1, repro.analysis.sanitize):

- the sanitized fleet scan is bit-identical to the default build (the
  checks are traced in, the arithmetic is untouched);
- corrupted scheduler state trips a *readable* checkify error naming the
  violated invariant ("window order ...") instead of silently running,
  and a clean fleet runs every tick's checks, availability conservation
  among them, without a trip;
- the B=1 fleet-vs-serial calibration equivalence still holds with every
  invariant armed, so the whole §IV pipeline is invariant-clean
  end-to-end.

The sanitize switch is read per call, so monkeypatch.setenv is enough to
flip modes inside one process.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import checkify
from jax.experimental.checkify import JaxRuntimeError

from repro.analysis import sanitize
from repro.calib import CalibConfig, check_report, load_baseline, run_calibration
from repro.calib.harness import PAPER_TRACES
from repro.fleet import FleetParams, fleet_run, make_fleet, make_workload
from repro.fleet import engine
from repro.fleet.engine import HP_IDX, LP2_IDX, LP4_IDX

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, "results", "calib", "baseline.json")

B, F, DEV = 4, 6, 4
PARAMS = FleetParams(n_devices=DEV, segment_frames=3)


def _corrupt(st, cfg=HP_IDX):
    """Give one valid window of config list ``cfg`` t1 > t2 — the
    signature of a racy write."""
    at = (0, 0, cfg, 0, 0)
    return st._replace(
        win_t1=st.win_t1.at[at].set(9.0),
        win_t2=st.win_t2.at[at].set(1.0),
        win_valid=st.win_valid.at[at].set(True),
    )


def test_enabled_reads_env(monkeypatch):
    monkeypatch.delenv(sanitize.ENV_VAR, raising=False)
    assert not sanitize.enabled()
    monkeypatch.setenv(sanitize.ENV_VAR, "0")
    assert not sanitize.enabled()
    monkeypatch.setenv(sanitize.ENV_VAR, "1")
    assert sanitize.enabled()


# ---------------------------------------------------------------------------
# sanitized == unsanitized (bit-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trace,congestion", [
    ("uniform", 0.0), ("weighted1", 0.0), ("weighted4", 0.3),
])
def test_fleet_run_equivalent_under_sanitize(monkeypatch, trace, congestion):
    wl = make_workload(trace, B, F, DEV, seed=0, congestion=congestion)
    monkeypatch.delenv(sanitize.ENV_VAR, raising=False)
    out0, stats0 = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                             params=PARAMS)
    monkeypatch.setenv(sanitize.ENV_VAR, "1")
    out1, stats1 = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                             params=PARAMS)
    for a, b in zip(jax.tree_util.tree_leaves(stats0),
                    jax.tree_util.tree_leaves(stats1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(out0.sched.win_t1),
                                  np.asarray(out1.sched.win_t1))
    np.testing.assert_array_equal(np.asarray(out0.sched.win_valid),
                                  np.asarray(out1.sched.win_valid))


# ---------------------------------------------------------------------------
# corrupted state trips readably
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [HP_IDX, LP2_IDX, LP4_IDX],
                         ids=["hp", "lp2", "lp4"])
def test_corrupted_window_order_trips_fleet(monkeypatch, cfg):
    monkeypatch.setenv(sanitize.ENV_VAR, "1")
    fleet = make_fleet(B, DEV)
    fleet = fleet._replace(sched=_corrupt(fleet.sched, cfg))
    wl = make_workload("uniform", B, F, DEV, seed=0)
    with pytest.raises(JaxRuntimeError, match="window order"):
        fleet_run(fleet, wl.values, wl.bw_scale, params=PARAMS)


def test_clean_fleet_does_not_trip(monkeypatch):
    """A sound fleet under a trace that preempts, re-queues and fills
    every slot passes every tick's checks, availability conservation
    among them, and still places work."""
    monkeypatch.setenv(sanitize.ENV_VAR, "1")
    wl = make_workload("weighted4", B, F, DEV, seed=3, congestion=0.3)
    _, stats = fleet_run(make_fleet(B, DEV), wl.values, wl.bw_scale,
                         params=PARAMS)
    assert np.asarray(stats.hp_completed).sum() > 0
    assert np.asarray(stats.lp_completed).sum() > 0


def test_manufactured_availability_trips_fleet(monkeypatch):
    """An HP commit that hands back 5 s before every window it trims
    trips the tick's availability-conservation check.  The trace
    releases HP tasks only, so no LP commit hides the gain."""
    real = engine.commit_device_rows

    def leaky(t1, t2, valid, min_dur, cfg, s, e, do):
        nt1, nt2, nv, n_drop, t_drop = real(t1, t2, valid, min_dur, cfg,
                                            s, e, do)
        grow = do[:, None, None, None] & nv
        return jnp.where(grow, nt1 - 5.0, nt1), nt2, nv, n_drop, t_drop

    wl = make_workload("uniform", B, F, DEV, seed=0)
    args = (engine.initial_carry(make_fleet(B, DEV)),
            jnp.zeros(wl.values.shape, jnp.int32),
            jnp.asarray(wl.bw_scale, jnp.float32), jnp.int32(0),
            jnp.int32(F))
    # a fresh program each time: the fleet's cached runners never see
    # the leaky commit
    checked = lambda: jax.jit(checkify.checkify(
        functools.partial(engine._segment_impl, params=PARAMS,
                          sanitize=True), errors=checkify.user_checks))
    err, _ = checked()(*args)
    assert err.get() is None
    monkeypatch.setattr(engine, "commit_device_rows", leaky)
    err, _ = checked()(*args)
    with pytest.raises(JaxRuntimeError, match="availability increased"):
        err.throw()


# ---------------------------------------------------------------------------
# B=1 fleet-vs-serial equivalence with every invariant armed
# ---------------------------------------------------------------------------

def test_b1_calibration_holds_under_sanitize(monkeypatch):
    """The committed fleet-vs-serial tolerance still gates when the whole
    fleet scan runs checkified — and no invariant trips along the way."""
    monkeypatch.setenv(sanitize.ENV_VAR, "1")
    cfg = CalibConfig(scenarios=(PAPER_TRACES[0],),
                      congestion_levels=(0.0,), n_seeds=1, n_frames=40)
    report = run_calibration(cfg)
    ok, failures = check_report(report, load_baseline(BASELINE))
    assert ok, failures
