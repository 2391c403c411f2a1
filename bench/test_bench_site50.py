"""The plain reference against ``run_sweep`` on the CPU for the site of
``site50`` (``configs/site50.json``) at a size a test run holds: 8
sites, 20 frames, weighted1 at congestion 0.3, with 4, 8 and the
configuration's 50 devices on the one link.  Per-replica counters,
final state and per-cell summaries agree, and at 50 devices preemption,
re-queue, offload and compaction all happen."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fleetbench import compare  # noqa: E402
from fleetbench import reference as R  # noqa: E402

N_SITES, N_FRAMES, SEED = 8, 20, 11
SCENARIO, CONGESTION = "weighted1", 0.3


def _config(n_devices):
    with open(os.path.join(HERE, "configs", "site50.json")) as f:
        conf = json.load(f)
    conf["site"]["n_frames"] = N_FRAMES
    conf["site"]["n_devices"] = n_devices
    return conf


@pytest.fixture(scope="module", params=[4, 8, 50],
                ids=["dev4", "dev8", "site50"])
def runs(request):
    """One tiny sweep through the program, its per-batch outputs kept,
    and the reference's run of the same inputs."""
    import repro.fleet.sweep as sweep
    from repro.fleet import FleetParams, SweepConfig

    conf = _config(request.param)
    site = conf["site"]
    kept = []
    orig = sweep.fleet_run

    def keep(fleet, values, bw, *, params):
        out = orig(fleet, values, bw, params=params)
        kept.append(out)
        return out

    sweep.fleet_run = keep
    try:
        out = sweep.run_sweep(SweepConfig(
            scenarios=(SCENARIO,), congestion_levels=(CONGESTION,),
            n_seeds=N_SITES, n_frames=N_FRAMES, n_devices=site["n_devices"],
            batch_size=N_SITES, base_seed=SEED,
            params=FleetParams(n_devices=site["n_devices"]),
        ))
    finally:
        sweep.fleet_run = orig
    (state, stats), = kept
    values, bw = R.make_inputs(SCENARIO, N_SITES, N_FRAMES,
                               site["n_devices"], SEED, CONGESTION,
                               site["congestion_residual"])
    ref = R.SiteModel(conf).run(values, bw)
    prog = {k: np.asarray(getattr(stats, k))
            for k in R.INT_COUNTERS + R.FLOAT_COUNTERS}
    prog["rq_pending"] = np.asarray(state.rq_valid).sum(axis=1)
    for k, path in compare.STATE_FIELDS.items():
        x = state
        for p in path:
            x = getattr(x, p)
        prog[k] = np.asarray(x)
    return conf, out, prog, ref


def test_site_reference_exercises_preemption_requeue_offload(runs):
    conf, _, _, ref = runs
    if conf["site"]["n_devices"] == 50:
        for k in ("hp_preempted", "lp_requeued", "missed_by_preemption",
                  "lp_offloaded", "lp_four_core"):
            assert ref[k].sum() > 0, k
    # the 20 frames cross two compaction ticks
    assert N_FRAMES >= 2 * conf["engine"]["compact_every"]


def test_site_reference_agrees_per_replica(runs):
    _, _, prog, ref = runs
    for k in R.INT_COUNTERS + ("rq_pending",):
        np.testing.assert_array_equal(prog[k], ref[k], err_msg=k)
    exact_bad, rel = compare.replica_gaps(prog, ref)
    assert not exact_bad.any()
    # float32 rounding only (XLA may contract a multiply-add)
    assert rel.max() <= compare.TIME_RTOL


def test_site_reference_agrees_per_cell(runs):
    conf, out, prog, _ = runs
    ref = R.summarize(prog, N_FRAMES, conf["site"]["frame_period_s"])
    # the program rounds its summaries to 4 decimals
    assert compare.summary_gap(out[f"{SCENARIO}@{CONGESTION:g}"],
                               ref) <= 5.01e-5
    assert ref["conservation_residual"]["max_abs"] == 0
