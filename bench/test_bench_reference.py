"""The plain reference against ``run_sweep`` on the CPU, at a size a test
run holds: per-replica counters, final state and per-cell summaries."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fleetbench import compare  # noqa: E402
from fleetbench import reference as R  # noqa: E402

N_SITES, N_FRAMES, SEED = 8, 20, 7


def _config(n_devices=4):
    """The paper site at ``N_FRAMES`` frames, with ``n_devices`` on the
    link (8 drives the reference's per-device loops past the testbed's
    4)."""
    with open(os.path.join(HERE, "configs", "paper_site.json")) as f:
        conf = json.load(f)
    conf["site"]["n_frames"] = N_FRAMES
    conf["site"]["n_devices"] = n_devices
    return conf


@pytest.fixture(scope="module", params=[4, 8],
                ids=["paper_site", "paper_site_dev8"])
def runs(request):
    """One tiny sweep through the program, its per-batch outputs kept."""
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
            scenarios=("weighted4",), congestion_levels=(0.3,),
            n_seeds=N_SITES, n_frames=N_FRAMES, n_devices=site["n_devices"],
            batch_size=N_SITES, base_seed=SEED,
            params=FleetParams(n_devices=site["n_devices"]),
        ))
    finally:
        sweep.fleet_run = orig
    (state, stats), = kept
    values, bw = R.make_inputs("weighted4", N_SITES, N_FRAMES,
                               site["n_devices"], SEED, 0.3,
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


def test_bench_reference_exercises_every_phase(runs):
    _, _, _, ref = runs
    # preemption, re-queue and 4-core widening all happen, and the 20
    # frames cross two compaction ticks
    for k in ("hp_preempted", "lp_requeued", "missed_by_preemption",
              "lp_four_core", "lp_offloaded", "hp_failed"):
        assert ref[k].sum() > 0, k
    assert N_FRAMES >= 2 * _config()["engine"]["compact_every"]


def test_bench_reference_agrees_per_replica(runs):
    _, _, prog, ref = runs
    for k in R.INT_COUNTERS + ("rq_pending",):
        np.testing.assert_array_equal(prog[k], ref[k], err_msg=k)
    exact_bad, rel = compare.replica_gaps(prog, ref)
    assert not exact_bad.any()
    # float32 rounding only (XLA may contract a multiply-add)
    assert rel.max() <= compare.TIME_RTOL


def test_bench_reference_agrees_per_cell(runs):
    conf, out, prog, _ = runs
    ref = R.summarize(prog, N_FRAMES, conf["site"]["frame_period_s"])
    # the program rounds its summaries to 4 decimals
    assert compare.summary_gap(out["weighted4@0.3"], ref) <= 5.01e-5
    assert ref["conservation_residual"]["max_abs"] == 0


def test_bench_reference_inputs_are_the_sweep_stream():
    """Own draw, same stream as the program's scenario generator."""
    from repro.fleet.scenarios import make_workload

    for scen, cong in (("uniform", 0.3), ("weighted1", 0.0),
                       ("weighted4", 0.3)):
        wl = make_workload(scen, 16, 12, 8, seed=123, congestion=cong)
        v, bw = R.make_inputs(scen, 16, 12, 8, 123, cong, 0.2)
        np.testing.assert_array_equal(wl.values, v)
        np.testing.assert_array_equal(wl.bw_scale, bw)


def test_bench_reference_lower_precision_disagrees():
    """The control: the reference in bfloat16 fails the comparison."""
    import ml_dtypes

    conf = _config()
    v, bw = R.make_inputs("weighted4", 8, N_FRAMES, 4, 3, 0.3, 0.2)
    ref = R.SiteModel(conf).run(v, bw)
    low = R.SiteModel(conf, ml_dtypes.bfloat16).run(v, bw)
    share, _ = compare.replica_mismatch(low, ref)
    assert share > compare.LIMITS["replica_mismatch"]
    counters = {k: ref[k] for k in R.INT_COUNTERS + R.FLOAT_COUNTERS
                + ("rq_pending",)}
    full = R.summarize(counters, N_FRAMES, 18.86)
    low_summary = R.summarize(counters, N_FRAMES, 18.86, dtype=ml_dtypes.bfloat16)
    gap = compare.summary_gap(low_summary, full)
    # wider than the program's 4-decimal rounding; at a cell's 4,096
    # replicas it reads 0.9 to 3 (PERF.md), at these 8 it stays under the
    # limit, so here the control fails by its replicas
    assert gap > 5.01e-5
    correct, _ = compare.judge({"replica_mismatch": share, "summary_gap": gap,
                                "residual_failures": 0})
    assert not correct
