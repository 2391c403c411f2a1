"""Calibration subsystem tests: B=1 fleet-vs-serial equivalence on every
paper trace (gated by the committed tolerance file), report/gate
plumbing, and the bench registry.

The module fixture runs the whole paper-trace grid at B=1 / 40 frames, so
every fleet invocation here shares one compiled engine signature.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.calib import (
    CalibConfig,
    check_report,
    load_baseline,
    run_calibration,
    write_baseline,
)
from repro.calib.harness import DELTA_KEYS, PAPER_TRACES, fleet_view
from repro.sim.engine import ExperimentConfig, run_experiment

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, "results", "calib", "baseline.json")
N_FRAMES = 40


@pytest.fixture(scope="module")
def calib_report():
    cfg = CalibConfig(scenarios=PAPER_TRACES, congestion_levels=(0.0,),
                      n_seeds=1, n_frames=N_FRAMES)
    return run_calibration(cfg)


def test_report_structure(calib_report):
    assert set(calib_report["cells"]) == {f"{t}@0" for t in PAPER_TRACES}
    for point in calib_report["cells"].values():
        assert set(point["delta"]) == set(DELTA_KEYS)
        for side in ("serial", "fleet"):
            for k in DELTA_KEYS:
                assert k in point[side]
        assert point["max_abs_delta"] >= 0


def test_b1_equivalence_within_committed_tolerance(calib_report):
    """Acceptance: at B=1 the fleet engine with victim re-queue matches
    the serial DES within the committed tolerance on ALL paper traces."""
    ok, failures = check_report(calib_report, load_baseline(BASELINE))
    assert ok, failures


def test_gate_trips_when_tolerance_artificially_exceeded(calib_report):
    """Pushing any delta past an artificially zeroed tolerance must fail
    the gate — the CI regression check is not a no-op."""
    zero = {"tolerances": {k: 0.0 for k in DELTA_KEYS}}
    ok, failures = check_report(calib_report, zero)
    assert not ok
    # the preemption-model abstraction always leaves a non-zero residual
    assert any("preemption_rate" in f for f in failures)


def test_gate_overrides_widen_specific_cells(calib_report):
    zero = {"tolerances": {k: 0.0 for k in DELTA_KEYS},
            "overrides": {"@0": {k: 1.0 for k in DELTA_KEYS}}}
    ok, failures = check_report(calib_report, zero)
    assert ok, failures  # every cell here is @0, all widened to 1.0


def test_write_baseline_roundtrip(tmp_path, calib_report):
    path = str(tmp_path / "baseline.json")
    base = write_baseline(calib_report, path)
    assert set(base["tolerances"]) == set(DELTA_KEYS)
    ok, failures = check_report(calib_report, load_baseline(path))
    assert ok, failures  # tolerances derived from a report must admit it


def test_serial_calib_view_keys_and_ranges():
    m = run_experiment(ExperimentConfig(trace="uniform", n_frames=20, seed=3))
    view = m.calib_view()
    for k in DELTA_KEYS:
        assert k in view
        assert 0.0 <= view[k] <= 1.0  # every gated metric is a rate
    assert view["lp_placed_rate"] >= view["lp_completion_rate"]


def test_fleet_view_matches_stats(calib_report):
    # fleet_view is exercised through the fixture; spot-check its algebra
    # on a trivial all-zero stats pytree
    from repro.fleet.metrics import init_stats

    view = fleet_view(init_stats(3))
    assert view["frames"] == 0
    assert view["frame_completion_rate"] == 0.0
    assert view["preemption_rate"] == 0.0


def test_unknown_scenario_rejected():
    with pytest.raises(ValueError, match="paper trace"):
        run_calibration(CalibConfig(scenarios=("poisson_burst",),
                                    n_seeds=1, n_frames=4))


def test_bench_registry_list_flag():
    """`benchmarks.run --list` enumerates the registry without importing
    (or running) any bench module."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--list"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO_ROOT, "src")},
    )
    assert out.returncode == 0, out.stderr
    names = [line.split()[0] for line in out.stdout.strip().splitlines()]
    for expected in ("completion", "fleet", "calib", "roofline"):
        assert expected in names
