"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (warm-up, window, check) on a tiny cell on the CPU, with one fault
planted in the program: a step that returns its state unchanged, half
of the batch left out of the per-cell mean, a placement answer altered
where the kernel produces it, and (with two CPU devices, in a child
process) the exchange between chips left out of the sharded
reduction."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fleetbench.harness import run_cell  # noqa: E402
from fleetbench.spec import ROOT, Benchmark  # noqa: E402
from fleetbench.testing import tiny_root  # noqa: E402

SEED = 2**31 + 977


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return Benchmark(tiny_root(tmp_path_factory.mktemp("bench")))


def _run(bench, seconds=0.0):
    return run_cell("tiny4.tiny", SEED, seconds, False, time.time(),
                    bench=bench, require_tpu=False, log=lambda s: None)


def test_bench_sound_run_is_correct(bench):
    r = _run(bench)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 16
    assert set(r["metrics"]) == {"site_ticks_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


def test_bench_state_left_unchanged_is_caught(bench, monkeypatch):
    import repro.fleet.engine as engine

    monkeypatch.setattr(engine, "_run_segment",
                        lambda carry, *a, **k: carry)
    r = _run(bench)
    assert not r["correct"]
    assert r["checks"]["replica_mismatch"]["value"] == 1.0


def test_bench_half_batch_mean_is_caught(bench, monkeypatch):
    import repro.fleet.sweep as sweep
    from repro.fleet.metrics import FleetStats

    orig = sweep.summarize

    def half(stats, n_frames, *, rq_pending=None):
        n = len(stats.frames) // 2
        return orig(FleetStats(*(x[:n] for x in stats)), n_frames,
                    rq_pending=rq_pending[:n])

    monkeypatch.setattr(sweep, "summarize", half)
    r = _run(bench)
    assert not r["correct"]
    assert r["checks"]["summary_gap"]["value"] >= 1.0


def test_bench_altered_answer_is_caught(bench, monkeypatch):
    import jax
    import repro.fleet.engine as engine

    orig = engine.fused_place_op

    def late(*a, **k):
        out = list(orig(*a, **k))
        out[5] = out[5] + 1.0      # every placed task starts a second late
        return tuple(out)

    monkeypatch.setattr(engine, "fused_place_op", late)
    jax.clear_caches()
    try:
        r = _run(bench)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not r["correct"]
    assert r["checks"]["replica_mismatch"]["value"] > 0.5


CHILD = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, {bench_dir!r})
    import repro.fleet.sweep as sweep
    from fleetbench.harness import run_cell
    from fleetbench.spec import Benchmark
    if {fault!r}:
        orig = sweep.cell_moments
        def local(*a, axis_name=None, **k):   # no psum/pmax across chips
            return orig(*a, axis_name=None, **k)
        sweep.cell_moments = local
    r = run_cell("tiny4.tiny", {seed}, 0.0, False, time.time(),
                 bench=Benchmark({root!r}), require_tpu=False,
                 log=lambda s: None)
    print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
""")


@pytest.mark.parametrize("fault", [False, True])
def test_bench_exchange_left_out_is_caught(tmp_path, fault):
    root = tiny_root(tmp_path, mesh_shards=2)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    code = CHILD.format(bench_dir=HERE, fault=fault, seed=SEED,
                        root=str(root))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = __import__("json").loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not fault), r["checks"]
    if fault:
        assert r["checks"]["summary_gap"]["value"] >= 1.0
