"""Fleet-engine throughput: replicas/sec vs batch size, against the
serial discrete-event simulator looped one replica at a time.

The acceptance bar for the batched engine is >= 10x the serial DES at
batch 256 (same frame count, same uniform workload family).  Emits
BENCH_fleet.json with the full curve, reporting **compile time** (first
call, includes tracing + XLA) and **steady-state tick time** as separate
columns so compile-latency regressions are visible independently of
throughput.

As a CLI this doubles as the CI perf gate: ``--gate`` compares the
speedup-vs-serial at batch 256 against the committed BENCH_fleet.json
and exits non-zero on a >20% regression.  Speedup (not raw replicas/sec)
is gated because both engines run on the same machine, making the ratio
portable across CI hardware.

When more than one device is visible (a real accelerator mesh, or
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` emulation) the
bench also times the `shard_map` engine at batch 256, asserts its
counters are **bit-identical** to the unsharded run, and reports
``replicas_per_s_per_device`` plus ``efficiency_vs_unsharded`` — the
latter joins the ``--gate`` check (same >20% floor; the ratio is
portable across hosts the same way speedup-vs-serial is).

Extra CI modes:

``--mesh-smoke``
    B=64 two-scenario sharded sweep + counter-identity assert; writes
    results/bench/BENCH_fleet_mesh.json and exits non-zero on mismatch
    (the gating check of the `mesh` CI leg).

``--mega``
    The million-replica demonstration: a 4-cell scenario grid at 250k
    seeds/cell (1e6 replicas total) swept in one invocation on the
    8-way mesh, merged into BENCH_fleet.json as the ``mega`` row with
    wall-clock and replicas/sec-per-device.

    PYTHONPATH=src python -m benchmarks.bench_fleet --quick --gate
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

import jax

from benchmarks.common import RESULTS_DIR, csv_row, emit
from repro.compile_cache import use_compile_cache
from repro.fleet import (
    FleetParams, SweepConfig, fleet_run, make_fleet, make_workload,
    run_sweep,
)
from repro.obs.profile import PhaseTimer, span
from repro.sim.engine import ExperimentConfig, run_experiment

#: host-side phase breakdown artifact (see README "Observability").
PROFILE_PATH = os.path.join("results", "obs", "profile_fleet.json")

#: relative speedup loss at batch 256 that fails the ``--gate`` check.
GATE_REGRESSION = 0.20


def _time_fleet(batch: int, n_frames: int, params: FleetParams) -> dict:
    with span(f"bench/workload_b{batch}"):
        wl = make_workload("uniform", batch, n_frames, params.n_devices,
                           seed=0)
        fleet = make_fleet(batch, params.n_devices)
    t0 = time.perf_counter()
    with span(f"bench/first_call_b{batch}"):
        jax.block_until_ready(
            fleet_run(fleet, wl.values, wl.bw_scale, params=params)
        )
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with span(f"bench/steady_call_b{batch}"):
        jax.block_until_ready(
            fleet_run(fleet, wl.values, wl.bw_scale, params=params)
        )
    run_s = time.perf_counter() - t0
    return {
        "batch": batch,
        # first call = trace + XLA compile + one run; steady run subtracted
        # out so the column isolates compile latency
        "compile_s": round(max(first_s - run_s, 0.0), 3),
        "run_s": round(run_s, 4),
        "tick_us": round(run_s / n_frames * 1e6, 1),
        "replicas_per_s": round(batch / run_s, 2),
    }


def _assert_counters_match(a, b, ctx: str) -> None:
    """Bit-identity of every FleetStats counter array (the sharded
    engine's correctness contract — not a tolerance check)."""
    for f in a._fields:
        if not np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))):
            raise SystemExit(
                f"sharded/unsharded FleetStats mismatch in `{f}` ({ctx})"
            )


def _bench_shards() -> int:
    return min(8, jax.device_count())


def _time_sharded(batch: int, n_frames: int, params: FleetParams,
                  unsharded_rps: float) -> dict:
    """Time the shard_map engine at `batch` and hard-assert counter
    identity against a fresh unsharded run of the same workload."""
    shards = _bench_shards()
    sp = dataclasses.replace(params, mesh_shards=shards)
    wl = make_workload("uniform", batch, n_frames, params.n_devices, seed=0)
    fleet = make_fleet(batch, params.n_devices)
    with span(f"bench/sharded_first_call_b{batch}"):
        t0 = time.perf_counter()
        _, stats = jax.block_until_ready(
            fleet_run(fleet, wl.values, wl.bw_scale, params=sp)
        )
        first_s = time.perf_counter() - t0
    with span(f"bench/sharded_steady_call_b{batch}"):
        t0 = time.perf_counter()
        _, stats = jax.block_until_ready(
            fleet_run(fleet, wl.values, wl.bw_scale, params=sp)
        )
        run_s = time.perf_counter() - t0
    _, ref_stats = fleet_run(fleet, wl.values, wl.bw_scale, params=params)
    _assert_counters_match(ref_stats, stats, f"bench b={batch}")
    rps = batch / run_s
    return {
        "batch": batch,
        "shards": shards,
        "compile_s": round(max(first_s - run_s, 0.0), 3),
        "run_s": round(run_s, 4),
        "replicas_per_s": round(rps, 2),
        "replicas_per_s_per_device": round(rps / shards, 2),
        "efficiency_vs_unsharded": round(rps / unsharded_rps, 3),
        "counters_match": True,
    }


def _time_serial(n_frames: int, reps: int = 3) -> float:
    """Seconds per replica of the serial DES (median of `reps` runs)."""
    times = []
    for seed in range(reps):
        t0 = time.perf_counter()
        run_experiment(
            ExperimentConfig(trace="uniform", n_frames=n_frames, seed=seed)
        )
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def run(*, quick: bool = False, n_frames: int = 40) -> dict:
    batch_sizes = (256,) if quick else (32, 128, 256)
    params = FleetParams()

    timer = PhaseTimer()
    with timer, span("bench/serial_des"):
        serial_s = _time_serial(n_frames)
    serial_rps = 1.0 / serial_s
    csv_row("fleet_serial_des", serial_s * 1e6, "1_replica_per_process")

    curve = []
    with timer:
        for b in batch_sizes:
            r = _time_fleet(b, n_frames, params)
            r["speedup_vs_serial"] = round(
                r["replicas_per_s"] / serial_rps, 2
            )
            curve.append(r)
            csv_row(
                f"fleet_batched_b{b}", r["run_s"] / b * 1e6,
                f"{r['speedup_vs_serial']}x_serial_compile_{r['compile_s']}s",
            )
    sharded = None
    if jax.device_count() > 1:
        rps_256 = next(
            (r["replicas_per_s"] for r in curve if r["batch"] == 256),
            curve[-1]["replicas_per_s"],
        )
        with timer:
            sharded = _time_sharded(256, n_frames, params, rps_256)
        csv_row(
            "fleet_sharded_b256",
            sharded["run_s"] / 256 * 1e6,
            f"{sharded['shards']}shards_"
            f"{sharded['replicas_per_s_per_device']}rps_per_dev",
        )
    # per-phase host breakdown (includes fleet_run's internal
    # fleet/segment spans) alongside the headline curve
    timer.save(PROFILE_PATH, extra={
        "n_frames": n_frames, "batch_sizes": list(batch_sizes),
    })

    out = {
        "n_frames": n_frames,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "segment_frames": params.segment_frames,
        "compact_every": params.compact_every,
        "serial_des_s_per_replica": round(serial_s, 4),
        "serial_des_replicas_per_s": round(serial_rps, 2),
        "fleet": curve,
        "speedup_at_256": next(
            (r["speedup_vs_serial"] for r in curve if r["batch"] == 256), None
        ),
    }
    if sharded is not None:
        out["sharded"] = sharded
    out["meets_10x_bar"] = bool(
        out["speedup_at_256"] and out["speedup_at_256"] >= 10.0
    )
    # keep the committed mega row (refreshed only by explicit --mega runs)
    prior = _load_committed()
    if prior and "mega" in prior:
        out["mega"] = prior["mega"]
    emit("BENCH_fleet", out)
    return out


def _load_committed(path: str | None = None) -> dict | None:
    path = path or os.path.join(RESULTS_DIR, "BENCH_fleet.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def check_regression(out: dict, committed: dict | None) -> tuple[bool, str]:
    """Compare speedup-at-256 against the committed curve: a drop of more
    than ``GATE_REGRESSION`` fails (the committed file is refreshed by
    running the full bench and committing results/bench/BENCH_fleet.json).
    """
    if committed is None:
        return False, "no committed baseline (results/bench/BENCH_fleet.json)"
    base = committed.get("speedup_at_256")
    new = out.get("speedup_at_256")
    if not base or not new:
        return False, "speedup_at_256 missing from baseline or run"
    floor = round(base * (1.0 - GATE_REGRESSION), 2)
    ok = new >= floor
    msg = f"speedup_at_256 {new} vs committed {base} (floor {floor})"
    # sharded leg: gate parallel efficiency the same way, when both the
    # baseline and this run produced the sharded column (same shard count
    # so the ratio compares like with like)
    base_sh = committed.get("sharded")
    new_sh = out.get("sharded")
    if base_sh and new_sh and base_sh.get("shards") == new_sh.get("shards"):
        b_eff = base_sh.get("efficiency_vs_unsharded")
        n_eff = new_sh.get("efficiency_vs_unsharded")
        if b_eff and n_eff:
            sh_floor = round(b_eff * (1.0 - GATE_REGRESSION), 3)
            ok = ok and n_eff >= sh_floor
            msg += (f"; sharded efficiency {n_eff} vs committed {b_eff} "
                    f"(floor {sh_floor})")
    return ok, msg


def run_mesh_smoke() -> int:
    """The gating check of the CI `mesh` leg: a B=64 two-scenario sharded
    sweep plus a counter-identity assert, written to BENCH_fleet_mesh.json.
    Returns a process exit code (non-zero on any mismatch)."""
    shards = _bench_shards()
    n_frames = 16
    params = FleetParams()

    # counter identity on a fresh workload at the smoke batch
    sp = dataclasses.replace(params, mesh_shards=shards)
    wl = make_workload("uniform", 64, n_frames, params.n_devices, seed=0)
    fleet = make_fleet(64, params.n_devices)
    _, ref_stats = fleet_run(fleet, wl.values, wl.bw_scale, params=params)
    t0 = time.perf_counter()
    _, stats = jax.block_until_ready(
        fleet_run(fleet, wl.values, wl.bw_scale, params=sp)
    )
    wall_s = time.perf_counter() - t0
    _assert_counters_match(ref_stats, stats, "mesh smoke b=64")

    cfg = SweepConfig(
        scenarios=("uniform", "weighted2"), congestion_levels=(0.3,),
        n_seeds=64, n_frames=n_frames, batch_size=64, mesh_shards=shards,
    )
    t0 = time.perf_counter()
    sweep = run_sweep(cfg)
    sweep_s = time.perf_counter() - t0
    bad = [
        cell for cell, s in sweep.items()
        if not cell.startswith("_")
        and s["conservation_residual"]["max_abs"] != 0
    ]
    out = {
        "mode": "mesh-smoke",
        "shards": shards,
        "device_count": jax.device_count(),
        "counters_match": True,
        "fleet_run_wall_s": round(wall_s, 3),
        "sweep_wall_s": round(sweep_s, 3),
        "sweep": sweep,
        "conservation_violations": bad,
    }
    emit("BENCH_fleet_mesh", out)
    print(json.dumps({k: v for k, v in out.items() if k != "sweep"},
                     indent=1))
    if bad:
        print(f"# mesh smoke FAILED: nonzero conservation residual in {bad}")
        return 1
    print("# mesh smoke OK: sharded counters bit-identical, "
          "residual 0 in every cell")
    return 0


def run_mega() -> int:
    """The million-replica demonstration: 4 cells x 250k seeds swept in
    one invocation, merged into BENCH_fleet.json as the `mega` row."""
    shards = _bench_shards()
    n_frames, batch, n_seeds = 8, 2048, 250_000
    cfg = SweepConfig(
        scenarios=("uniform", "weighted2"),
        congestion_levels=(0.0, 0.3),
        n_seeds=n_seeds, n_frames=n_frames, batch_size=batch,
        mesh_shards=shards,
    )
    total = n_seeds * 4
    t0 = time.perf_counter()
    sweep = run_sweep(cfg)
    wall_s = time.perf_counter() - t0
    rps = total / wall_s
    bad = [
        cell for cell, s in sweep.items()
        if not cell.startswith("_")
        and s["conservation_residual"]["max_abs"] != 0
    ]
    mega = {
        "total_replicas": total,
        "cells": sweep["_sweep"]["cells"],
        "n_frames": n_frames,
        "batch_size": sweep["_sweep"]["batch_size"],
        "shards": shards,
        "wall_s": round(wall_s, 1),
        "replicas_per_s": round(rps, 1),
        "replicas_per_s_per_device": round(rps / shards, 1),
        "conservation_violations": bad,
    }
    committed = _load_committed() or {}
    committed["mega"] = mega
    emit("BENCH_fleet", committed)
    emit("BENCH_fleet_mega_sweep", sweep)
    print(json.dumps(mega, indent=1))
    return 1 if bad else 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="batch 256 only (CI mode)")
    ap.add_argument("--gate", action="store_true",
                    help="fail on >20%% speedup regression vs the "
                         "committed BENCH_fleet.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default committed BENCH_fleet)")
    ap.add_argument("--mesh-smoke", action="store_true",
                    help="sharded sweep smoke + counter-identity assert "
                         "(the CI mesh leg); writes BENCH_fleet_mesh.json")
    ap.add_argument("--mega", action="store_true",
                    help="one-invocation million-replica sharded sweep; "
                         "merges the `mega` row into BENCH_fleet.json")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.mesh_smoke:
        return run_mesh_smoke()
    if args.mega:
        return run_mega()
    # load the committed baseline BEFORE the run overwrites it via emit()
    base_path = args.baseline or os.path.join(RESULTS_DIR,
                                              "BENCH_fleet.json")
    try:
        committed = json.load(open(base_path))
    except FileNotFoundError:
        committed = None
    out = run(quick=args.quick)
    print(json.dumps(out, indent=1))
    if not args.gate:
        return 0
    ok, msg = check_regression(out, committed)
    print(f"# fleet perf gate {'OK' if ok else 'FAILED'}: {msg}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
