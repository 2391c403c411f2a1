#!/usr/bin/env python3
"""Smoke test of the fleet simulator's main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the four-chip mesh phase

One chip, in order:

1. ``sweep``: ``run_sweep`` over 4 scenarios x 2 congestion levels x 2,048
   seeds at 95 frames (30 simulated minutes): 16,384 replicas in 4
   batches of 4,096 that share one compiled program.  The LP
   conservation residual must be 0 in every cell.
2. ``kernel_in_program``: the default backend resolves to the compiled
   Pallas placement kernel, and the B=4096 segment program holds it
   (``tpu_custom_call``).
3. ``oracle_kernel`` / ``oracle_ref``: one 4,096-replica batch through
   ``fleet_run`` with ``placement_backend="kernel"`` and ``"ref"``; every
   ``FleetStats`` counter and the whole final state must be bit-identical.
4. ``calib``: the calibration quick grid (paper traces, congestion 0, 2
   seeds, 40 frames) against the serial DES, within the committed bands
   of ``results/calib/baseline.json``.

Four chips: ``fleet_run`` with ``mesh_shards=4`` on B=4096 against the
unsharded run (bit-identical), with the carry, the workload and the
sweep's ``owner`` array checked to land on all four chips; then
``run_sweep(mesh_shards=4)`` against the host reduction of the unsharded
sweep.

Everything runs in this one process, which holds the chip(s).  Without a
TPU it prints the reason and exits 1 before running anything.  A failed
phase exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the lines before it give each phase's wall and compile seconds.  Rates
and times printed here are informational, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.calib import (  # noqa: E402
    CalibConfig, check_report, load_baseline, run_calibration,
)
from repro.calib.harness import PAPER_TRACES  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.fleet import (  # noqa: E402
    FleetParams, SweepConfig, fleet_mesh, fleet_run, make_fleet,
    make_workload, run_sweep,
)
from repro.fleet import mesh as _mesh  # noqa: E402
from repro.fleet.engine import _run_segment, initial_carry  # noqa: E402
from repro.kernels.placement.ops import resolve_backend  # noqa: E402

SCENARIOS = ("uniform", "weighted2", "poisson_burst", "mobility")
CONGESTION = (0.0, 0.3)
N_FRAMES = 95
BATCH = 4096
#: replicas per cell: 8 cells x 2,048 = 16,384 in four batches of BATCH
#: on one chip; 8 x 512 = one batch for the four-chip sweep
SWEEP_SEEDS = 2048
MESH_SWEEP_SEEDS = 512

#: JAX's compile-path events: tracing, lowering to MLIR, backend compile
#: (a persistent-cache read included).
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class PhaseClock:
    """Wall and compile seconds of each phase.  Compile time is the union
    of JAX's compile-path spans inside the phase, so the nested traces of
    inner jits count once."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        self.results: dict[str, dict] = {}
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))

    def _compile_seconds(self, t0: float, t1: float) -> float:
        total, reach = 0.0, t0
        for s, e in sorted(self.spans):
            s, e = max(s, reach), min(e, t1)
            if e > s:
                total += e - s
                reach = e
        return total

    @contextlib.contextmanager
    def __call__(self, name: str):
        print(f"[{name}] start", flush=True)
        t0 = time.time()
        yield
        t1 = time.time()
        wall, comp = t1 - t0, self._compile_seconds(t0, t1)
        self.results[name] = {"wall_s": wall, "compile_s": comp}
        print(f"[{name}] ok: wall {wall:.3f} s, compile {comp:.3f} s, "
              f"rest {wall - comp:.3f} s", flush=True)


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "not installed"


def _sweep_config(n_seeds: int):
    return SweepConfig(scenarios=SCENARIOS, congestion_levels=CONGESTION,
                       n_seeds=n_seeds, n_frames=N_FRAMES, batch_size=BATCH)


def _workload():
    return make_workload("poisson_burst", BATCH, N_FRAMES, 4, seed=1,
                         congestion=0.3)


def _run(params, wl):
    fleet = make_fleet(BATCH, params.n_devices,
                       requeue_slots=params.requeue_slots)
    return jax.block_until_ready(
        fleet_run(fleet, wl.values, wl.bw_scale, params=params)
    )


def _assert_trees_equal(a, b, what: str) -> None:
    la, lb = jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)
    check(len(la) == len(lb), f"{what}: tree structures differ")
    for (path, x), y in zip(la, lb):
        check(np.array_equal(np.asarray(x), np.asarray(y)),
              f"{what}: {jax.tree_util.keystr(path)} differs")


def phase_sweep(phase: PhaseClock) -> None:
    with phase("sweep"):
        out = run_sweep(_sweep_config(SWEEP_SEEDS))
    meta = out["_sweep"]
    total = len(meta["cells"]) * SWEEP_SEEDS
    check(meta["total_replicas"] == total and meta["batch_size"] == BATCH,
          f"sweep shape {meta}")
    for cell in meta["cells"]:
        s = out[cell]
        fc, lv = s["frame_completion_rate"], s["lp_violation_rate"]
        resid = s["conservation_residual"]["max_abs"]
        print(f"  {cell:<18s} replicas {s['replicas']}  "
              f"frame_completion_rate {fc['mean']} +- {fc['ci95']}  "
              f"lp_violation_rate {lv['mean']} +- {lv['ci95']}  "
              f"conservation_residual.max_abs {resid}", flush=True)
        check(s["replicas"] == SWEEP_SEEDS,
              f"{cell}: {s['replicas']} replicas")
        check(resid == 0, f"{cell}: conservation residual {resid}")
    t = phase.results["sweep"]
    print(f"  site-ticks/s outside compile: "
          f"{total * N_FRAMES / (t['wall_s'] - t['compile_s']):.1f}")


def phase_kernel_in_program(phase: PhaseClock) -> None:
    with phase("kernel_in_program"):
        p = FleetParams()
        check(resolve_backend(p.placement_backend) == (True, False),
              f"backend {p.placement_backend!r} does not resolve to the "
              f"compiled placement kernel")
        fleet = make_fleet(BATCH, p.n_devices, requeue_slots=p.requeue_slots)
        S = p.segment_frames
        text = _run_segment.lower(
            initial_carry(fleet),
            jax.ShapeDtypeStruct((S, BATCH, p.n_devices), jnp.int32),
            jax.ShapeDtypeStruct((S, BATCH), jnp.float32),
            jnp.int32(0), jnp.int32(N_FRAMES), params=p,
        ).as_text()
        # the jitted kernel lowers to one function that every placement
        # attempt of the tick calls
        check("tpu_custom_call" in text,
              "no tpu_custom_call in the segment program")
        print(f"  B={BATCH} segment program holds the placement kernel "
              f"(tpu_custom_call)")


def phase_oracle(phase: PhaseClock) -> None:
    wl = _workload()
    runs = {}
    for backend in ("kernel", "ref"):
        p = FleetParams(placement_backend=backend)
        with phase(f"oracle_{backend}"):
            runs[backend] = _run(p, wl)
        # the same call warm: its program is compiled now
        with phase(f"oracle_{backend}_warm"):
            _run(p, wl)
    (k_state, k_stats), (r_state, r_stats) = runs["kernel"], runs["ref"]
    _assert_trees_equal(k_stats, r_stats, "kernel vs ref FleetStats")
    _assert_trees_equal(k_state, r_state, "kernel vs ref final state")
    print(f"  kernel and ref bit-identical: {len(k_stats)} counters and "
          f"the final state over {BATCH} replicas x {N_FRAMES} frames")


def phase_calib(phase: PhaseClock) -> None:
    # the quick grid of benchmarks/bench_calib.py
    cfg = CalibConfig(scenarios=PAPER_TRACES, congestion_levels=(0.0,),
                      n_seeds=2, n_frames=40)
    with phase("calib"):
        report = run_calibration(cfg)
    for cell, point in sorted(report["cells"].items()):
        print(f"  {cell:<14s} max_abs_delta {point['max_abs_delta']}")
    ok, failures = check_report(report, load_baseline(
        os.path.join(ROOT, "results", "calib", "baseline.json")
    ))
    check(ok, "calibration outside the committed bands: "
          + "; ".join(failures))


def phase_four_chips(phase: PhaseClock) -> None:
    shards = 4
    devices = set(fleet_mesh(shards).devices.flat)
    check(len(devices) == shards, f"mesh spans {len(devices)} devices")

    def check_spread(leaf, batch_axis, what):
        shard_devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[batch_axis] for s in leaf.addressable_shards}
        check(shard_devs == devices and
              rows == {leaf.shape[batch_axis] // shards},
              f"{what} {leaf.shape} is not split over the {shards} chips: "
              f"devices {sorted(d.id for d in shard_devs)}, rows {rows}")

    # every array the engine and the sweep put on the mesh goes through
    # put_sharded: check each as it is placed (the carry is donated later)
    placed = []
    put_sharded = _mesh.put_sharded

    def checked_put(tree, mesh, batch_axis=0):
        out = put_sharded(tree, mesh, batch_axis)
        for leaf in jax.tree.leaves(out):
            check_spread(leaf, batch_axis, "placed array")
            placed.append(leaf.shape)
        return out

    wl = _workload()
    p = FleetParams()
    with phase("fleet_unsharded"):
        ref_state, ref_stats = _run(p, wl)
    _mesh.put_sharded = checked_put
    try:
        with phase("fleet_sharded"):
            state, stats = _run(dataclasses.replace(p, mesh_shards=shards),
                                wl)
        n_fleet = len(placed)
        cfg = _sweep_config(MESH_SWEEP_SEEDS)
        with phase("sweep_sharded"):
            out = run_sweep(dataclasses.replace(cfg, mesh_shards=shards))
    finally:
        _mesh.put_sharded = put_sharded
    _assert_trees_equal(stats, ref_stats, "sharded vs unsharded FleetStats")
    _assert_trees_equal(state, ref_state, "sharded vs unsharded state")
    # carry and workload (fleet_run), owner (run_sweep)
    check(n_fleet > 0 and len(placed) > n_fleet,
          "fleet_run or run_sweep placed nothing on the mesh")
    for leaf in jax.tree.leaves(stats):
        check_spread(leaf, 0, "FleetStats leaf")
    print(f"  {len(placed)} placed arrays and every FleetStats leaf split "
          f"{BATCH // shards} rows per chip over {shards} chips")

    with phase("sweep_unsharded"):
        ref = run_sweep(cfg)
    # counts and residuals exactly; means and CIs to the summaries'
    # 4-decimal rounding (the device accumulates moments in f32)
    tol = 1e-4 + 1e-9
    for cell in ref["_sweep"]["cells"]:
        a, b = out[cell], ref[cell]
        check(a["replicas"] == b["replicas"] == MESH_SWEEP_SEEDS,
              f"{cell}: replicas {a['replicas']} vs {b['replicas']}")
        check(a["conservation_residual"]["max_abs"] == 0
              and b["conservation_residual"]["max_abs"] == 0,
              f"{cell}: non-zero conservation residual")
        for key, val in b.items():
            if not isinstance(val, dict):
                continue
            for stat in ("mean", "ci95"):
                check(abs(a[key][stat] - val[stat]) <= tol,
                      f"{cell} {key}.{stat}: sharded {a[key][stat]} vs "
                      f"host {val[stat]}")
    print(f"  sharded sweep moments agree with the host reduction in "
          f"{len(ref['_sweep']['cells'])} cells")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter
    )
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              f"TPU; nothing run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s); nothing run", file=sys.stderr)
        return 1
    print(f"device {dev.device_kind!r} x{len(devices)}; jax {jax.__version__}"
          f", jaxlib {_version('jaxlib')}, libtpu {_version('libtpu')}",
          flush=True)

    print(f"compile cache: {use_compile_cache()}", flush=True)
    phase = PhaseClock()
    try:
        if args.chips == 4:
            phase_four_chips(phase)
        else:
            phase_sweep(phase)
            phase_kernel_in_program(phase)
            phase_oracle(phase)
            phase_calib(phase)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print("phases " + json.dumps(phase.results, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
