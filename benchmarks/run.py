"""Benchmark harness entry point — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark) and
writes full JSON to results/bench/ (plus results/calib/ for the
fleet-vs-serial calibration report).

    PYTHONPATH=src python -m benchmarks.run [--quick]
    PYTHONPATH=src python -m benchmarks.run --list   # enumerate benches
    PYTHONPATH=src python -m benchmarks.run --only fleet --only calib
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Callable

from repro.compile_cache import use_compile_cache


@dataclasses.dataclass(frozen=True)
class BenchSpec:
    """A registered benchmark: lazy import keeps --list instant."""

    name: str
    description: str
    run: Callable[[argparse.Namespace], dict]


def _completion(a):
    from benchmarks import bench_completion
    return bench_completion.run(n_frames=a.n_frames, seeds=a.seeds)


def _latency(a):
    from benchmarks import bench_latency
    return bench_latency.run(n_frames=a.n_frames)


def _bw_interval(a):
    from benchmarks import bench_bw_interval
    return bench_bw_interval.run(n_frames=a.n_frames, seeds=a.seeds)


def _congestion(a):
    from benchmarks import bench_congestion
    return bench_congestion.run(n_frames=a.n_frames, seeds=a.seeds)


def _fleet(a):
    from benchmarks import bench_fleet
    return bench_fleet.run(quick=a.quick)


def _calib(a):
    from benchmarks import bench_calib
    return bench_calib.run(quick=a.quick)


def _roofline(a):
    from benchmarks import roofline
    return roofline.run() or {}


def _analysis(a):
    from benchmarks import bench_analysis
    return bench_analysis.run()


def _obs(a):
    from benchmarks import bench_obs
    return bench_obs.run(quick=a.quick)


#: Execution order matters: paper figures first, then kernels/fleet/calib.
REGISTRY: tuple[BenchSpec, ...] = (
    BenchSpec("completion", "Fig. 4 frame-completion vs trace family "
              "(RAS / WPS / hybrid)", _completion),
    BenchSpec("latency", "Fig. 5 scheduling-latency breakdown by scenario",
              _latency),
    BenchSpec("bw_interval", "Fig. 7 completion vs bandwidth-probe interval",
              _bw_interval),
    BenchSpec("congestion", "Fig. 8 completion under §VI.C link congestion",
              _congestion),
    BenchSpec("fleet", "batched fleet engine replicas/sec vs serial DES",
              _fleet),
    BenchSpec("calib", "fleet-vs-serial calibration deltas + tolerance gate",
              _calib),
    BenchSpec("roofline", "HLO FLOP/byte roofline of the model zoo",
              _roofline),
    BenchSpec("analysis", "Pallas geometry checker + jaxlint gate "
              "(REPRO_ANALYSIS_FIXTURE seeds violations)", _analysis),
    BenchSpec("obs", "record/export/validate observability smoke "
              "(fleet telemetry + serial event log -> Perfetto)", _obs),
)

#: Benches whose result dict carries a ``paper_checks`` table.
PAPER_CHECK_BENCHES = {"completion": "fig4", "latency": "fig5",
                      "bw_interval": "fig7", "congestion": "fig8"}


def list_benches() -> None:
    width = max(len(b.name) for b in REGISTRY)
    for b in REGISTRY:
        print(f"{b.name:<{width}}  {b.description}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer seeds/frames (CI mode)")
    ap.add_argument("--list", action="store_true",
                    help="enumerate registered benchmarks and exit")
    ap.add_argument("--only", action="append", metavar="NAME",
                    help="run only the named benchmark(s); repeatable. "
                         "Paper-claim aggregation covers what actually ran.")
    args = ap.parse_args()
    if args.list:
        list_benches()
        return
    use_compile_cache()
    selected = REGISTRY
    if args.only:
        known = {b.name for b in REGISTRY}
        unknown = sorted(set(args.only) - known)
        if unknown:
            ap.error(f"unknown benchmark(s): {', '.join(unknown)} "
                     f"(see --list)")
        selected = tuple(b for b in REGISTRY if b.name in set(args.only))
    args.n_frames = 40 if args.quick else 95
    args.seeds = (7,) if args.quick else (7, 11, 23)

    print("name,us_per_call,derived")
    t0 = time.time()
    results = {}
    for spec in selected:
        results[spec.name] = spec.run(args)

    all_checks = {}
    for bench, fig in PAPER_CHECK_BENCHES.items():
        if bench not in results:
            continue
        for k, v in results[bench]["paper_checks"].items():
            all_checks[f"{fig}.{k}"] = bool(v)
    if "fleet" in results:
        all_checks["fleet.speedup_10x_at_b256"] = bool(
            results["fleet"]["meets_10x_bar"]
        )
    if "calib" in results:
        all_checks["calib.within_tolerance"] = bool(
            results["calib"]["gate_ok"]
        )
    if "analysis" in results:
        all_checks["analysis.clean"] = bool(results["analysis"]["ok"])
    if "obs" in results:
        all_checks["obs.trace_valid"] = bool(results["obs"]["ok"])
    n_ok = sum(all_checks.values())
    print(f"# paper-claim checks: {n_ok}/{len(all_checks)} passed "
          f"({time.time() - t0:.1f}s total)")
    failed = [k for k, v in all_checks.items() if not v]
    if failed:
        print("# FAILED:", ", ".join(failed))
    # subset runs (--only) must not clobber the full paper_checks table
    if not args.only:
        os.makedirs("results/bench", exist_ok=True)
        json.dump(all_checks, open("results/bench/paper_checks.json", "w"),
                  indent=1)
    # the static-analysis gate is hard: violations fail the invocation
    # (the other benches stay report-only; calib has its own CI gate)
    if not all_checks.get("analysis.clean", True):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
