"""Calibration example: how far is the fleet abstraction from the DES?

Runs matched (seed, trace) points through the serial discrete-event
simulator and the batched fleet engine, prints a side-by-side rate table
per paper trace, and checks the deltas against the committed tolerance
bands in results/calib/baseline.json (the same gate CI enforces).

    PYTHONPATH=src python examples/calibrate.py [--frames 40] [--seeds 2]
"""

import argparse
import time

from repro.compile_cache import use_compile_cache
from repro.calib import CalibConfig, check_report, load_baseline, run_calibration
from repro.calib.harness import DELTA_KEYS, PAPER_TRACES


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=40)
    ap.add_argument("--seeds", type=int, default=2,
                    help="matched points per trace family")
    ap.add_argument("--congestion", type=float, default=0.0,
                    help="§VI.C burst duty-cycle for both engines")
    args = ap.parse_args()
    use_compile_cache()

    cfg = CalibConfig(scenarios=PAPER_TRACES,
                      congestion_levels=(args.congestion,),
                      n_seeds=args.seeds, n_frames=args.frames)
    print(f"calibrating {len(PAPER_TRACES)} trace families x {args.seeds} "
          f"matched seeds, {args.frames} frames each...")
    t0 = time.time()
    report = run_calibration(cfg)
    print(f"done in {time.time() - t0:.1f}s\n")

    for metric in DELTA_KEYS:
        hdr = (f"{metric:>24} | {'serial':>8} | {'fleet':>8} | {'delta':>8}")
        print(hdr)
        print("-" * len(hdr))
        for cell, point in sorted(report["cells"].items()):
            print(f"{cell:>24} | {point['serial'][metric]:>8.3f} | "
                  f"{point['fleet'][metric]:>8.3f} | "
                  f"{point['delta'][metric]:>+8.3f}")
        print()

    try:
        ok, failures = check_report(report, load_baseline())
    except FileNotFoundError:
        print("no committed baseline found — run "
              "`python -m benchmarks.bench_calib --rebaseline`")
        return
    if ok:
        print("within committed tolerance bands (results/calib/baseline.json)")
    else:
        print("OUTSIDE committed tolerance bands:")
        for f in failures:
            print(f"  {f}")


if __name__ == "__main__":
    main()
