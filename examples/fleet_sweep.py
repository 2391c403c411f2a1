"""Fleet sweep example: a 256-replica seed x congestion Monte-Carlo run.

Four congestion duty-cycles x 64 seeds = 256 independent replicas of the
paper's uniform-trace experiment (SSVI.C's Fig. 4/8 axes), advanced as
ONE jitted `lax.scan` — no Python loop over replicas — then reduced to a
Fig.-4-style completion table with 95% confidence intervals.

    PYTHONPATH=src python examples/fleet_sweep.py [--frames 95]
"""

import argparse
import time

from repro.compile_cache import use_compile_cache
from repro.fleet import SweepConfig, run_sweep


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=95,
                    help="frame periods per replica (95 = 30 sim-minutes)")
    ap.add_argument("--seeds", type=int, default=64,
                    help="replicas per congestion level")
    args = ap.parse_args()
    use_compile_cache()

    levels = (0.0, 0.2, 0.4, 0.6)
    cfg = SweepConfig(
        scenarios=("uniform",),
        congestion_levels=levels,
        n_seeds=args.seeds,
        n_frames=args.frames,
        batch_size=args.seeds * len(levels),   # the whole grid in one scan
    )
    total = args.seeds * len(levels)
    print(f"sweeping {total} replicas ({len(levels)} congestion levels x "
          f"{args.seeds} seeds, {args.frames} frames each) in one batch...")
    t0 = time.time()
    out = run_sweep(cfg)
    dt = time.time() - t0
    print(f"done in {dt:.1f}s ({total / dt:.1f} replicas/s incl. compile)\n")

    hdr = (f"{'congestion':>10} | {'frame completion':>20} | "
           f"{'LP violations':>17} | {'offloaded':>13} | {'LP/s':>11}")
    print(hdr)
    print("-" * len(hdr))
    for lv in levels:
        s = out[f"uniform@{lv:g}"]
        fc = s["frame_completion_rate"]
        vi = s["lp_violation_rate"]
        of = s["lp_offload_fraction"]
        th = s["lp_throughput_per_s"]
        print(f"{lv:>10.1f} | {fc['mean']:>11.3f} ±{fc['ci95']:.3f} | "
              f"{vi['mean']:>8.3f} ±{vi['ci95']:.3f} | "
              f"{of['mean']:>5.3f} ±{of['ci95']:.3f} | "
              f"{th['mean']:>4.2f} ±{th['ci95']:.2f}")
    print("\n(95% CIs over seeds; congestion = link-saturating burst "
          "duty-cycle, SSVI.C)")


if __name__ == "__main__":
    main()
