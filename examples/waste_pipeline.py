"""End-to-end paper scenario (§III/§V): deadline-constrained serving of the
waste-classification pipeline with REAL model execution.

Four workers sample conveyor-belt frames; stage-1 detection runs locally as
a high-priority task; recyclable detections spawn 1–4 low-priority
classification tasks that the RAS scheduler may offload to idle workers.
Both schedulers are run on the same trace for comparison.

    PYTHONPATH=src python examples/waste_pipeline.py [--frames 25]
"""

import argparse
import json

from repro.compile_cache import use_compile_cache
from repro.launch.serve import serve


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=25)
    ap.add_argument("--trace", default="weighted3")
    args = ap.parse_args()
    use_compile_cache()

    out = {}
    for sched in ("ras", "wps"):
        out[sched] = serve(
            arch="waste-pipeline",
            frames=args.frames,
            scheduler=sched,
            trace=args.trace,
            seed=7,
        )
        print(f"[{sched}] {json.dumps(out[sched])}")
    print(
        f"\ncompletion: RAS {out['ras']['completion_rate']:.3f} vs "
        f"WPS {out['wps']['completion_rate']:.3f} under {args.trace}"
    )
    print(
        "note: this example demonstrates scheduler+model INTEGRATION with"
        " real forward passes; scheduling latency is not charged to the"
        " wall clock here, which favours the exhaustive baseline.  The"
        " paper's accuracy-vs-performance comparison (latency, queueing,"
        " congestion) lives in the discrete-event simulator:"
        " `python -m benchmarks.run`."
    )


if __name__ == "__main__":
    main()
