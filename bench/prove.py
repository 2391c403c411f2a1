#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/prove.py --workload <cell> --first-seed <n> --seeds 12 \
        --control-seeds 3 --seconds <s>

In one process (set-up once): for each seed, a short window of the
cell's own calls through the timed path, then the numbers that
``bench/run.py`` compares, for the program and, on the first
``--control-seeds`` seeds, for the control: the reference put in the
program's place in bfloat16.  One JSON line per reading on standard
output.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from fleetbench.harness import NoChip, Session, check, collect  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window per seed; 0 makes one call")
    args = ap.parse_args(argv)
    try:
        session = Session(args.workload, log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"prove: {e}; nothing run", file=sys.stderr)
        return 2
    with session as s:
        s.warm_up(args.first_seed)
        for i in range(args.seeds):
            seed = args.first_seed + i
            w = s.window(seed, args.seconds, False)
            host = collect(s.cell, w.calls, seed)
            failed_calls = sum(c.error is not None for c in w.calls)
            runs = [("program", False)]
            if i < args.control_seeds:
                runs.append(("control_bf16", True))
            for what, control in runs:
                t = time.perf_counter()
                numbers, failed, notes = check(s.cell, host, failed_calls,
                                               control=control)
                notes["check_s"] = time.perf_counter() - t
                print(json.dumps({"workload": args.workload, "seed": seed,
                                  "what": what, "calls": len(w.calls),
                                  "numbers": numbers, "notes": notes}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
