#!/usr/bin/env python3
"""Run one cell of the fleet simulator's chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
warms up the cell's shapes with one ``run_sweep`` call, measures whole
calls until ``--seconds`` have passed, checks the window's answers
against the plain reference (``bench/fleetbench/reference.py``) and
prints one JSON object as the last line of standard output.  Without a
TPU, or with fewer chips than the cell asks for, it prints the reason
and exits 2 with no result.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from fleetbench.harness import NoChip, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START,
                          log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
