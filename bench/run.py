#!/usr/bin/env python3
"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload mnist-n800.sync --seed 7 --seconds 10 \\
        --trace 0

Runs on the machine it is started on, on its TPU chips only: with no TPU,
or fewer chips than the cell asks for, it exits non-zero and prints no
result.  The last line of standard output is the result as one JSON
object; the last lines of standard error are the compared numbers, each
beside its limit.  ``--trace 1`` makes a separate run whose window is
recorded by the profiler, and reports the cell's per-layer metrics
instead of its end-to-end ones.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
