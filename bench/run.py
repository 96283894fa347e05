#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic mix
and metrics are found by name from ``BENCHMARK.json``.  Set-up (imports,
device start, compile-cache loads, warm-up) is timed as ``setup_s``;
then requests are sent for ``--seconds`` and every verdict is checked
against the plain reference.  The last stdout line is one JSON object;
the numbers the check compared, each beside its limit, are the last
lines of stderr.  Exits non-zero, printing no result, when the first
device is not a TPU or there are fewer chips than the cell asks for.
``--trace 1`` reports the per-layer metrics from a profiled run.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from benchkit.harness import run_cell
    return run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_process=T_PROCESS,
                    keep_trace=args.keep_trace)


if __name__ == "__main__":
    sys.exit(main())
