#!/usr/bin/env python3
"""Read the check's numbers for the program and for its control.

  python3 bench/control.py --workload <cell> --seconds <s> \
      --seeds 1,2,3 [--fault none|validator_off|answer_altered|no_verdict]

Runs the cell's window once per seed in one process (set-up paid once
per run, as in `run.py`) with the named fault planted in the timed path,
and prints each run's result line.  ``none`` gives the lower readings
the limits are set from, ``validator_off`` the control's.  The
benchmark's own runs (`run.py`) plant nothing.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="none")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import contextlib

    from benchkit.faults import FAULTS
    from benchkit.harness import run_cell
    plant = contextlib.nullcontext if args.fault == "none" \
        else FAULTS[args.fault]
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        print(f"== fault {args.fault} seed {seed}", flush=True)
        with plant():
            rc |= run_cell(ROOT, args.workload, seed, args.seconds, False,
                           t_process=time.perf_counter())
    return rc


if __name__ == "__main__":
    sys.exit(main())
