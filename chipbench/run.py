"""One run of one cell: ``python chipbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

A new process each time. Touches no JAX before the arguments are parsed,
fails without a TPU (non-zero, no result), and prints the one JSON result
object as the last line of standard output.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()      # set-up is counted from here

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        parser.error('--seed must be >= 0 and --seconds > 0')
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench import harness
    line = harness.run(args.workload, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), started=STARTED)
    print(line, flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
