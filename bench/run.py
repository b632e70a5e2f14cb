#!/usr/bin/env python3
"""The benchmark's entry: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``) builds the cell's model through the
program's own serving entry, compiles every shape the cell's traffic uses
and serves one session; the window then drives the replicated token server
as a closed loop for ``--seconds``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a run that
also records a profiler trace.  After the window the served tokens are
compared with a plain float32 reference and the replicas' state with the
replies; the last line of standard output is the result, and the last
lines of standard error are the numbers compared, each with its limit.

It runs on the chip only: with no TPU, or fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import runner
    from harness.spec import SpecError
    try:
        result, numbers = runner.execute(
            args.workload, args.seed % (1 << 64), args.seconds,
            bool(args.trace), T_START)
    except (runner.NoChip, SpecError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    runner.report(result, numbers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
