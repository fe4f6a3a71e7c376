"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes the cell's relation from the seed, builds the hierarchy, warms up,
runs the timed window and checks every answer against the plain
reference.  The last line of standard output is the result (JSON); the
compared numbers, each beside its limit, are the last lines of standard
error.  With no TPU, or fewer chips than the cell asks for, it exits 1
and prints no result.  ``--rows N`` rehearses the cell at N rows: off
the chip every step runs and it still exits 1 with no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearse at this many rows")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from bench.lib.harness import main
    code = main(parse(), T_START)
    sys.stdout.flush()
    sys.stderr.flush()
    # the compared numbers stay the last lines: no teardown logging
    os._exit(code)
