"""The benchmark's command: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for (``BENCHMARK.json``).  It exits non-zero, and prints no result,
where JAX finds no TPU.  The last line of its standard output is the
run's result; the last lines of its standard error are the numbers that
decided ``correct``, each with its limit.
"""

import time

T_START = time.monotonic()          # set-up counts from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from bench import harness
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
