"""Rehearse cells on the CPU, with no chip: the whole run of
``bench/run.py`` at a short window, with the device check, the compile
cache and the peaks left out and the kernels interpreted (what
``kernels/ops.py`` does on the CPU).  No number it prints is a device
number; this is not the benchmark's command.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--seconds 2] [--trace 0|1] [cell ...]
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("cells", nargs="*")
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--seed", type=int, default=2 ** 31 + 11)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from bench import harness, layout
    cells = args.cells or [w["name"] for w in layout.benchmark()["workloads"]]
    rc = 0
    for cell in cells:
        rc |= harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, rehearsal=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
