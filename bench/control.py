"""The control of the comparison that decides ``correct``: the cell's
plain reference computed at int4 (the precision below the configuration's
int8) put in the program's place behind the engine, at the cell's own
traffic.  Its answers must come out not correct; the smallest reading it
gives is the upper reading of each number compared.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

The benchmark's own runs never run it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_BITS = 4


def int4_serve(cell, weights):
    """``serve`` at int4: the reference's forward pass over the batch."""
    from bench import refops
    ref = cell.ref_module()
    quant = refops.low_precision(CONTROL_BITS)

    def serve(net, images, backend):
        batch = np.concatenate([np.asarray(i) for i in images])
        return ref.forward(cell.config, weights, batch, quant), []
    return serve


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rehearsal", action="store_true",
                   help="on the CPU, with no chip (tests)")
    args = p.parse_args(argv)
    from bench import harness, layout
    for seed in (int(s) for s in args.seeds.split(",")):
        done = harness.run_cell(
            layout.load_cell(args.workload), seed, args.seconds, False,
            t_start=time.monotonic(), rehearsal=args.rehearsal,
            serve_with=int4_serve)
        if done is None:
            return 2
        result, _ = done
        print("CONTROL " + json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": result["correct"],
            **{k: v["value"] for k, v in result["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
