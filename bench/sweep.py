"""Find an open-loop cell's knee once, on the chip: offer its traffic at
each of a list of rates, in one process, and print per rate what was
offered, answered and refused, the tail, and whether the backlog grew
(the latency of the last fifth of the requests against the first fifth).
The knee is the highest rate answered at the offered rate with no
refusal and no growing backlog; the cell's mix is then fixed at about
four fifths of it.

    python3 bench/sweep.py --workload <cell> --rates 100,200,400 [--seconds 5]
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seed", type=int, default=2 ** 31 + 101)
    args = p.parse_args(argv)
    from bench import harness, layout, stats
    for rate in (float(r) for r in args.rates.split(",")):
        cell = layout.load_cell(args.workload)
        cell.traffic["rate_rps"] = rate
        done = harness.run_cell(cell, args.seed, args.seconds, False,
                                t_start=time.monotonic())
        if done is None:
            return 2
        result, r = done
        by_due = r.latencies_ms()
        lat = sorted(by_due)
        fifth = max(1, len(by_due) // 5)
        first = sorted(by_due[:fifth])
        last = sorted(by_due[-fifth:])
        refused = sum(1 for q in r.requests if q.refused is not None)
        print("SWEEP " + json.dumps({
            "workload": args.workload, "rate_rps": rate,
            "offered": len(r.requests), "failed": result["failed"],
            "refused": refused, "correct": result["correct"],
            "answered_per_s": r.images_per_s,
            "p50_ms": stats.nearest_rank(lat, 50),
            "p95_ms": stats.nearest_rank(lat, 95),
            "first_fifth_p50_ms": stats.nearest_rank(first, 50),
            "last_fifth_p50_ms": stats.nearest_rank(last, 50)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
