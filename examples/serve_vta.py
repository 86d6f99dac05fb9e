"""Async serving demo: a seeded request stream through the VTA serving
engine (DESIGN.md §Serving).

  1. compile LeNet-5 through the VTA pipeline (compile-once);
  2. start the async engine — bounded request queue, max-batch/max-wait
     dynamic batch former, a worker pool draining formed batches on the
     batched (and optionally pallas) backend;
  3. replay a seeded Poisson arrival trace against it in real time;
  4. assert the serving contracts: every result bit-identical to a
     direct ``NetworkProgram.serve`` of the same image, and zero SLO
     accounting errors (``metrics.audit()`` empty);
  5. print the latency/throughput summary (p50/p95/p99, occupancy,
     SLO violations).

    PYTHONPATH=src python examples/serve_vta.py [--requests 16]
        [--rate 200] [--max-batch 4] [--max-wait 0.005]
        [--backends batched,batched] [--slo 0.5] [--guard]

Used by CI as the serving smoke: it exits non-zero on any contract
violation.  The hermetic latency-curve campaign lives in
``benchmarks/serving_latency_tables.py`` (EXPERIMENTS.md
§Serving-latency).
"""

import argparse
import sys

import numpy as np

from repro.core.network_compiler import compile_network
from repro.kernels.compile_cache import enable_compile_cache
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                synthetic_digit)
from repro.serving.vta import (BatchPolicy, QueueFull, VTAServingEngine,
                               WallClock, poisson_arrival_times,
                               request_images)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="offered load in requests/second (Poisson)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-wait", type=float, default=0.005)
    ap.add_argument("--backends", default="batched,batched",
                    help="comma-separated worker backends "
                         "(batched|pallas), one worker per entry")
    ap.add_argument("--slo", type=float, default=0.5,
                    help="per-request latency SLO in seconds")
    ap.add_argument("--guard", action="store_true",
                    help="serve through the PR 6 integrity guards "
                         "(batched workers only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    print("compiling LeNet-5 through the VTA pipeline...")
    net = compile_network(lenet5_specs(lenet5_random_weights(0)),
                          synthetic_digit(0))
    print(f"  plan shapes: {[s['inp_nbytes'] for s in net.plan_shapes()]} "
          f"INP bytes/layer; padded batch ladder = "
          f"{net.padded_batch_sizes(args.max_batch)}")

    guard = None
    if args.guard:
        from repro.harden import GuardPolicy
        guard = GuardPolicy()

    backends = tuple(args.backends.split(","))
    policy = BatchPolicy(max_batch=args.max_batch,
                         max_wait_s=args.max_wait,
                         max_depth=max(64, 4 * args.requests))
    engine = VTAServingEngine(net, policy=policy, backends=backends,
                              guard=guard, slo_s=args.slo)

    images = request_images(net, args.requests, seed=args.seed + 1)
    arrivals = poisson_arrival_times(args.rate, args.requests,
                                     seed=args.seed)
    clock = WallClock()
    tickets = []
    with engine:                       # start; drain + shutdown on exit
        t0 = clock.now()
        for img, t_rel in zip(images, arrivals):
            clock.sleep_until(t0 + t_rel)     # replay the seeded trace
            try:
                tickets.append(engine.submit(img))
            except QueueFull as exc:
                print(f"  backpressure: {exc}", file=sys.stderr)
                raise
        outs = [t.result(timeout=120.0) for t in tickets]

    # contract 1: bit-identity vs the direct compile-once serve path
    direct, _ = net.serve(images)
    mismatches = sum(1 for got, want in zip(outs, direct)
                     if not np.array_equal(got, want))
    # contract 2: zero SLO accounting errors after drain
    audit = engine.metrics.audit()
    summary = engine.metrics.summary()

    print(f"\nserved {summary['completed']:.0f}/{args.requests} requests "
          f"on {backends} (guarded={bool(guard)})")
    print(f"  p50/p95/p99 latency = {summary['p50_ms']:.2f}/"
          f"{summary['p95_ms']:.2f}/{summary['p99_ms']:.2f} ms; "
          f"throughput = {summary['throughput_rps']:.1f} rps")
    print(f"  mean batch occupancy = {summary['mean_batch_occupancy']:.2f}"
          f" (padded {summary['mean_padded_size']:.2f}); "
          f"SLO({args.slo * 1e3:.0f}ms) violations = "
          f"{summary['slo_violations']:.0f}")
    print(f"  bit-identical to direct serve: "
          f"{args.requests - mismatches}/{args.requests}")
    print(f"  accounting audit: "
          f"{'clean' if not audit else audit}")
    if args.guard:
        outcomes = [t.guard_report.outcome for t in tickets]
        print(f"  guard outcomes: "
              f"{ {o: outcomes.count(o) for o in set(outcomes)} }")

    if mismatches or audit or summary["completed"] != args.requests:
        print("SERVING CONTRACT VIOLATION", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
