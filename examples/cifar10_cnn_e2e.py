"""CIFAR-10-scale CNN end-to-end: the first workload past LeNet-5.

This is the scaling demonstration of DESIGN.md §3: same-padded
convolutions, max pooling, and layer matrices that no longer fit one SRAM
residency.  Layer 1 (conv 3→64 k5, same padding) lowers to a 1024×75
input matrix — 5120 INP vectors against a 2048-vector buffer — so its
program is multi-chunk *by construction*, with the pool/requant ALU uops
re-indexed against each chunk's local ACC window.

  1. calibrate static requant shifts over a held-out image set (§4.2);
  2. compile all 5 layers into one shared DRAM allocation (Fig. 12) and
     report the per-layer chunk/uop/wave statistics;
  3. verify the chain bit-exactly on the fast backend — and, unless
     ``--skip-oracle``, on the oracle too, asserting both backends agree
     byte-for-byte;
  4. serve a batch of classification requests against the integer
     reference.

    PYTHONPATH=src python examples/cifar10_cnn_e2e.py [--requests 4]
                                                      [--batch 4]
                                                      [--backend fast|oracle]
                                                      [--skip-oracle]

``--batch N`` serves the requests through the batched runtime (one
compiled plan per layer over the whole group, DESIGN.md §Batching)
instead of one VTA chain per image.
"""

import argparse
import time

import numpy as np

from repro.core import isa
from repro.core.cycle_model import FPGA_CLOCK_HZ
from repro.core.network_compiler import compile_network
from repro.kernels.compile_cache import enable_compile_cache
from repro.models.cifar_cnn import (calibrate_shifts,
                                    cifar_cnn_random_weights,
                                    cifar_cnn_specs, reference_forward_int8,
                                    synthetic_cifar_image)


def layer_stats(net) -> None:
    print("layer      chunks  gemm_loops  uops   uop_waves")
    for layer in net.layers:
        prog = layer.program
        waves = sum(1 for i in prog.instructions
                    if isinstance(i, isa.MemInsn)
                    and i.memory_type == isa.MemId.UOP) - 1
        print(f"  {layer.spec.name:<9}{layer.n_chunks:>5}"
              f"{prog.gemm_loops():>12}{len(prog.uops):>7}{waves:>10}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=1,
                    help="requests per batched VTA execution; 1 = serve "
                         "per-image (default: 1)")
    ap.add_argument("--backend", choices=("fast", "oracle"), default="fast",
                    help="backend for the per-image serving loop")
    ap.add_argument("--skip-oracle", action="store_true",
                    help="skip the oracle cross-check (CI smoke mode)")
    args = ap.parse_args()
    if args.batch > 1 and args.backend != "fast":
        ap.error("--batch > 1 runs the batched engine; "
                 "--backend oracle is per-image only (use --batch 1)")
    enable_compile_cache()

    weights = cifar_cnn_random_weights(seed=0)
    print("calibrating static requant shifts (§4.2)...")
    cal = [synthetic_cifar_image(s) for s in range(1, 9)]
    shifts = calibrate_shifts(weights, cal)

    print("compiling the CIFAR-10 CNN through the VTA pipeline...")
    t0 = time.perf_counter()
    net = compile_network(cifar_cnn_specs(weights, shifts),
                          synthetic_cifar_image(0))
    print(f"  compiled in {time.perf_counter() - t0:.3f}s; "
          f"total GeMM loops = {net.gemm_loops()} "
          f"(LeNet-5 was 2942 — ~{net.gemm_loops() / 2942:.0f}x larger)")
    layer_stats(net)
    assert max(net.chunks_per_layer()) > 1, "expected a multi-chunk layer"
    cr = net.cycle_report()
    print(f"  compute cycles = {cr.total_compute_cycles} "
          f"(+{cr.compute_load_cycles} UOP/ACC-load) → "
          f"{cr.execution_time_s(include_loads=True) * 1e6:.1f} µs @650 MHz")

    print("verifying the chain (fast backend)...")
    out_fast, _ = net.verify(backend="fast")
    if not args.skip_oracle:
        print("verifying the chain (oracle backend)...")
        out_oracle, _ = net.verify(backend="oracle")
        np.testing.assert_array_equal(out_oracle, out_fast)
        print("  oracle and fast backends agree bit-for-bit")

    rng = np.random.default_rng(42)
    images = [rng.integers(-64, 64, (1, 3, 32, 32)).astype(np.int8)
              for _ in range(args.requests)]
    serve_s = 0.0
    logits_all = []
    if args.batch > 1:
        mode = f"batched (batch {args.batch})"
        for lo in range(0, len(images), args.batch):
            t0 = time.perf_counter()
            outs, _ = net.serve(images[lo:lo + args.batch])
            serve_s += time.perf_counter() - t0
            logits_all.extend(outs)
    else:
        mode = f"per-image ({args.backend})"
        for img in images:
            t0 = time.perf_counter()
            logits_all.append(net.serve_one(img, backend=args.backend))
            serve_s += time.perf_counter() - t0
    shifts = [l.requant_shift for l in net.layers]
    for r, (img, logits) in enumerate(zip(images, logits_all)):
        ref_logits, _ = reference_forward_int8(weights, img, shifts)
        assert np.array_equal(logits, ref_logits), f"request {r}: mismatch!"
    if args.requests:
        print(f"\nserved {args.requests} requests in {serve_s:.2f}s "
              f"({args.requests / serve_s:.1f} img/s, {mode}); "
              f"bit-exact vs integer reference: "
              f"{args.requests}/{args.requests}")


if __name__ == "__main__":
    main()
