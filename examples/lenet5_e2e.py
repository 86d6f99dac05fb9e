"""End-to-end driver (deliverable (b)): LeNet-5 served through the VTA
compiler pipeline with batched requests — the paper's own workload (§4.3).

  1. compile all 5 layers into one shared DRAM allocation (Fig. 12);
  2. serve a batch of digit-classification requests — per-image
     (``--batch 1``: host re-binarises the input, launches the 5 chained
     VTA executions, reads back the logits) or truly batched
     (``--batch N``: one compiled plan per layer executes over the whole
     request batch at once, DESIGN.md §Batching);
  3. verify every answer bit-exactly against the integer reference and
     report agreement with the float (JAX) model + the §5 tables.

    PYTHONPATH=src python examples/lenet5_e2e.py [--requests 16]
                                                 [--batch 8]
                                                 [--backend fast|oracle|pallas]

``--backend fast`` (the default) serves on the vectorised plan-compiling
simulator; ``--backend oracle`` uses the per-struct reference interpreter
(per-image serving only); ``--backend pallas`` lowers each layer to the
``vta_gemm`` MXU kernel (compiled on a TPU, interpreted on the CPU; batched
serving via ``--batch``).  All paths are bit-exact — batching just gets
there sooner (EXPERIMENTS.md §Serving).  JAX's compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``.
"""

import argparse
import time

import numpy as np

from repro.core.cycle_model import FPGA_CLOCK_HZ
from repro.core.network_compiler import compile_network
from repro.kernels.compile_cache import enable_compile_cache
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                reference_forward_float,
                                reference_forward_int8)


def serve_request(net, image: np.ndarray, *,
                  backend: str = "fast") -> np.ndarray:
    """One inference: rewrite the layer-1 INP region for this image, then
    run the 5 chained VTA executions (Fig. 12).  Thin wrapper kept for
    compatibility — the logic lives in ``NetworkProgram.serve_one``."""
    return net.serve_one(image.astype(np.int8), backend=backend)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=1,
                    help="requests per batched VTA execution; 1 = serve "
                         "per-image (default: 1)")
    ap.add_argument("--backend", choices=("fast", "oracle", "pallas"),
                    default="fast",
                    help="execution backend: fast/oracle simulators, or "
                         "the vta_gemm Pallas kernel (default: fast)")
    args = ap.parse_args()
    if args.batch > 1 and args.backend == "oracle":
        ap.error("--batch > 1 runs the batched engine; "
                 "--backend oracle is per-image only (use --batch 1)")
    enable_compile_cache()

    weights = lenet5_random_weights(seed=0)
    print("compiling LeNet-5 through the VTA pipeline...")
    t0 = time.perf_counter()
    # static requant shifts calibrated over a held-out image set (§4.2:
    # everything is fixed at compile time — predictable execution)
    from repro.models.lenet import calibrate_shifts
    cal_rng = np.random.default_rng(7)
    cal = [cal_rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
           for _ in range(8)]
    shifts = calibrate_shifts(weights, cal)
    net = compile_network(lenet5_specs(weights, shifts),
                          np.zeros((1, 1, 32, 32), np.int8))
    print(f"  compiled in {time.perf_counter() - t0:.3f}s; "
          f"total GeMM loops = {net.gemm_loops()} (paper: 2942)")
    cr = net.cycle_report()
    print(f"  TensorGemm cycles = {cr.tensor_gemm_cycles} (paper: 2972); "
          f"exec = {cr.execution_time_s(FPGA_CLOCK_HZ) * 1e6:.2f} µs "
          f"@650 MHz (paper: 9.8 µs, leaner ALU schedule)")
    shifts = [l.requant_shift for l in net.layers]

    rng = np.random.default_rng(42)
    images = [rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
              for _ in range(args.requests)]
    logits_all = []
    serve_s = 0.0
    # the pallas kernel serves batches only: at --batch 1, of one request
    if args.batch > 1 or args.backend == "pallas":
        batch_backend = "pallas" if args.backend == "pallas" else "batched"
        mode = f"batched (batch {args.batch}, {batch_backend})"
        for lo in range(0, len(images), args.batch):
            group = images[lo:lo + args.batch]
            t0 = time.perf_counter()
            outs, _ = net.serve(group, backend=batch_backend)
            serve_s += time.perf_counter() - t0
            logits_all.extend(outs)
    else:
        mode = f"per-image ({args.backend})"
        for img in images:
            t0 = time.perf_counter()
            logits_all.append(serve_request(net, img,
                                            backend=args.backend))
            serve_s += time.perf_counter() - t0

    agree_float = 0
    for r, (img, logits) in enumerate(zip(images, logits_all)):
        ref_logits, _ = reference_forward_int8(weights, img, shifts)
        assert np.array_equal(logits, ref_logits), f"request {r}: mismatch!"
        fl = reference_forward_float(weights, img)
        agree_float += int(np.argmax(logits) == np.argmax(fl))
    if args.requests:
        print(f"\nserved {args.requests} requests in {serve_s:.2f}s "
              f"({args.requests / serve_s:.1f} img/s, {mode} on the "
              f"functional simulator; verification excluded)")
        print(f"bit-exact vs integer reference: "
              f"{args.requests}/{args.requests}")
        print(f"argmax agreement with float model: "
              f"{agree_float}/{args.requests}")


if __name__ == "__main__":
    main()
