"""resnet8 end-to-end: ResNet-scale CNNs through the strided lowering.

The first workload with real stage transitions (DESIGN.md
§Strided-lowering): a 3-stage CIFAR-10-scale ResNet-8 whose
downsampling runs as stride-2 convolutions (k3/s2/p1 main path +
k2/s2 projection shortcut per transition, joins on the VTA) and whose
classification head is a global-average-pool tree reduction fused with
a 1×1 mixing conv — ADD-pair rounds + one SHR, all on the TensorAlu.

  1. calibrate weight scales + static requant shifts (two-phase §4.2);
  2. compile the DAG into 11 VTA layer programs sharing one DRAM
     allocation; print the per-layer schedule — input/residual sources,
     strides, chunk counts, ALU ADD instructions;
  3. verify the network bit-exactly on the fast backend — and, unless
     ``--skip-oracle``, on the oracle too;
  4. serve a batch of requests (batched runtime for ``--batch > 1``)
     against the graph's integer reference.

    PYTHONPATH=src python examples/resnet8_e2e.py [--requests 8]
                                                  [--batch 8]
                                                  [--backend fast|oracle|pallas]
                                                  [--skip-oracle]

``--backend pallas`` runs every layer through the ``vta_gemm`` MXU kernel
(compiled on a TPU, interpreted on the CPU) — residual joins, strided
chunks and the GAP head all execute bit-identically to the simulators.
"""

import argparse
import time

import numpy as np

from repro.core import isa
from repro.kernels.compile_cache import enable_compile_cache
from repro.models.resnet8 import (compile_resnet8, reference_forward_int8,
                                  synthetic_image)


def schedule_stats(net) -> None:
    srcs, rsrcs = net._sources(), net._res_sources()
    print("layer   in<-   res<-  stride  pool  chunks  gemm_loops  alu_adds")
    for k, layer in enumerate(net.layers):
        adds = sum(1 for i in layer.program.instructions
                   if isinstance(i, isa.AluInsn)
                   and i.alu_opcode == isa.AluOp.ADD and not i.use_imm)
        src = "img" if srcs[k] < 0 else net.layers[srcs[k]].spec.name
        res = ("-" if rsrcs[k] is None
               else net.layers[rsrcs[k]].spec.name)
        pool = layer.spec.pool or "-"
        print(f"  {layer.spec.name:<6}{src:>5}{res:>8}"
              f"{layer.spec.stride:>7}{pool:>7}"
              f"{layer.n_chunks:>7}{layer.program.gemm_loops():>12}"
              f"{adds:>9}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=1,
                    help="requests per batched VTA execution; 1 = serve "
                         "per-image (default: 1)")
    ap.add_argument("--backend", choices=("fast", "oracle", "pallas"),
                    default="fast",
                    help="serving backend: fast/oracle per image, or the "
                         "vta_gemm Pallas kernel (through serve)")
    ap.add_argument("--skip-oracle", action="store_true",
                    help="skip the oracle cross-check (CI smoke mode)")
    args = ap.parse_args()
    if args.batch > 1 and args.backend == "oracle":
        ap.error("--batch > 1 runs the batched engine; "
                 "--backend oracle is per-image only (use --batch 1)")
    enable_compile_cache()

    print("calibrating weight scales + requant shifts, compiling the "
          "resnet8 DAG...")
    t0 = time.perf_counter()
    net, graph = compile_resnet8()
    print(f"  compiled in {time.perf_counter() - t0:.3f}s; "
          f"{len(net.layers)} VTA layers, "
          f"total GeMM loops = {net.gemm_loops()}")
    schedule_stats(net)
    strided = [l for l in net.layers if l.spec.stride == 2]
    assert len(strided) == 4, "expected 4 stride-2 convs (2 per transition)"
    res_layers = [l for l in net.layers if l.spec.residual_add]
    assert len(res_layers) == 3, "expected three residual joins"
    gap_layers = [l for l in net.layers if l.spec.pool == "gap"]
    assert len(gap_layers) == 1, "expected a fused GAP head"
    print(f"  GAP head @{gap_layers[0].spec.name}: "
          f"{len(gap_layers[0].keep_rows)} surviving row, tree reduction "
          f"on-device")

    print("verifying the network (fast backend)...")
    out_fast, _ = net.verify(backend="fast")
    if not args.skip_oracle:
        print("verifying the network (oracle backend)...")
        out_oracle, _ = net.verify(backend="oracle")
        np.testing.assert_array_equal(out_oracle, out_fast)
        print("  oracle and fast backends agree bit-for-bit")

    images = [synthetic_image(100 + r) for r in range(args.requests)]
    serve_s = 0.0
    logits_all = []
    # the pallas kernel serves batches only: at --batch 1, of one request
    if args.batch > 1 or args.backend == "pallas":
        batch_backend = "pallas" if args.backend == "pallas" else "batched"
        mode = f"batched (batch {args.batch}, {batch_backend})"
        for lo in range(0, len(images), args.batch):
            t0 = time.perf_counter()
            outs, _ = net.serve(images[lo:lo + args.batch],
                                backend=batch_backend)
            serve_s += time.perf_counter() - t0
            logits_all.extend(outs)
    else:
        mode = f"per-image ({args.backend})"
        for img in images:
            t0 = time.perf_counter()
            logits_all.append(net.serve_one(img, backend=args.backend))
            serve_s += time.perf_counter() - t0
    for r, (img, logits) in enumerate(zip(images, logits_all)):
        ref = reference_forward_int8(graph, img)
        assert np.array_equal(logits, ref), f"request {r}: mismatch!"
    if args.requests:
        print(f"\nserved {args.requests} requests in {serve_s:.2f}s "
              f"({args.requests / serve_s:.1f} img/s, {mode}); "
              f"bit-exact vs graph integer reference: "
              f"{args.requests}/{args.requests}")


if __name__ == "__main__":
    main()
