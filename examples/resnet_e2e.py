"""resnet_tiny end-to-end: branching CNNs through the graph compiler.

The first workload the flat `List[LayerSpec]` front end could not
express (DESIGN.md §Graph): a CIFAR-10-scale ResNet with two residual
joins, compiled through the DAG IR + pass pipeline (`repro.graph`) and
executed with the skip adds *on the VTA* — each join is an ALU
vector-vector ADD against an ACC-loaded second operand, visible in the
instruction stream below, not a host-side numpy merge.

  1. calibrate weight scales + static requant shifts (two-phase §4.2);
  2. compile the DAG into 7 VTA layer programs sharing one DRAM
     allocation; print the per-layer schedule — input/residual sources,
     chunk counts, ALU ADD instructions;
  3. verify the network bit-exactly on the fast backend — and, unless
     ``--skip-oracle``, on the oracle too;
  4. serve a batch of requests (batched runtime for ``--batch > 1``)
     against the graph's integer reference.

    PYTHONPATH=src python examples/resnet_e2e.py [--requests 4]
                                                 [--batch 4]
                                                 [--backend fast|oracle]
                                                 [--skip-oracle]
"""

import argparse
import time

import numpy as np

from repro.core import isa
from repro.kernels.compile_cache import enable_compile_cache
from repro.models.resnet_tiny import (compile_resnet_tiny,
                                      reference_forward_int8,
                                      synthetic_image)


def schedule_stats(net) -> None:
    srcs, rsrcs = net._sources(), net._res_sources()
    print("layer   in<-  res<-  chunks  gemm_loops  alu_add_insns")
    for k, layer in enumerate(net.layers):
        adds = sum(1 for i in layer.program.instructions
                   if isinstance(i, isa.AluInsn)
                   and i.alu_opcode == isa.AluOp.ADD and not i.use_imm)
        src = "img" if srcs[k] < 0 else net.layers[srcs[k]].spec.name
        res = ("-" if rsrcs[k] is None
               else net.layers[rsrcs[k]].spec.name)
        print(f"  {layer.spec.name:<6}{src:>5}{res:>7}"
              f"{layer.n_chunks:>7}{layer.program.gemm_loops():>12}"
              f"{adds:>10}")


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

    print("calibrating weight scales + requant shifts, compiling the "
          "resnet_tiny DAG...")
    t0 = time.perf_counter()
    net, graph = compile_resnet_tiny()
    print(f"  compiled in {time.perf_counter() - t0:.3f}s; "
          f"{len(net.layers)} VTA layers, "
          f"total GeMM loops = {net.gemm_loops()}")
    schedule_stats(net)
    res_layers = [l for l in net.layers if l.spec.residual_add]
    assert len(res_layers) == 2, "expected two residual joins"
    assert max(l.n_chunks for l in res_layers) > 1, \
        "expected a multi-chunk residual layer"
    for l in res_layers:
        print(f"  join @{l.spec.name}: on-VTA ADD, skip pre-shift "
              f"{l.spec.residual_pre_shift}, post-add requant "
              f"{l.post_shift}")

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
