"""Smoke test of the pallas serving path on one TPU chip.

    python chip_smoke.py

Runs in one process on JAX's first device and refuses to run unless that
is a TPU: no CPU fallback, no interpret mode.  For lenet5 (the paper's
workload) and resnet8 (the widest net the repo compiles), at their defined
widths with seeded weights, it

1. serves a batch of 8 seeded images with ``NetworkProgram.serve(...,
   backend="pallas")`` and checks it bit for bit against the numpy batched
   interpreter and the net's integer reference;
2. serves 16 requests through ``VTAServingEngine`` with two pallas workers,
   in bursts that use several rungs of the batch ladder, and checks every
   answer against a direct serve and the metrics audit;
3. lowers one layer's kernel call as serving makes it (one program: A
   staged from the activations, the pads, ``vta_gemm``, the slice) and
   checks that it holds a compiled TPU kernel (``tpu_custom_call``).

JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, or else in ``.jax_cache/`` of the checkout; the compile lines report
backend compiles, their seconds and the cache hits, so a second run that
shares the cache shows it.  Any failed check exits non-zero.  The last line
of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
BATCH = 8
# 16 engine requests in bursts; each burst forms whole batches, padded to
# ladder rungs 8, 4, 4 and 1
BURSTS = (8, 3, 4, 1)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
KERNEL_FUN = "jit(_staged_vta_gemm)"


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_device(devices) -> dict:
    """The run's device report; raises unless JAX's first device is a TPU."""
    dev = devices[0]
    require(dev.platform == "tpu",
            f"JAX's first device is {dev.platform!r} ({dev.device_kind}); "
            f"this smoke test runs only on a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


class CompileLog:
    """Backend compiles and persistent-cache hits seen through
    ``jax.monitoring`` (listeners fire on the engine's worker threads)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = []          # (function name, seconds)
        self.hits = 0

    def on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.compiles.append((kw.get("fun_name"), duration))

    def on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.hits += 1

    def mark(self):
        with self._lock:
            return len(self.compiles), self.hits

    def report(self, since=(0, 0)) -> str:
        with self._lock:
            done = self.compiles[since[0]:]
            hits = self.hits - since[1]
        kernels = [s for name, s in done if name == KERNEL_FUN]
        return (f"{len(done)} backend compiles in "
                f"{sum(s for _, s in done):.3f} s, {len(kernels)} of them "
                f"kernel calls in {sum(kernels):.3f} s; "
                f"{hits} persistent-cache hits")


def lenet5():
    from repro.core.network_compiler import compile_network
    from repro.models.lenet import (calibrate_shifts, lenet5_random_weights,
                                    lenet5_specs, reference_forward_int8)
    weights = lenet5_random_weights(seed=0)
    cal_rng = np.random.default_rng(7)
    cal = [cal_rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
           for _ in range(8)]
    net = compile_network(lenet5_specs(weights,
                                       calibrate_shifts(weights, cal)),
                          np.zeros((1, 1, 32, 32), np.int8))
    shifts = [layer.requant_shift for layer in net.layers]
    rng = np.random.default_rng(42)
    images = [rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
              for _ in range(sum(BURSTS))]
    return net, images, lambda img: reference_forward_int8(
        weights, img, shifts)[0]


def resnet8():
    from repro.models.resnet8 import (compile_resnet8, reference_forward_int8,
                                      synthetic_image)
    net, graph = compile_resnet8()
    images = [synthetic_image(100 + r) for r in range(sum(BURSTS))]
    return net, images, lambda img: reference_forward_int8(graph, img)


def serve_phase(name, net, images, reference, log) -> None:
    batch = images[:BATCH]
    mark = log.mark()
    t0 = time.perf_counter()
    out_p, _ = net.serve(batch, backend="pallas")
    cold_s = time.perf_counter() - t0
    print(f"{name}: first pallas serve @{BATCH} took {cold_s:.3f} s; "
          f"compile: {log.report(mark)}", flush=True)
    out_b, _ = net.serve(batch, backend="batched")
    ref = np.stack([reference(img) for img in batch])
    require(np.array_equal(out_p, out_b),
            f"{name}: pallas serve differs from the batched interpreter")
    require(np.array_equal(out_p, ref),
            f"{name}: pallas serve differs from reference_forward_int8")
    print(f"{name}: pallas serve @{BATCH} bit-identical to the batched "
          f"interpreter and to reference_forward_int8: {BATCH}/{BATCH}",
          flush=True)


def engine_phase(name, net, images, log) -> None:
    from repro.serving.vta import BatchPolicy, VTAServingEngine
    policy = BatchPolicy(max_batch=BATCH, max_wait_s=0.05, max_depth=64)
    engine = VTAServingEngine(net, policy=policy,
                              backends=("pallas", "pallas"))
    mark = log.mark()
    tickets, outs = [], []
    with engine:
        lo = 0
        for size in BURSTS:
            burst = [engine.submit(img) for img in images[lo:lo + size]]
            outs += [t.result(timeout=600.0) for t in burst]
            tickets += burst
            lo += size
    direct, _ = net.serve(images, backend="batched")
    same = sum(np.array_equal(o, d) for o, d in zip(outs, direct))
    rungs = sorted({t.record.padded_size for t in tickets})
    workers = sorted({t.record.worker for t in tickets})
    audit = engine.metrics.audit()
    print(f"{name}: engine (pallas, pallas) answers bit-identical to a "
          f"direct serve: {same}/{len(images)}; ladder rungs used {rungs}, "
          f"workers used {workers}; audit {audit or 'clean'}; "
          f"compile: {log.report(mark)}", flush=True)
    require(same == len(images), f"{name}: engine answers differ")
    require(len(rungs) > 1, f"{name}: engine used one ladder rung only")
    require(audit == [], f"{name}: metrics audit failed: {audit}")


def kernel_phase(name, net) -> None:
    """Lower the first layer's kernel call at batch 8 as serving makes it:
    one program holding A's staging, the pads, ``vta_gemm`` and the
    slice."""
    from repro.core.pallas_backend import kernel_call
    from repro.kernels.ops import _staged_vta_gemm, pallas_interpret

    args, statics = kernel_call(net.layers[0], BATCH).matmul_args()
    text = _staged_vta_gemm.lower(*args, interpret=pallas_interpret(),
                                  **statics).as_text()
    require("tpu_custom_call" in text,
            f"{name}: the lowered kernel call holds no tpu_custom_call")
    print(f"{name}: layer 0 kernel call, activations {args[0].shape} "
          f"staged to A, A @ W {args[2].shape} (staging, pads, vta_gemm, "
          f"slice) lowers to a tpu_custom_call (compiled Mosaic kernel, not "
          f"interpreted)", flush=True)


def main() -> None:
    import jax
    device = check_device(jax.devices())
    print(f"device: {device['kind']}, {device['count']} device(s), "
          f"platform {device['platform']}", flush=True)

    sys.path.insert(0, str(REPO / "src"))
    from repro.kernels.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)

    t_all = time.perf_counter()
    for name, build in (("lenet5", lenet5), ("resnet8", resnet8)):
        t0 = time.perf_counter()
        net, images, reference = build()
        print(f"{name}: compiled {len(net.layers)} VTA layers in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        serve_phase(name, net, images, reference, log)
        engine_phase(name, net, images, log)
        kernel_phase(name, net)
    print(f"total: {time.perf_counter() - t_all:.3f} s; "
          f"compile: {log.report()}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as exc:
        print(f"chip smoke FAILED: {exc}", file=sys.stderr)
        sys.exit(1)
