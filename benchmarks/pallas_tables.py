"""Pallas-backend serving benchmarks (``pallas/*`` rows, BENCH_pallas.json).

Kernel-vs-fast serving throughput for the wired backend (DESIGN.md §2):
LeNet-5 batched serving and resnet8 serving of one image, each executed
on both the fast simulator and the pallas backend, with a bit-identity check
riding along (a perf row from a diverging backend would be meaningless).
Off-TPU the kernel runs in interpret mode — reported in the row names, as
with ``kernel/*`` — so these are correctness-trajectory numbers on CPU
and real accelerator numbers on TPU.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np


def _mode() -> str:
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"


def _time_serve(fn, repeats: int = 3) -> float:
    fn()                                    # warm up (plans, kernel traces)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _lenet_section(batch: int = 8) -> Dict:
    from repro.core.network_compiler import compile_network
    from repro.models.lenet import lenet5_random_weights, lenet5_specs
    net = compile_network(lenet5_specs(lenet5_random_weights(seed=0)),
                          np.zeros((1, 1, 32, 32), np.int8))
    rng = np.random.default_rng(0)
    images = np.stack([rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
                       for _ in range(batch)])
    out_fast, _ = net.serve(images)
    out_pal, _ = net.serve(images, backend="pallas")
    dt_fast = _time_serve(lambda: net.serve(images))
    dt_pal = _time_serve(lambda: net.serve(images, backend="pallas"))
    return {"batch": batch,
            "fast_img_per_s": batch / dt_fast,
            "kernel_img_per_s": batch / dt_pal,
            "bit_identical": bool(np.array_equal(out_fast, out_pal))}


def _resnet8_section() -> Dict:
    from repro.models.resnet8 import compile_resnet8, synthetic_image
    net, _ = compile_resnet8()
    img = synthetic_image(0)
    out_fast = net.serve_one(img, backend="fast")
    out_pal = net.serve([img], backend="pallas")[0][0]
    dt_fast = _time_serve(lambda: net.serve_one(img, backend="fast"))
    dt_pal = _time_serve(lambda: net.serve([img], backend="pallas"))
    return {"fast_img_per_s": 1.0 / dt_fast,
            "kernel_img_per_s": 1.0 / dt_pal,
            "bit_identical": bool(np.array_equal(out_fast, out_pal))}


def collect() -> Dict:
    return {"mode": _mode(),
            "lenet5": _lenet_section(),
            "resnet8": _resnet8_section()}


def all_tables(data: Dict) -> List[Dict]:
    mode = data["mode"]
    rows: List[Dict] = []
    for workload in ("lenet5", "resnet8"):
        sec = data[workload]
        rows.append({"name": f"pallas/{workload}/fast_img_per_s",
                     "value": round(sec["fast_img_per_s"], 2),
                     "paper": None, "note": ""})
        rows.append({"name": f"pallas/{workload}/{mode}_img_per_s",
                     "value": round(sec["kernel_img_per_s"], 2),
                     "paper": None,
                     "note": f"kernel-vs-fast "
                             f"{sec['kernel_img_per_s'] / sec['fast_img_per_s']:.2f}x"})
        rows.append({"name": f"pallas/{workload}/bit_identical",
                     "value": "PASS" if sec["bit_identical"] else "FAIL",
                     "paper": None,
                     "note": "OUT == fast simulator (saturate=False)"})
        if not sec["bit_identical"]:
            raise AssertionError(
                f"pallas backend diverged from the fast simulator on "
                f"{workload} — perf rows withheld (fail-loud)")
    return rows
