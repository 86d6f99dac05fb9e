"""Serving-latency benchmarks: throughput–latency curves vs offered load
(EXPERIMENTS.md §Serving-latency, DESIGN.md §Serving).

For lenet5 and resnet8 on the batched backend:

1. calibrate a deterministic :class:`ServiceModel` from real timed
   serves (the one wall-clock step);
2. sweep ≥3 offered loads — 0.5×, 0.8× and 1.2× of the modeled
   two-worker capacity — through the virtual-clock discrete-event
   simulation of the engine's own max-batch/max-wait policy, emitting
   p50/p99 latency, throughput, batch occupancy, SLO violations and
   backpressure rejections per load point;
3. ``servelat/<net>/bit_identity`` (EXACT): the *threaded* engine's
   outputs for a seeded request set must equal a direct
   ``NetworkProgram.serve`` of the same images bit-for-bit;
4. ``servelat/<net>/deterministic_replay`` (EXACT): two same-seed
   virtual-clock runs must produce identical request traces and
   summaries.

``SERVING_CAMPAIGN_N`` scales the per-load request count (default 200;
CI smoke runs a small N).  Timing-derived rows are reported, not gated —
container throughput varies run to run; the EXACT rows gate the
correctness and determinism contracts, which do not.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from repro.core.network_compiler import compile_network
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                synthetic_digit)
from repro.serving.vta import (BatchPolicy, PoissonSource, VTAServingEngine,
                               calibrate_service_model, request_images,
                               serve_all, simulate)

WORKERS = 2
MAX_BATCH = 8
LOAD_FACTORS = (0.5, 0.8, 1.2)
BIT_IDENTITY_N = 12


def _lenet5():
    return compile_network(lenet5_specs(lenet5_random_weights(0)),
                           synthetic_digit(0))


def _resnet8():
    from repro.models.resnet8 import compile_resnet8
    net, _ = compile_resnet8()
    return net


def _campaign_n() -> int:
    return int(os.environ.get("SERVING_CAMPAIGN_N", "200"))


def _curve(net, model, policy, slo_s, n) -> List[Dict]:
    capacity_rps = WORKERS * MAX_BATCH / model.service_s(MAX_BATCH)
    points = []
    for i, factor in enumerate(LOAD_FACTORS):
        rate = factor * capacity_rps
        result = simulate(PoissonSource(rate, n, seed=100 + i), policy,
                          model, workers=WORKERS, slo_s=slo_s)
        summary = result.metrics.summary()
        audit = result.metrics.audit()
        if audit:
            raise AssertionError(f"SLO accounting errors at load "
                                 f"{factor}: {audit}")
        points.append({
            "load_factor": factor,
            "offered_rps": round(float(rate), 2),
            "throughput_rps": round(float(summary["throughput_rps"]), 2),
            "p50_ms": round(float(summary["p50_ms"]), 4),
            "p99_ms": round(float(summary["p99_ms"]), 4),
            "mean_batch_occupancy": round(
                float(summary["mean_batch_occupancy"]), 3),
            "slo_violations": int(summary["slo_violations"]),
            "rejected": int(summary["rejected"]),
            "completed": int(summary["completed"]),
        })
    return points


def _bit_identity(net, tag: str) -> str:
    """Threaded engine vs direct serve on the same seeded images."""
    images = request_images(net, BIT_IDENTITY_N, seed=11)
    policy = BatchPolicy(max_batch=4, max_wait_s=0.002, max_depth=64)
    engine = VTAServingEngine(net, policy=policy,
                              backends=("batched", "batched")).start()
    try:
        outs, _ = serve_all(engine, images)
    finally:
        engine.shutdown()
    audit = engine.metrics.audit()
    if audit:
        raise AssertionError(f"{tag}: engine accounting errors: {audit}")
    direct, _ = net.serve(images)
    return "PASS" if np.array_equal(outs, direct) else "FAIL"


def _deterministic_replay(net, model, policy, slo_s, n) -> str:
    runs = []
    for _ in range(2):
        result = simulate(PoissonSource(0.8 * WORKERS * MAX_BATCH
                                        / model.service_s(MAX_BATCH),
                                        n, seed=42),
                          policy, model, workers=WORKERS, slo_s=slo_s)
        runs.append((result.trace(), result.metrics.summary()))
    same = runs[0] == runs[1]
    return "PASS" if same else "FAIL"


def collect() -> Dict:
    n = _campaign_n()
    replay_n = min(n, 100)
    data: Dict = {"campaign_n": n, "workers": WORKERS,
                  "max_batch": MAX_BATCH, "load_factors": LOAD_FACTORS,
                  "backend": "batched", "nets": {}}
    for tag, make_net in (("lenet5", _lenet5), ("resnet8", _resnet8)):
        net = make_net()
        model = calibrate_service_model(net, batch=MAX_BATCH)
        policy = BatchPolicy(max_batch=MAX_BATCH,
                             max_wait_s=model.service_s(MAX_BATCH),
                             max_depth=8 * MAX_BATCH)
        slo_s = 10 * model.service_s(MAX_BATCH)
        data["nets"][tag] = {
            "service_model": {"base_ms": round(model.base_s * 1e3, 4),
                              "per_image_ms": round(
                                  model.per_image_s * 1e3, 4)},
            "slo_ms": round(slo_s * 1e3, 4),
            "curve": _curve(net, model, policy, slo_s, n),
            "bit_identity": _bit_identity(net, tag),
            "deterministic_replay": _deterministic_replay(
                net, model, policy, slo_s, replay_n),
        }
    return data


def all_tables(data: Dict) -> List[Dict]:
    rows: List[Dict] = []
    for tag, entry in data["nets"].items():
        for point in entry["curve"]:
            rho = point["load_factor"]
            rows.append({"name": f"servelat/{tag}/p50_ms@rho{rho}",
                         "value": point["p50_ms"], "paper": None,
                         "note": f"offered={point['offered_rps']}rps"})
            rows.append({"name": f"servelat/{tag}/p99_ms@rho{rho}",
                         "value": point["p99_ms"], "paper": None,
                         "note": f"slo_viol={point['slo_violations']} "
                                 f"rejected={point['rejected']}"})
            rows.append({"name": f"servelat/{tag}/throughput_rps@rho{rho}",
                         "value": point["throughput_rps"], "paper": None,
                         "note": f"occupancy="
                                 f"{point['mean_batch_occupancy']}"})
        rows.append({"name": f"servelat/{tag}/bit_identity",
                     "value": entry["bit_identity"], "paper": "PASS",
                     "note": "engine == direct serve, bit-exact"})
        rows.append({"name": f"servelat/{tag}/deterministic_replay",
                     "value": entry["deterministic_replay"],
                     "paper": "PASS",
                     "note": "same seed => identical trace+summary"})
    return rows
