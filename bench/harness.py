"""One run of one cell: set-up, the measured window, the drain, the check
against the configuration's plain reference, and the result line.

:func:`run` is what ``bench/run.py`` calls.  Set-up counts from the
process's start: importing JAX, the device check, the compile cache, the
weights and images drawn from the seed, compiling the network, starting
the engine and serving one batch at every rung of the padding ladder, so
that nothing compiles in the window.  The window offers the cell's
traffic for ``seconds``; then every request due in it is waited for (at
most :data:`DRAIN_S` past the close) and every answer is compared with
the reference.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import resource
import shutil
import sys
import time
from typing import Callable, List, Optional

import numpy as np

from bench import device, layout, loadgen, refops, stats
from bench.compile_log import CompileLog
from bench.served import ServedNet
from bench.trace_reduce import TraceSummary, find_trace, reduce_trace

POOL = 256               # distinct request images per run
DRAIN_S = 60.0           # longest wait for an answer past the window
TRACE_DIR = layout.ROOT / ".bench_trace"
CLOCK = time.monotonic   # the engine's clock too (serving/vta/clock.py)


@dataclasses.dataclass
class Readings:
    """What a run measured; the per-layer readers take it."""

    cell: layout.Cell
    window: tuple                      # (start, end) on CLOCK
    requests: List[loadgen.Request]
    served: ServedNet
    compile_log: CompileLog
    gemm_shapes: list                  # [(layer, M, K, N)] per image
    peak: Optional[dict]               # None off the chip
    trace: Optional[TraceSummary] = None

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def ops_per_image(self) -> int:
        return sum(2 * m * k * n for _, m, k, n in self.gemm_shapes)

    def answered(self) -> List[loadgen.Request]:
        return [r for r in self.requests if r.answered_t is not None]

    @property
    def images_per_s(self) -> float:
        lo, hi = self.window
        done = sum(1 for r in self.answered() if lo <= r.answered_t <= hi)
        return stats.rate(done, self.seconds)

    def latencies_ms(self) -> List[float]:
        """Every request due in the window, in the order they were due,
        from when it was due to its answer; one with no answer counts as
        waited for until the drain gave up."""
        give_up = self.window[1] + DRAIN_S
        return [1e3 * ((r.answered_t if r.answered_t is not None
                        else give_up) - r.due_t) for r in self.requests]


def end_to_end(r: Readings, setup_s: float) -> dict:
    lat = sorted(r.latencies_ms())
    return {
        "setup_s": setup_s,
        "images_per_s": r.images_per_s,
        "latency_p50_ms": stats.nearest_rank(lat, 50),
        "latency_p95_ms": stats.nearest_rank(lat, 95),
    }


def _drain(requests, served: ServedNet, deadline: float) -> None:
    """Wait for every accepted request and keep its answer or error, and
    when the benchmark saw the serve call that answered it return."""
    for r in requests:
        if r.ticket is None:
            continue
        try:
            r.answer = r.ticket.result(
                timeout=max(0.0, deadline - CLOCK()))
        except Exception as exc:  # noqa: BLE001 - a failed request is a reading
            r.error = exc
            continue
        r.answered_t = served.returned_at(r.ticket.image)
        if r.answered_t is None:
            raise RuntimeError(
                f"request answered by no serve call the benchmark saw: the "
                f"engine no longer hands the submitted rows to serve")


def check(requests, reference: np.ndarray) -> dict:
    """The numbers compared, each with its limit: answers that differ from
    the reference, the widest logit gap, and requests the engine took but
    never answered.  The arithmetic is exact, so every limit is 0."""
    wrong = gap = lost = 0
    for r in requests:
        if r.ticket is None:
            continue                   # refused at the door: a failure
        if r.error is not None:
            lost += 1
            continue
        want = reference[r.image]
        got = np.asarray(r.answer)
        if got.shape != want.shape:
            wrong += 1
            gap = max(gap, 256)
            continue
        diff = int(np.abs(got.astype(np.int64) - want.astype(np.int64)).max())
        wrong += diff > 0
        gap = max(gap, diff)
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "max_logit_diff": {"value": gap, "limit": 0},
            "unanswered": {"value": lost, "limit": 0}}


class HostWatch:
    """What the host did in the window that can hold answers back: the
    interpreter's garbage collections and the process's involuntary
    context switches (another process took its core)."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t = None
        gc.callbacks.append(self._on_gc)
        self._switches = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = CLOCK()
        elif self._t is not None:
            self.pauses.append(CLOCK() - self._t)
            self._t = None

    def stop(self, served: ServedNet, window) -> str:
        gc.callbacks.remove(self._on_gc)
        switches = (resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
                    - self._switches)
        spans = sorted(s for s, _ in served.spans_between(*window))
        longest = max((s[1] - s[0] for s in spans), default=0.0)
        idle, reach = 0.0, window[0]
        for s in spans:
            idle, reach = max(idle, s[0] - reach), max(reach, s[1])
        idle = max(idle, window[1] - reach)
        return (f"{len(self.pauses)} gc collections, longest "
                f"{1e3 * max(self.pauses, default=0.0):.1f} ms; {switches} "
                f"involuntary context switches; longest serve call "
                f"{1e3 * longest:.1f} ms; longest time with no serve call "
                f"running {1e3 * idle:.1f} ms")


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, **kw) -> int:
    """One run of the cell named ``workload``; prints the result line and
    returns the exit code (2: no chip)."""
    done = run_cell(layout.load_cell(workload), seed, seconds, trace,
                    t_start=t_start, **kw)
    return 0 if done is not None else 2


def run_cell(cell: layout.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, rehearsal: bool = False,
             serve_with: Optional[Callable] = None, out=None, err=None):
    """One run of ``cell``; prints the result line and returns
    ``(result, readings)``, or None where there is no chip.

    ``rehearsal`` is for the CPU: no device check, no compile cache, no
    peaks.  ``serve_with(cell, weights)`` returns a function
    ``(net, images, backend) -> (outputs, reports)`` that stands in for
    ``NetworkProgram.serve`` (the control, the fault tests)."""
    out = out or sys.stdout
    err = err or sys.stderr
    workload = cell.name
    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    # nothing outside its checkout and its own temporary directories
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if rehearsal:
        dev, peak = device.describe(devices[:cell.chips]), None
    else:
        try:
            dev = device.check_device(devices, cell.chips)
        except device.NoChip as exc:
            print(f"bench: {exc}", file=err)
            return None
        peak = device.peaks_for(dev["kind"])
        from repro.kernels.compile_cache import enable_compile_cache
        # the checkout's own .jax_cache/, whatever the environment names
        enable_compile_cache({})
    from repro.serving.vta import BatchPolicy, VTAServingEngine

    t_device = CLOCK()
    log = CompileLog(CLOCK).register()
    cfg, ref = cell.config, cell.ref_module()
    weights = ref.weights(cfg, seed)
    images = refops.draw_images(cfg, seed, POOL)
    net = cell.net_module().build(cfg, weights)
    served = ServedNet(net, clock=CLOCK, serve_fn=(
        serve_with(cell, weights) if serve_with else None))
    mix = cell.traffic
    policy = BatchPolicy(**mix["policy"])
    engine = VTAServingEngine(served, policy=policy,
                              backends=tuple(mix["workers"]))
    t_built = CLOCK()
    engine.start()
    for backend in sorted(set(mix["workers"])):
        for rung in net.padded_batch_sizes(policy.max_batch):
            served.serve([images[i % POOL][None] for i in range(rung)],
                         backend=backend)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no Python function events,
        options.host_tracer_level = 1       # and only the main host spans
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)

    with jax.profiler.TraceAnnotation("bench.window"):
        w0 = CLOCK()
        setup_s = w0 - t_start
        watch = HostWatch()
        requests = loadgen.drive(engine, served, mix, images, seed, w0,
                                 seconds, CLOCK)
        w1 = w0 + seconds
        while CLOCK() < w1:
            time.sleep(w1 - CLOCK())
    if trace:
        jax.profiler.stop_trace()
    _drain(requests, served, w1 + DRAIN_S)
    host = watch.stop(served, (w0, w1))
    engine.shutdown(drain=False, timeout=DRAIN_S)
    dev["memory_peak_bytes"] = device.memory_peak_bytes(
        devices[:cell.chips])

    readings = Readings(cell, (w0, w1), requests, served, log,
                        ref.gemm_shapes(cfg), peak)
    result = {"attempted": len(requests)}
    if trace:
        found = find_trace(TRACE_DIR)
        readings.trace = reduce_trace(found) if found else None
        metrics = {}
        for m in cell.per_layer:
            value = layout.metric_reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if readings.trace is not None:
            dev["busy_s"] = readings.trace.busy_s
            dev["window_s"] = readings.trace.window_s
    else:
        values = end_to_end(readings, setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    del engine, served, net              # the program's state goes first
    reference = ref.forward(cfg, weights, images)
    checks = check(requests, reference)
    answered = len(readings.answered())
    failed = len(requests) - answered
    correct = answered > 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    result.update(correct=correct, failed=failed, metrics=metrics,
                  device=dev)
    if trace and readings.trace is not None:
        result["breakdown"] = {
            "device_ops": [list(x) for x in readings.trace.device_ops[:10]],
            "idle_gaps": [list(x) for x in readings.trace.idle_gaps[:10]]}
    result["checks"] = checks
    print(f"bench: {workload} seed {seed}: {len(requests)} requests, "
          f"{answered} answered; set-up {setup_s:.3f} s (import and device "
          f"{t_device - t_start:.3f} s, weights and network "
          f"{t_built - t_device:.3f} s, engine and warm-up "
          f"{w0 - t_built:.3f} s); compile: {log.summary()}", file=err)
    print(f"bench: host in the window: {host}", file=err)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    result = {"correct": result.pop("correct"), **result}
    print(json.dumps(result), file=out, flush=True)
    return result, readings
