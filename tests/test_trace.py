"""Program spans (``repro.trace``) on the CPU.

With no profiler session ``span`` is one shared null context.  Under
``jax.profiler.trace`` a lenet5 ``serve(backend="pallas")`` of 2 images
and one engine batch of 3 requests (padded to a rung of 4) record every
span of the serving path, nested on one thread as the layers call each
other, with the counts the benchmark's readers sum: ``issued_macs`` is
the padded ``vta_gemm`` call's ``gemm_blocks`` product, ``a_bytes`` the
A the device stages, ``useful_macs`` the layer's valid M·K·N per row,
``bytes`` what each kernel call sends (the activations) and fetches,
``positions`` what each pool reads, and ``rows``/``real``/
``first_rid``/``worker`` of ``engine.execute`` those of the batch's
``RequestRecord``s.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import trace
from repro.core.network_compiler import compile_network
from repro.core.pallas_backend import kernel_call, plan_pallas
from repro.kernels.ops import gemm_blocks
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                synthetic_digit)
from repro.serving.vta import (BatchPolicy, VTAServingEngine, request_images,
                               serve_all)

# parent -> the spans directly inside it, as the serving path nests them
NESTING = {
    "engine.execute": {"vta.serve"},
    "vta.serve": {"vta.layer"},
    "vta.layer": {"vta.stage", "vta.kernel", "vta.epilogue", "vta.readout"},
    "vta.kernel": {"vta.kernel.put", "vta.kernel.dispatch",
                   "vta.kernel.fetch"},
    "vta.epilogue": {"vta.pool"},
}
SPANS = {"engine.batch_form"} | set(NESTING) | set().union(*NESTING.values())


class Node:
    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end = name, start, end
        self.stats = dict(stats or ())
        self.children = []

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def _threads(path):
    """Per host thread that recorded program spans, its span forest."""
    profile = ProfileData.from_file(str(path))
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((ev.start_ns, -ev.duration_ns, ev.name, ev.stats)
                          for ev in line.events
                          if ev.name.startswith(("vta.", "engine."))),
                         key=lambda x: x[:2])
            if not evs:
                continue
            roots, stack = [], []
            for start, neg, name, stats in evs:
                node = Node(name, start, start - neg, stats)
                while stack and stack[-1].end < node.end:
                    stack.pop()
                (stack[-1].children if stack else roots).append(node)
                stack.append(node)
            out.append(roots)
    return out


@pytest.fixture(scope="module")
def lenet():
    return compile_network(lenet5_specs(lenet5_random_weights(0)),
                           synthetic_digit(0))


@pytest.fixture(scope="module")
def traced(lenet, tmp_path_factory):
    """One direct serve of 2 images and one engine batch of 3 requests,
    every shape warmed before the profiler starts."""
    images = request_images(lenet, 3, seed=1)
    for rung in (2, 4):
        lenet.serve(images[:1] * rung, backend="pallas")
    engine = VTAServingEngine(
        lenet, policy=BatchPolicy(max_batch=4, max_wait_s=0.2, max_depth=8),
        backends=("pallas",)).start()
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        lenet.serve(images[:2], backend="pallas")
        _, tickets = serve_all(engine, images)
        engine.shutdown()          # ends the worker's last batch_form span
    path, = log_dir.glob("plugins/profile/*/*.xplane.pb")
    return _threads(path), [t.record for t in tickets]


def test_no_session_gives_the_shared_null_context():
    assert not trace._recording()
    assert trace.span("vta.serve", rows=4) is trace.span("vta.layer")
    with trace.span("vta.stage"):
        pass


def test_the_flag_flips_with_the_profiler_session(tmp_path):
    """The flag is jaxlib's ``TraceMe.is_enabled``: a JAX upgrade that moved
    it would leave tracing silently off, so pin it to the session."""
    assert not trace._recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace._recording()
        assert isinstance(trace.span("vta.serve", rows=1),
                          jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    assert not trace._recording()


def test_every_span_is_recorded_nested_on_one_thread(traced):
    threads, _ = traced
    seen = set()
    for roots in threads:
        for root in roots:
            for node in root.walk():
                seen.add(node.name)
                assert {c.name for c in node.children} <= \
                    NESTING.get(node.name, set()), node.name
    assert seen == SPANS
    serves = [n for roots in threads for r in roots for n in r.walk()
              if n.name == "vta.serve"]
    assert sorted(s.stats["rows"] for s in serves) == [2, 4]
    for serve in serves:
        assert [c.name for c in serve.children] == ["vta.layer"] * 5


def test_counts_are_the_kernel_calls_and_the_layers_work(traced, lenet):
    threads, _ = traced
    serves = [n for roots in threads for r in roots for n in r.walk()
              if n.name == "vta.serve"]
    for serve in serves:
        rows = serve.stats["rows"]
        for k, (layer_span, layer) in enumerate(
                zip(serve.children, lenet.layers)):
            assert layer_span.stats == {"layer": k,
                                        "useful_macs": rows * layer.macs}
            call = kernel_call(layer, rows)
            g = gemm_blocks(call.m, call.k, call.n)
            dispatch, = [n for n in layer_span.walk()
                         if n.name == "vta.kernel.dispatch"]
            assert dispatch.stats == {"issued_macs": g.m * g.k * g.n,
                                      "a_bytes": call.m * call.k}


def test_pool_positions_and_kernel_bytes(traced, lenet):
    """``vta.pool`` counts the input positions each pooled layer's
    epilogue reads (rows × the layer's pooled result rows); the kernel
    call's put counts the activations it sends (the weights and bias are
    on the device already), its fetch the result."""
    threads, _ = traced
    serves = [n for roots in threads for r in roots for n in r.walk()
              if n.name == "vta.serve"]
    for serve in serves:
        rows = serve.stats["rows"]
        for layer_span, layer in zip(serve.children, lenet.layers):
            p = plan_pallas(layer.program)
            call = kernel_call(layer, rows)
            put, = [n for n in layer_span.walk()
                    if n.name == "vta.kernel.put"]
            fetch, = [n for n in layer_span.walk()
                      if n.name == "vta.kernel.fetch"]
            assert put.stats == {"bytes": math.prod(call.x)}
            assert fetch.stats == {"bytes": call.m * call.n * (
                1 if call.out_dtype == jnp.int8 else 4)}
            pools = [n for n in layer_span.walk() if n.name == "vta.pool"]
            if layer.spec.pool is None:
                assert not pools and p.pool_rows == 0
            else:
                pool, = pools
                assert p.pool_rows == layer.input_matrix.shape[0] > 0
                assert pool.stats == {"positions": rows * p.pool_rows}


def test_engine_spans_match_the_request_records(traced):
    threads, records = traced
    execute, = [n for roots in threads for r in roots for n in r.walk()
                if n.name == "engine.execute"]
    assert execute.stats == {
        "worker": records[0].worker, "rows": records[0].padded_size,
        "real": records[0].batch_size,
        "first_rid": min(r.rid for r in records)}
    assert {(r.padded_size, r.batch_size) for r in records} == {(4, 3)}
    worker, = [roots for roots in threads
               if any(r.name == "engine.execute" for r in roots)]
    assert "engine.batch_form" in {r.name for r in worker}
