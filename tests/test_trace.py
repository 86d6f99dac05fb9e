"""Program spans (``repro.trace``) on the CPU.

With no profiler session ``span`` is one shared null context.  Under
``jax.profiler.trace`` a lenet5 ``serve(backend="pallas")`` of 2 images
and one engine batch of 3 requests (padded to a rung of 4) record every
span of the serving path, nested on one thread as the layers call each
other, with the counts the benchmark's readers sum: ``issued_macs`` is
the padded ``vta_gemm`` call's ``gemm_blocks`` product, ``a_bytes`` the
A the device stages, ``alu_ops`` the TensorAlu ops the call runs after
the kernel, ``useful_macs`` the layer's valid M·K·N per row, ``bytes``
what each kernel call sends (the activations) and fetches (the committed
int8 rows), and ``rows``/``real``/
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
from repro.core.pallas_backend import kernel_call
from repro.kernels.ops import gemm_blocks
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                synthetic_digit)
from repro.serving.vta import (BatchPolicy, VTAServingEngine, request_images,
                               serve_all)

# parent -> the spans directly inside it, as the serving path nests them
NESTING = {
    "engine.execute": {"vta.serve"},
    "vta.serve": {"vta.layer"},
    "vta.layer": {"vta.stage", "vta.kernel", "vta.readout"},
    "vta.kernel": {"vta.kernel.put", "vta.kernel.dispatch",
                   "vta.kernel.fetch"},
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
                                      "a_bytes": call.m * call.k,
                                      "alu_ops": len(call.epilogue or ())}
            # the pooled layers' epilogues run in the call, the rest fuse
            assert (call.epilogue is None) == (layer.spec.pool is None)
            if call.epilogue is not None:
                assert len(call.epilogue) == len(layer.program.alu_ops)


def test_pool_positions_and_kernel_bytes(traced, lenet):
    """The pools run inside the kernel's jitted call, so no ``vta.pool``
    (nor ``vta.epilogue``) span is left; the kernel call's put counts the
    activations it sends (the weights, bias and epilogue tables are on
    the device already), its fetch the committed int8 result: each
    image's valid columns of the rows a pooled layer keeps."""
    threads, _ = traced
    serves = [n for roots in threads for r in roots for n in r.walk()
              if n.name == "vta.serve"]
    for serve in serves:
        rows = serve.stats["rows"]
        for layer_span, layer in zip(serve.children, lenet.layers):
            call = kernel_call(layer, rows)
            put, = [n for n in layer_span.walk()
                    if n.name == "vta.kernel.put"]
            fetch, = [n for n in layer_span.walk()
                      if n.name == "vta.kernel.fetch"]
            assert put.stats == {"bytes": math.prod(call.x)}
            m, n = call.valid
            kept = call.keep or m
            assert fetch.stats == {"bytes": rows * kept * n}
            if layer.spec.pool is not None:
                assert kept == len(layer.keep_rows) < m
            assert not [node for node in layer_span.walk()
                        if node.name in ("vta.pool", "vta.epilogue")]


def test_skip_activations_are_counted_in_the_put(tmp_path):
    """At a join the skip's int8 activations go up with the layer's own,
    in the same put, and its bytes count both."""
    import numpy as np
    from repro.graph import GraphBuilder, compile_graph
    rng = np.random.default_rng(3)
    w = lambda *shape: rng.integers(-4, 5, shape).astype(np.int8)
    bld = GraphBuilder("join")
    x = bld.input("x", shape=(1, 8, 6, 6))
    v = bld.requant("a_q", bld.conv("a", x, w(8, 8, 3, 3), padding=1))
    v = bld.requant("j_q", bld.relu("j_r", bld.add("j", v, x)))
    bld.output(v)
    images = [rng.integers(-64, 64, (1, 8, 6, 6)).astype(np.int8)
              for _ in range(2)]
    net = compile_graph(bld.build(), images[0], calib=images)
    net.serve(images, backend="pallas")
    with jax.profiler.trace(str(tmp_path)):
        net.serve(images, backend="pallas")
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    puts = [n for roots in _threads(path) for r in roots for n in r.walk()
            if n.name == "vta.kernel.put"]
    joins = [layer for layer in net.layers if layer.spec.residual_add]
    assert joins and len(puts) == len(net.layers)
    for put, layer in zip(puts, net.layers):
        call = kernel_call(layer, 2)
        skip = math.prod(call.res) if call.res else 0
        assert put.stats == {"bytes": math.prod(call.x) + skip}
        assert bool(skip) == layer.spec.residual_add


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


def test_depthwise_calls_record_their_own_span_inside_the_dispatch(tmp_path):
    """A depthwise layer's call (``vta_dwconv``) records
    ``vta.kernel.dwconv`` inside ``vta.kernel.dispatch``, with the layer's
    useful multiply-adds per row, what the kernel issues (channels padded
    to the 128 lanes; also the dispatch's ``issued_macs``) and the bytes
    it moves."""
    import numpy as np
    from repro.graph import GraphBuilder, compile_graph
    from repro.kernels.vta_dwconv import dw_blocks
    rng = np.random.default_rng(3)
    bld = GraphBuilder("dw")
    x = bld.input("x", shape=(1, 48, 9, 9))
    v = bld.requant("dw_q", bld.conv(
        "dw", x, rng.integers(-4, 5, (48, 1, 3, 3)).astype(np.int8),
        stride=2, padding=1, groups=48))
    bld.output(bld.relu6("dw_r6", v))
    images = [rng.integers(-64, 64, (1, 48, 9, 9)).astype(np.int8)
              for _ in range(3)]
    net = compile_graph(bld.build(), images[0], calib=images)
    net.serve(images, backend="pallas")
    with jax.profiler.trace(str(tmp_path)):
        net.serve(images, backend="pallas")
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    roots = [r for t in _threads(path) for r in t]
    dispatch, = [n for r in roots for n in r.walk()
                 if n.name == "vta.kernel.dispatch"]
    dw, = dispatch.children
    want = dw_blocks(3, 48, 9, 9, 3, 3, 2, 1)
    assert dw.name == "vta.kernel.dwconv"
    assert dw.stats["useful_macs"] == 3 * net.layers[0].macs \
        == 3 * 25 * 9 * 48
    assert dw.stats["issued_macs"] == dispatch.stats["issued_macs"] \
        == want.issued_macs == 3 * 25 * 9 * 128
    assert dw.stats["bytes"] == want.bytes
