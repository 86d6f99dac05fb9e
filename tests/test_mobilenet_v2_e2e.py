"""MobileNetV2's mechanisms on the VTA, at small sizes on the CPU.

Each mechanism MobileNetV2 brings (DESIGN.md §Graph) compiles through
``compile_graph`` and serves bit-identical to the graph reference
(``evaluate_graph``) on every backend — the oracle and fast simulators
(``verify``), the batched interpreter and the pallas path (``serve``,
where a depthwise conv runs ``vta_dwconv``):

* a depthwise 3×3 conv at strides 1 and 2, lowered as a grouped GEMM
  whose output blocks run only over their own channels' nine K blocks,
  with buffers small enough that it takes several chunks;
* ReLU6 as MAX 0 and MIN cap after the requant, its cap planned inside
  int8 and below what the layer gives;
* a linear-bottleneck join (no ReLU after it) and a global pool after a
  ReLU6.

A whole ``build_mobilenet_v2(width=0.5, image_size=32)`` — one block of
every kind — then serves equal, bit for bit, to the plain float32
``reference_forward`` and to the benchmark's numpy reference.

Hypothesis-free: part of the tier-1 floor.
"""

import json

import numpy as np
import pytest

from repro.core.conv_lowering import depthwise_conv
from repro.core.errors import CompileError
from repro.core.hwconfig import VTAConfig
from repro.core.layer_compiler import LayerSpec, compile_layer
from repro.graph import (GraphBuilder, compile_graph, evaluate_graph,
                         plan_requant)
from repro.models import mobilenet_v2 as mb

# buffers small enough that a depthwise layer compiles to several chunks
TINY = VTAConfig(inp_buff_vectors=512, wgt_buff_matrices=64,
                 acc_buff_vectors=64, out_buff_vectors=64,
                 uop_buff_entries=256)
SMALL = dict(width=0.5, image_size=32)


def _w(rng, *shape):
    return rng.integers(-4, 5, shape).astype(np.int8)


def _b(rng, n):
    return rng.integers(-32, 33, (n,)).astype(np.int32)


def _images(rng, shape, n=3):
    return [rng.integers(-64, 64, shape).astype(np.int8) for _ in range(n)]


def _compiled(graph, images, cfg=TINY):
    """Compile against the images themselves and check every backend
    against the graph reference."""
    net = compile_graph(graph, images[0], calib=images, cfg=cfg)
    want = np.stack([evaluate_graph(graph, img)[graph.outputs[0]]
                     for img in images]).astype(np.int8)
    for backend in ("oracle", "fast"):
        out, _ = net.verify(backend=backend)
        np.testing.assert_array_equal(out, want[0], err_msg=backend)
    for backend in ("batched", "pallas"):
        out, _ = net.serve(images, backend=backend)
        np.testing.assert_array_equal(np.asarray(out), want,
                                      err_msg=backend)
    return net


@pytest.mark.parametrize("channels, size, stride", [
    (16, 9, 1), (48, 10, 2), (32, 15, 2)])
def test_depthwise_conv_is_a_grouped_gemm_on_every_backend(channels, size,
                                                          stride):
    rng = np.random.default_rng(channels + size + stride)
    bld = GraphBuilder("dw")
    x = bld.input("x", shape=(1, channels, size, size))
    v = bld.requant("dw_q", bld.conv("dw", x, _w(rng, channels, 1, 3, 3),
                                     _b(rng, channels), stride=stride,
                                     padding=1, groups=channels))
    bld.output(bld.relu6("dw_r6", v))
    graph = bld.build()
    images = _images(rng, (1, channels, size, size))
    net = _compiled(graph, images)
    layer, = net.layers
    assert layer.n_chunks > 1
    out = (size - 1) // stride + 1
    # the layer's work is each channel's nine taps, not its band matrix's
    assert layer.macs == out * out * 9 * channels
    # issued: every block row runs nine K blocks against its own
    # column's nine weight blocks, never the other columns' zeros
    bs, plan = 16, layer.program.chunk_plan
    assert plan.band == 9 and plan.lam == 9 * plan.beta
    assert layer.program.gemm_loops() == plan.alpha * plan.row_height * 9 \
        * plan.beta
    assert layer.program.regions["wgt"].nbytes == 9 * plan.beta * bs * bs


def test_relu6_cap_is_planned_inside_int8_and_clips():
    rng = np.random.default_rng(6)
    bld = GraphBuilder("r6")
    x = bld.input("x", shape=(1, 3, 8, 8))
    v = bld.requant("c_q", bld.conv("c", x, _w(rng, 16, 3, 3, 3),
                                    _b(rng, 16), padding=1))
    bld.output(bld.relu6("c_r6", v))
    graph = bld.build()
    images = _images(rng, (1, 3, 8, 8))
    plan = plan_requant(graph, images)
    cap = plan.caps["c_r6"]
    e = cap.bit_length() - 3                   # cap = 6·2^e
    assert cap == 6 << e and 1 <= cap <= 127
    # the cap sits at the requant's scale, and below what it gives
    assert plan.exps["c_q"] == e
    top = max(int(evaluate_graph(graph, img)["c_q"].max()) for img in images)
    assert cap < top
    _compiled(graph, images)


def test_linear_join_and_a_global_pool_after_relu6():
    """expand → ReLU6 → depthwise → ReLU6 → projection joined with the
    block's input (no ReLU), then a 1×1 head with ReLU6 into a global
    pool and an fc: MobileNetV2's block and head."""
    rng = np.random.default_rng(7)
    bld = GraphBuilder("block")
    x = bld.input("x", shape=(1, 16, 6, 6))
    v = bld.relu6("ex_r6", bld.requant("ex_q", bld.conv(
        "ex", x, _w(rng, 32, 16, 1, 1), _b(rng, 32))))
    v = bld.relu6("dw_r6", bld.requant("dw_q", bld.conv(
        "dw", v, _w(rng, 32, 1, 3, 3), _b(rng, 32), padding=1, groups=32)))
    v = bld.requant("pw_q", bld.conv("pw", v, _w(rng, 16, 32, 1, 1),
                                     _b(rng, 16)))
    v = bld.requant("j_q", bld.add("j", v, x))
    v = bld.relu6("head_r6", bld.requant("head_q", bld.conv(
        "head", v, _w(rng, 48, 16, 1, 1), _b(rng, 48))))
    v = bld.requant("gap_q", bld.global_avg_pool("gap", v))
    v = bld.fc("fc", bld.flatten("flat", v), _w(rng, 48, 10), _b(rng, 10))
    bld.output(bld.requant("fc_q", v))
    graph = bld.build()
    net = _compiled(graph, _images(rng, (1, 16, 6, 6)))
    pw, head = net.layers[2], net.layers[3]
    assert pw.spec.residual_add and not pw.spec.relu
    assert head.spec.clip is not None and head.spec.pool == "gap"


def test_groups_other_than_one_or_the_channels_are_refused():
    rng = np.random.default_rng(8)
    bld = GraphBuilder("g")
    x = bld.input("x", shape=(1, 16, 6, 6))
    with pytest.raises(CompileError) as err:
        bld.conv("c", x, _w(rng, 16, 2, 3, 3), groups=8)
    assert err.value.constraint == "conv-groups"
    spec = LayerSpec("c", "conv", _w(rng, 16, 4, 3, 3), groups=4)
    with pytest.raises(CompileError) as err:
        compile_layer(spec, _images(rng, (1, 16, 6, 6), 1)[0])
    assert err.value.constraint == "conv-groups"
    # a depthwise conv lowers in whole blocks of channels
    spec = LayerSpec("c", "conv", _w(rng, 12, 1, 3, 3), groups=12,
                     padding=1)
    with pytest.raises(CompileError) as err:
        compile_layer(spec, _images(rng, (1, 12, 6, 6), 1)[0])
    assert err.value.constraint == "depthwise-channel-blocks"


def test_depthwise_conv_reference_is_a_direct_loop():
    rng = np.random.default_rng(9)
    x = rng.integers(-128, 128, (2, 5, 7, 7))
    w = rng.integers(-5, 6, (5, 1, 3, 3))
    got = depthwise_conv(x, w, stride=2, pad=1)
    xp = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    for c in range(5):
        for i in range(4):
            for j in range(4):
                want = (xp[:, c, 2 * i:2 * i + 3, 2 * j:2 * j + 3]
                        * w[c, 0]).sum(axis=(1, 2))
                np.testing.assert_array_equal(got[:, c, i, j], want)


@pytest.fixture(scope="module")
def small_net():
    net, graph = mb.compile_mobilenet_v2(**SMALL)
    return net, graph


def test_small_mobilenet_v2_topology(small_net):
    net, graph = small_net
    kinds = [n.kind for n in graph.nodes.values()]
    assert len(net.layers) == 53
    assert sum(layer.spec.groups > 1 for layer in net.layers) == 17
    assert kinds.count("relu6") == 35 and kinds.count("add") == 10
    joins = [layer for layer in net.layers if layer.spec.residual_add]
    assert len(joins) == 10 and not any(j.spec.relu for j in joins)
    # every kind of block: t=1, stride-2 and stride-1 depthwise, the head
    strides = {layer.spec.stride for layer in net.layers
               if layer.spec.groups > 1}
    assert strides == {1, 2}
    assert net.layers[-2].spec.pool == "gap" and net.layers[-2].spec.clip


def test_every_cap_clips_on_the_calibration_images(small_net):
    _, graph = small_net
    images = [mb.synthetic_image(s, SMALL["image_size"]) for s in (1, 2, 0)]
    vals = [evaluate_graph(graph, img) for img in images]
    for node in graph.nodes.values():
        if node.kind != "relu6":
            continue
        assert 1 <= node.cap <= 127
        top = max(int(v[node.inputs[0]].max()) for v in vals)
        assert top > node.cap, node.name


def test_small_mobilenet_v2_serves_equal_to_every_reference(small_net):
    from bench import layout
    net, graph = small_net
    images = np.concatenate([mb.synthetic_image(s, SMALL["image_size"])
                             for s in (5, 6, 7)])
    want = np.stack([evaluate_graph(graph, img[None])["fc_q"]
                     for img in images]).astype(np.int8)
    for backend in ("batched", "pallas"):
        out, _ = net.serve(images, backend=backend)
        np.testing.assert_array_equal(np.asarray(out), want,
                                      err_msg=backend)
    np.testing.assert_array_equal(net.serve_one(images[:1],
                                                backend="oracle"), want[0])
    weights = mb.mobilenet_v2_random_weights(**SMALL)
    params = mb.graph_params(graph)
    np.testing.assert_array_equal(
        mb.reference_forward(weights, images, params, width=0.5), want)
    # the benchmark's numpy reference, on this geometry and these pins
    ref = layout.load_module(layout.BENCH / "configs" / "mobilenetv2_ref.py")
    cfg = json.loads((layout.BENCH / "configs" / "mobilenetv2.json")
                     .read_text())
    cfg.update(layers=mb.linear_layers(**SMALL),
               requant_shifts={k: v for k, v in params.items()
                               if k.endswith("_q")},
               join_pre_shifts={k: v for k, v in params.items()
                                if k.endswith("_join")},
               relu6_caps={k: v for k, v in params.items()
                           if k.endswith("_r6")})
    np.testing.assert_array_equal(ref.forward(cfg, weights, images), want)
