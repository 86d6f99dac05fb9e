"""ResNet-50 v1.5's mechanisms on the VTA, at small sizes on the CPU.

Each mechanism ResNet-50 brings (DESIGN.md §Strided-lowering) compiles
through ``compile_graph`` and serves bit-identical to the graph reference
(``evaluate_graph``) on every backend — the oracle and fast simulators
(``verify``), the batched interpreter and the pallas kernel (``serve``):

* the padded 3×3/s2 max pool, on even and odd maps, with buffers small
  enough that chunk boundaries fall between bands that share a halo row;
* 1×1/s2 convolutions (the projection shortcuts);
* a residual join feeding a global average pool over 3×3 and 7×7.

A whole ``build_resnet50(base_width=16, blocks=(1, 1, 1, 1),
image_size=96)`` then serves equal, bit for bit, to the plain float32
``reference_forward`` and to the benchmark's numpy reference; shapes the
lowering still refuses raise their named ``CompileError``.

Hypothesis-free: part of the tier-1 floor.
"""

import numpy as np
import pytest

from repro.core import isa
from repro.core.conv_lowering import conv2d_reference
from repro.core.errors import CompileError
from repro.core.hwconfig import VTAConfig
from repro.core.layer_compiler import LayerSpec, compile_layer
from repro.graph import GraphBuilder, compile_graph, evaluate_graph
from repro.models import resnet50 as r50

# buffers small enough that every mechanism below compiles to several
# chunks and streams its micro-ops in waves
TINY = VTAConfig(inp_buff_vectors=128, wgt_buff_matrices=64,
                 acc_buff_vectors=64, out_buff_vectors=64,
                 uop_buff_entries=256)


def _w(rng, *shape):
    return rng.integers(-4, 5, shape).astype(np.int8)


def _b(rng, n):
    return rng.integers(-32, 33, (n,)).astype(np.int32)


def _images(rng, shape, n=3):
    return [rng.integers(-64, 64, shape).astype(np.int8) for _ in range(n)]


def _compiled(graph, images, cfg=TINY):
    """Compile against the images themselves (so the graph reference
    never leaves int8), and check every backend against it."""
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


@pytest.mark.parametrize("hw", [(10, 10), (9, 11), (7, 7)])
def test_padded_max_pool_3x3_s2_across_chunk_boundaries(hw):
    rng = np.random.default_rng(sum(hw))
    h, w = hw
    bld = GraphBuilder("pool")
    x = bld.input("x", shape=(1, 3, h, w))
    v = bld.relu("c_r", bld.conv("c", x, _w(rng, 8, 3, 3, 3), _b(rng, 8),
                                 padding=1))
    v = bld.requant("c_q", bld.pool("c_pool", v, "max3x3s2"))
    bld.output(v)
    graph = bld.build()
    images = _images(rng, (1, 3, h, w))
    net = _compiled(graph, images)
    layer, = net.layers
    oh, ow = (h - 1) // 2 + 1, (w - 1) // 2 + 1
    assert (layer.out_h, layer.out_w) == (oh, ow)
    # one band of three image rows per output row: the GEMM computes the
    # halo rows twice, useful work counts each position once
    assert layer.input_matrix.shape[0] > h * w
    assert layer.macs == h * w * 27 * 8
    assert layer.n_chunks > 1
    maxes = [i for i in layer.program.instructions
             if isinstance(i, isa.AluInsn) and i.alu_opcode == isa.AluOp.MAX
             and not i.use_imm]
    assert maxes, "the pool must run as TensorAlu MAX pairs"
    # the max pool is exact against zero padding over a ReLU's output
    conv = np.maximum(conv2d_reference(images[1], graph.node("c").weights,
                                       pad=1)
                      + graph.node("c").bias[None, :, None, None], 0)
    padded = np.pad(conv, ((0, 0), (0, 0), (1, 1), (1, 1)))
    pooled = np.max([padded[:, :, i:i + 2 * oh:2, j:j + 2 * ow:2]
                     for i in range(3) for j in range(3)], axis=0)
    got, _ = net.serve(images, backend="batched")
    np.testing.assert_array_equal(
        got[1], (pooled >> graph.node("c_q").shift).astype(np.int8))


@pytest.mark.parametrize("size", [8, 9])
def test_1x1_stride_2_conv_reads_the_floor_grid(size):
    rng = np.random.default_rng(size)
    wts = _w(rng, 16, 4, 1, 1)
    bld = GraphBuilder("proj")
    x = bld.input("x", shape=(1, 4, size, size))
    v = bld.requant("p_q", bld.conv("p", x, wts, _b(rng, 16), stride=2))
    bld.output(v)
    graph = bld.build()
    images = _images(rng, (1, 4, size, size))
    net = _compiled(graph, images)
    out = (size - 1) // 2 + 1
    assert (net.layers[0].out_h, net.layers[0].out_w) == (out, out)
    direct = conv2d_reference(images[2][:, :, ::2, ::2], wts) \
        + graph.node("p").bias[None, :, None, None]
    got, _ = net.serve(images, backend="pallas")
    np.testing.assert_array_equal(
        got[2], (direct >> graph.node("p_q").shift).astype(np.int8))


@pytest.mark.parametrize("size", [3, 7])
def test_join_feeds_a_global_pool(size):
    """ResNet-50's last bottleneck: conv, requant, ADD of the skip, ReLU,
    then the global pool over a map whose position count is not a power
    of two — all in the join's one VTA layer."""
    rng = np.random.default_rng(size)
    c = 24
    bld = GraphBuilder("join_gap")
    x = bld.input("x", shape=(1, c, size, size))
    a = bld.requant("a_q", bld.relu("a_r", bld.conv(
        "a", x, _w(rng, c, c, 3, 3), _b(rng, c), padding=1)))
    b = bld.requant("b_q", bld.conv("b", a, _w(rng, c, c, 1, 1), _b(rng, c)))
    v = bld.relu("j_r", bld.add("j", b, x))
    v = bld.requant("j_q", bld.global_avg_pool("gap", v))
    v = bld.requant("fc_q", bld.fc("fc", bld.flatten("flat", v),
                                   _w(rng, c, 10), _b(rng, 10)))
    bld.output(v)
    graph = bld.build()
    # the tree spans every row, so the join's ACC windows (result and
    # skip) hold the whole map, one block column each: chunks split the
    # channels instead
    acc = 2 * 16 * -(-size * size // 16)
    cfg = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                    acc_buff_vectors=acc, out_buff_vectors=acc,
                    uop_buff_entries=256)
    net = _compiled(graph, _images(rng, (1, c, size, size)), cfg)
    join = net.layers[1]
    assert join.n_chunks > 1
    assert join.spec.residual_add and join.spec.pool == "gap"
    assert join.keep_rows == (0,) and (join.out_h, join.out_w) == (1, 1)
    ops = [type(op).__name__ for op in join.program.alu_ops]
    rounds = (size * size - 1).bit_length()
    assert ops == (["AluImmOp"] * (ops[0] == "AluImmOp") + ["AluResidualOp",
                   "AluImmOp"] + ["AluPairOp"] * rounds + ["AluIndexedImmOp"])
    # the division's power of two is in the post-add SHR
    shr = join.program.alu_ops[-1]
    assert shr.imm == graph.node("j_q").shift
    assert shr.imm >= (size * size).bit_length() - 1


def test_still_unsupported_shapes_raise_their_named_errors():
    rng = np.random.default_rng(7)
    t = rng.integers(-16, 17, (1, 2, 8, 8)).astype(np.int8)
    w3 = _w(rng, 4, 2, 3, 3)

    def raises(constraint, fn, *args, **kwargs):
        with pytest.raises(CompileError) as exc:
            fn(*args, **kwargs)
        assert exc.value.constraint == constraint

    # stride 3
    raises("conv-stride-max", compile_layer,
           LayerSpec("c", "conv", w3, stride=3, padding=1), t)
    # a padded 3×3/s2 max pool over a map with no ReLU before it
    raises("maxpool3x3-needs-relu", compile_layer,
           LayerSpec("c", "conv", w3, padding=1, pool="max3x3s2"), t)
    bld = GraphBuilder("no_relu")
    x = bld.input("x", shape=(1, 2, 8, 8))
    v = bld.requant("q", bld.pool("p", bld.conv("c", x, w3, padding=1),
                                  "max3x3s2"))
    bld.output(v)
    raises("maxpool3x3-needs-relu", compile_graph, bld.build(), t)
    # a non-square global pool, after a join as anywhere
    bld = GraphBuilder("gap_rect")
    x = bld.input("x", shape=(1, 2, 8, 4))
    b = bld.requant("b_q", bld.conv("b", x, _w(rng, 2, 2, 1, 1)))
    v = bld.requant("j_q", bld.global_avg_pool("g", bld.add("j", b, x)))
    bld.output(v)
    with pytest.raises(CompileError) as exc:
        compile_graph(bld.build(), rng.integers(-8, 9, (1, 2, 8, 4))
                      .astype(np.int8))
    assert exc.value.constraint == "gap-square"
    # a pool other than the global one cannot close a join
    raises("residual-no-pool", compile_layer,
           LayerSpec("r", "conv", w3, padding=1, relu=True,
                     pool="max3x3s2", residual_add=True), t,
           residual=np.zeros((1, 4, 8, 8), np.int8))
    # a kernel as wide as its stride still may not drop real input at
    # the edge alone (k3/s2 valid on an even map)
    raises("conv-stride-tiling", compile_layer,
           LayerSpec("c", "conv", w3, stride=2), t)


# ---------------------------------------------------------------------------
# The whole net, small
# ---------------------------------------------------------------------------

SMALL = dict(base_width=16, blocks=(1, 1, 1, 1), image_size=96)


@pytest.fixture(scope="module")
def small_net():
    weights = r50.resnet50_random_weights(0, **SMALL)
    net, graph = r50.compile_resnet50(weights, **SMALL)
    # seed 0 is a calibration image of compile_resnet50; 12 and 13 not
    images = np.concatenate([r50.synthetic_image(s, SMALL["image_size"])
                             for s in (0, 12, 13)])
    return net, graph, weights, images


def test_small_resnet50_topology(small_net):
    net, _, _, _ = small_net
    names = [layer.spec.name for layer in net.layers]
    assert len(names) == 1 + 4 * 3 + 4 + 1 == len(r50.linear_layers(**SMALL))
    assert net.layers[0].spec.pool == "max3x3s2"
    assert (net.layers[0].out_h, net.layers[0].out_w) == (24, 24)
    projections = {l.spec.name: (l.spec.stride, l.spec.weights.shape[2:])
                   for l in net.layers if l.spec.name.endswith("p")}
    assert projections == {"s1b1p": (1, (1, 1)), "s2b1p": (2, (1, 1)),
                           "s3b1p": (2, (1, 1)), "s4b1p": (2, (1, 1))}
    last = net.layers[-2]
    assert last.spec.name == "s4b1c" and last.spec.residual_add
    assert last.spec.pool == "gap"             # over 3×3 at this size
    assert sum(r is not None for r in net.residual_sources) == 4


def test_small_resnet50_serves_equal_to_both_references(small_net):
    """pallas (interpreted) and batched serving, the float32 jax reference
    and the benchmark's numpy reference agree bit for bit on 3 images."""
    from bench.configs import resnet50_ref
    net, graph, weights, images = small_net
    shifts = r50.graph_shifts(graph)
    want = r50.reference_forward(weights, images, shifts)
    assert len({bytes(w) for w in want}) == 3       # the logits differ
    for backend in ("batched", "pallas"):
        got, _ = net.serve(images, backend=backend)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=backend)
    cfg = {"layers": r50.linear_layers(**SMALL),
           "requant_shifts": {k: v for k, v in shifts.items()
                              if not isinstance(v, tuple)},
           "join_pre_shifts": {k: list(v) for k, v in shifts.items()
                               if isinstance(v, tuple)}}
    np.testing.assert_array_equal(
        resnet50_ref.forward(cfg, weights, images), want)
    # over a calibration image the graph evaluation is the same again
    np.testing.assert_array_equal(
        want[0], evaluate_graph(graph, images[:1])[graph.outputs[0]])
