"""``serve(backend="pallas")`` staged on the device, on the CPU.

The kernel's jitted call builds A from the layer's int8 activations
(``ops.stage_rows``: zero padding, im2row by unit-stride slices, the
3×3/s2 max pool's band gather, block padding).  Here, through the
interpreted kernel, that A is checked bit for bit against the host's
``im2row_batch`` → ``gather_rows`` → zero pad at every lowering serving
meets; whole pallas serves are checked bit for bit against the batched
interpreter at rung 1, a partial rung and a full rung, and so are small
networks of the lowering forms the conformance fuzz draws (stride-2
convs, the GAP tree, a residual join, pools under a tiny SRAM); the host
epilogue (``apply_alu_epilogue``) is checked against
``gemm_compiler.reference_result`` over random ALU programs with an X
preload and a residual operand; and a pallas serve is checked to build no
DRAM stack and to put each program's constants on the device once.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa, pallas_backend
from repro.core.conv_lowering import gather_rows, im2row_batch
from repro.core.gemm_compiler import (AluImmOp, AluResidualOp, _wrap_int32,
                                      compile_matmul, reference_result)
from repro.core.hwconfig import VTAConfig, vta_default
from repro.core.layer_compiler import LayerSpec, compile_layer
from repro.core.layout import truncate_int8
from repro.core.network_compiler import NetworkProgram, compile_network
from repro.core.pallas_backend import (apply_alu_epilogue, kernel_call,
                                       plan_pallas, serving_lowering)
from repro.graph import GraphBuilder, compile_graph
from repro.kernels import ops
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                synthetic_digit)

BATCH = 3
# name: (input shape, weights shape (F, C, kh, kw) or (D, F), stride,
# padding, pool)
LOWERINGS = {
    "k3s1p1": ((1, 4, 9, 9), (8, 4, 3, 3), 1, 1, None),
    "k3s2p1": ((1, 4, 9, 9), (8, 4, 3, 3), 2, 1, None),
    "k7s2p3": ((1, 3, 17, 17), (8, 3, 7, 7), 2, 3, None),
    "k1s1": ((1, 8, 6, 6), (16, 8, 1, 1), 1, 0, None),
    "k1s2": ((1, 8, 7, 7), (16, 8, 1, 1), 2, 0, None),
    "k2s2": ((1, 4, 8, 8), (8, 4, 2, 2), 2, 0, None),
    "max3x3s2_bands": ((1, 3, 11, 11), (8, 3, 3, 3), 1, 1, "max3x3s2"),
    "fc_flatten": ((1, 4, 3, 3), (36, 10), 1, 0, None),
    "fc_single_row": ((1, 40), (40, 10), 1, 0, None),
}


def _layer(name):
    shape, wshape, stride, pad, pool = LOWERINGS[name]
    rng = np.random.default_rng(len(name))
    kind = "conv" if len(wshape) == 4 else "fc"
    spec = LayerSpec(name=name, kind=kind,
                     weights=rng.integers(-3, 4, wshape).astype(np.int8),
                     stride=stride, padding=pad, relu=pool is not None,
                     pool=pool)
    inp = rng.integers(-64, 64, shape).astype(np.int8)
    return compile_layer(spec, inp)


@pytest.mark.parametrize("name", sorted(LOWERINGS))
def test_device_staged_a_is_host_im2row_gather_and_pad(name):
    layer = _layer(name)
    p = plan_pallas(layer.program)
    rows, cols = p.padded_shape[0], p.lam * p.block_size
    call = kernel_call(layer, BATCH)
    assert (call.rows, call.k) == (rows, cols)
    rng = np.random.default_rng(7)
    x = rng.integers(-128, 128, call.x, dtype=np.int8)
    if layer.spec.kind == "conv":
        _, _, kh, kw = layer.spec.weights.shape
        want = im2row_batch(x, kh, kw, layer.spec.stride, layer.spec.padding)
        gather = serving_lowering(layer).gather
        if gather is not None:
            assert (gather < 0).any()          # bands padded with zero rows
            want = gather_rows(want, gather)
    else:
        want = x.reshape(BATCH, 1, -1)
        assert rows == 1                       # a single row stays unpadded
    assert want.shape[1:] == layer.input_matrix.shape
    padded = np.zeros((BATCH, rows, cols), np.int8)
    padded[:, :want.shape[1], :want.shape[2]] = want
    # A through the interpreted kernel: A @ I in int32 is A
    got = ops.staged_vta_matmul(
        jnp.asarray(x), None if call.gather == 0 else jnp.asarray(gather),
        jnp.eye(cols, dtype=jnp.int8), None, conv=call.conv, rows=rows,
        cols=cols, relu=False, shift=0, saturate=False,
        out_dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(got),
                                  padded.reshape(BATCH * rows, cols))


@pytest.fixture(scope="module")
def nets(chip_smoke):
    from repro.models import resnet50 as r50
    small, _ = r50.compile_resnet50(base_width=16, blocks=(1, 1, 1, 1),
                                    image_size=64)
    images = [r50.synthetic_image(10 + i, 64) for i in range(4)]
    lenet, lenet_images, _ = chip_smoke.lenet5()
    resnet8, resnet8_images, _ = chip_smoke.resnet8()
    return {"lenet5": (lenet, lenet_images[:4]),
            "resnet8": (resnet8, resnet8_images[:4]),
            "resnet50_small": (small, images)}


@pytest.mark.parametrize("rung", (1, 3, 4))
@pytest.mark.parametrize("net_name", ("lenet5", "resnet8", "resnet50_small"))
def test_pallas_serve_is_the_batched_interpreter_bit_for_bit(net_name, rung,
                                                             nets):
    """The small ResNet-50 brings the padded 3×3/s2 max pool, 1×1/s2
    projections and a join feeding the GAP; rung 3 is a partial rung of
    the engine's ladder of 4."""
    net, images = nets[net_name]
    got, reports = net.serve(images[:rung], backend="pallas")
    want, want_reports = net.serve(images[:rung], backend="batched")
    np.testing.assert_array_equal(got, want)
    assert [r.gemm_loops for r in reports] == \
        [r.gemm_loops for r in want_reports]


def _conv(rng, name, c, f, k, **kw):
    return LayerSpec(name, "conv",
                     rng.integers(-8, 8, (f, c, k, k)).astype(np.int8),
                     rng.integers(-100, 100, (f,)).astype(np.int32), **kw)


def _residual_join(rng, c=8, hw=8):
    """conv+ReLU, conv, on-VTA ADD of the block's input, ReLU: a join."""
    w = lambda *shape: rng.integers(-4, 5, shape).astype(np.int8)
    b = lambda n: rng.integers(-32, 33, (n,)).astype(np.int32)
    bld = GraphBuilder("join")
    x = bld.input("x", shape=(1, c, hw, hw))
    v = bld.requant("a_q", bld.relu("a_r", bld.conv(
        "a", x, w(c, c, 3, 3), b(c), padding=1)))
    v = bld.requant("b_q", bld.conv("b", v, w(c, c, 3, 3), b(c), padding=1))
    v = bld.requant("j_q", bld.relu("j_r", bld.add("j", v, x)))
    bld.output(v)
    images = [rng.integers(-64, 64, (1, c, hw, hw)).astype(np.int8)
              for _ in range(4)]
    return compile_graph(bld.build(), images[0], calib=images)


# the forms the conformance fuzz draws, as small networks
_POOL_CFG = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                      acc_buff_vectors=128, out_buff_vectors=128,
                      uop_buff_entries=256)
_GAP_CFG = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                     acc_buff_vectors=64, out_buff_vectors=64,
                     uop_buff_entries=32)
def _one_layer(spec, shape, cfg=None):
    """A one-layer network, compiled against an image drawn from the
    layer's own weights' generator."""
    def make(rng):
        image = rng.integers(-32, 64, shape).astype(np.int8)
        return compile_network([spec(rng)], image, cfg=cfg)
    return make


FORMS = {
    "conv_k3s2p1": _one_layer(lambda rng: _conv(
        rng, "s2", 3, 6, 3, stride=2, padding=1, relu=True), (1, 3, 12, 12)),
    "proj_k2s2": _one_layer(lambda rng: _conv(rng, "p2", 4, 8, 2, stride=2),
                            (1, 4, 16, 16)),
    "gap_tree": _one_layer(lambda rng: _conv(
        rng, "gap", 3, 7, 1, relu=True, pool="gap"), (1, 3, 8, 8)),
    # β-chunked under the tiny ACC: the tree pins α into one chunk
    "gap_beta_chunked": _one_layer(lambda rng: _conv(
        rng, "gapc", 3, 70, 1, pool="gap"), (1, 3, 4, 4), cfg=_GAP_CFG),
    "max2x2_multi_chunk": _one_layer(lambda rng: _conv(
        rng, "mp", 3, 8, 3, padding=1, relu=True, pool="max2x2"),
        (1, 3, 12, 12), cfg=_POOL_CFG),
    "residual_join": _residual_join,
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_pallas_serve_of_each_lowering_form_is_the_batched_interpreter(form):
    rng = np.random.default_rng(len(form))
    net = FORMS[form](rng)
    shape, _ = net.input_signature()
    images = [rng.integers(-64, 64, shape).astype(np.int8) for _ in range(3)]
    got, reports = net.serve(images, backend="pallas")
    want, want_reports = net.serve(images, backend="batched")
    np.testing.assert_array_equal(got, want)
    assert [r.gemm_loops for r in reports] == \
        [r.gemm_loops for r in want_reports]
    if form in ("gap_beta_chunked", "max2x2_multi_chunk"):
        assert net.layers[0].n_chunks > 1
    if form == "residual_join":
        assert any(layer.spec.residual_add for layer in net.layers)


def _random_alu_program(rng):
    """The conformance fuzz's immediate ops (ReLU, ADD, MIN, SHR), with a
    residual merge (ADD/MIN/MAX of the second operand, maybe pre-shifted)
    at a random place among them."""
    ops = []
    if rng.random() < 0.5:
        ops.append(AluImmOp.relu())
    if rng.random() < 0.5:
        ops.append(AluImmOp(isa.AluOp.ADD, int(rng.integers(-200, 200))))
    if rng.random() < 0.4:
        ops.append(AluImmOp(isa.AluOp.MIN, int(rng.integers(0, 128))))
    if rng.random() < 0.5:
        ops.append(AluImmOp.shr(int(rng.integers(1, 8))))
    op = (isa.AluOp.ADD, isa.AluOp.MIN, isa.AluOp.MAX)[rng.integers(3)]
    ops.insert(int(rng.integers(len(ops) + 1)),
               AluResidualOp(op, int(rng.integers(0, 4))))
    return ops


@pytest.mark.parametrize("seed", range(6))
def test_host_epilogue_is_the_compiler_reference_on_random_alu(seed):
    """``apply_alu_epilogue`` over ``A·B + X`` with a residual operand, in
    int32, equals ``reference_result``'s accumulator, and its truncation
    the reference's committed int8."""
    rng = np.random.default_rng(400 + seed)
    m, k, n = (int(rng.integers(1, 50)) for _ in range(3))
    A = rng.integers(-128, 128, (m, k)).astype(np.int8)
    B = rng.integers(-128, 128, (k, n)).astype(np.int8)
    X = rng.integers(-10**6, 10**6, (m, n)).astype(np.int32)
    R = rng.integers(-10**6, 10**6, (m, n)).astype(np.int32)
    ops = _random_alu_program(rng)
    cfg = vta_default()
    p = plan_pallas(compile_matmul(A, B, X=X, alu_ops=ops, residual=R,
                                   cfg=cfg))
    (mp, np_), kp = p.padded_shape, p.lam * p.block_size

    def padded(mat, shape, dtype):
        out = np.zeros(shape, dtype)
        out[:mat.shape[0], :mat.shape[1]] = mat
        return out

    acc = padded(A, (mp, kp), np.int64) @ padded(B, (kp, np_), np.int64)
    acc = _wrap_int32(acc + padded(X, (mp, np_), np.int64))
    res_vec = pallas_backend._to_vectors(padded(R, (mp, np_), np.int32)[None],
                                         p)
    got = pallas_backend._to_matrix(apply_alu_epilogue(
        pallas_backend._to_vectors(acc[None], p), p, res_vec), p)[0]
    want_acc, want_out = reference_result(A, B, X, ops, cfg,
                                          row_height=p.row_height,
                                          residual=R)
    np.testing.assert_array_equal(got, want_acc)
    np.testing.assert_array_equal(truncate_int8(got), want_out)


def _lenet():
    return compile_network(lenet5_specs(lenet5_random_weights(0)),
                           synthetic_digit(0))


def _record_spans(monkeypatch):
    names = []
    real = pallas_backend.span

    def recording(name, **stats):
        names.append(name)
        return real(name, **stats)

    monkeypatch.setattr(pallas_backend, "span", recording)
    return names


def test_pallas_serve_builds_no_dram_stack_and_puts_constants_once(
        monkeypatch):
    net = _lenet()
    images = [synthetic_digit(i) for i in range(4)]

    def no_stack(self, rows):
        raise AssertionError("a pallas serve built a DRAM stack")

    monkeypatch.setattr(NetworkProgram, "_fresh_stack", no_stack)
    names = _record_spans(monkeypatch)
    first, _ = net.serve(images, backend="pallas")
    assert names.count("vta.const.put") == len(net.layers)
    assert "vta.decode" not in names and "vta.encode" not in names
    names.clear()
    second, _ = net.serve(images[:2], backend="pallas")
    assert "vta.const.put" not in names
    assert names.count("vta.kernel.put") == len(net.layers)
    np.testing.assert_array_equal(second, first[:2])


def test_two_threads_share_one_upload(monkeypatch):
    """Serving threads share a network: its constants go up once."""
    net = _lenet()
    names = _record_spans(monkeypatch)
    images = [synthetic_digit(i) for i in range(2)]
    outs = [None, None]
    start = threading.Barrier(2)

    def serve(i):
        start.wait()
        outs[i] = net.serve(images, backend="pallas")[0]

    threads = [threading.Thread(target=serve, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert names.count("vta.const.put") == len(net.layers)
    np.testing.assert_array_equal(outs[0], outs[1])
    consts = pallas_backend.serving_constants(net.layers[0])
    assert all(isinstance(c, jax.Array) for c in consts if c is not None)


def test_pallas_serve_refuses_a_shape_it_was_not_compiled_for():
    net = _lenet()
    with pytest.raises(ValueError, match="compiled geometry"):
        net.serve([np.zeros((1, 1, 28, 28), np.int8)], backend="pallas")
