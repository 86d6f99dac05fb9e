"""``serve(backend="pallas")`` staged on the device, on the CPU.

The kernel's jitted call builds A from the layer's int8 activations
(``ops.stage_rows``: zero padding, im2row by unit-stride slices, the
3×3/s2 max pool's band gather, block padding).  Here, through the
interpreted kernel, that A is checked bit for bit against the host's
``im2row_batch`` → ``gather_rows`` → zero pad at every lowering serving
meets; whole pallas serves are checked bit for bit against the batched
interpreter at rung 1, a partial rung and a full rung, and so are small
networks of the lowering forms the conformance fuzz draws (stride-2
convs, the GAP tree, a residual join, pools under a tiny SRAM); the
device epilogue (``ops.alu_epilogue`` over ``pallas_backend.device_alu``'s
steps and tables, then ``ops.commit_int8``) is checked against
``gemm_compiler.reference_result`` over random ALU programs with an X
preload and a residual operand and at the edges of its semantics (shifts
of 32 and more, int32 wraparound, every residual op, indexed ops, pair
lattices by passes, by whole rows and in pair order); and a pallas serve
is checked to fetch only committed int8 rows, to build no DRAM stack and
to put each program's constants on the device once.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa, pallas_backend
from repro.core.conv_lowering import gather_rows, im2row_batch
from repro.core.gemm_compiler import (AluImmOp, AluIndexedImmOp, AluPairOp,
                                      AluResidualOp, _wrap_int32,
                                      compile_matmul, pair_lattice,
                                      reference_result)
from repro.core.hwconfig import VTAConfig, vta_default
from repro.core.layer_compiler import LayerSpec, _vec_index, compile_layer
from repro.core.network_compiler import NetworkProgram, compile_network
from repro.core.pallas_backend import (device_alu, kernel_call, plan_pallas,
                                       serving_lowering)
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


def _padded(mat, shape, dtype):
    out = np.zeros(shape, dtype)
    out[:mat.shape[0], :mat.shape[1]] = mat
    return out


def _device_epilogue_is_the_reference(A, B, X, R, alu_ops, p):
    """``A·B`` (wrapped to int32) through the device epilogue with ``X``
    as the preload and ``R`` as the skip operand equals
    ``reference_result``'s accumulator, and its commit the reference's
    int8."""
    (mp, np_), kp = p.padded_shape, p.lam * p.block_size
    acc = _wrap_int32(_padded(A, (mp, kp), np.int64)
                      @ _padded(B, (kp, np_), np.int64))
    steps, tables = device_alu(p)
    got = ops.alu_epilogue(
        jnp.asarray(acc[None]),
        None if X is None else jnp.asarray(_padded(X, (mp, np_), np.int32)),
        None if R is None else jnp.asarray(R[None]),
        tuple(jnp.asarray(t) for t in tables), steps=steps)
    want_acc, want_out = reference_result(A, B, X, alu_ops, vta_default(),
                                          row_height=p.row_height,
                                          residual=R)
    np.testing.assert_array_equal(np.asarray(got[0]), want_acc)
    np.testing.assert_array_equal(np.asarray(ops.commit_int8(got)[0]),
                                  want_out)
    return steps


@pytest.mark.parametrize("seed", range(6))
def test_host_epilogue_is_the_compiler_reference_on_random_alu(seed):
    """The epilogue the kernel's jitted call runs (``ops.alu_epilogue``)
    over ``A·B + X`` with a residual operand, in int32, equals
    ``reference_result``'s accumulator, and its commit the reference's
    committed int8."""
    rng = np.random.default_rng(400 + seed)
    m, k, n = (int(rng.integers(1, 50)) for _ in range(3))
    A = rng.integers(-128, 128, (m, k)).astype(np.int8)
    B = rng.integers(-128, 128, (k, n)).astype(np.int8)
    X = rng.integers(-10**6, 10**6, (m, n)).astype(np.int32)
    R = rng.integers(-10**6, 10**6, (m, n)).astype(np.int32)
    alu_ops = _random_alu_program(rng)
    p = plan_pallas(compile_matmul(A, B, X=X, alu_ops=alu_ops, residual=R,
                                   cfg=vta_default()))
    _device_epilogue_is_the_reference(A, B, X, R, alu_ops, p)


# 40×20 @ 20×40: α = 3 block rows of 16, β = 3 block columns
_M, _K, _N = 40, 20, 40
_BETA, _RH = 3, 16
_S = isa.AluOp


def _rows(pairs):
    """Row pairs as the vector pairs of every block column (a pool's)."""
    return tuple((_vec_index(d, j, _BETA, _RH), _vec_index(s, j, _BETA, _RH))
                 for d, s in pairs for j in range(_BETA))


# name: (ALU program, X scale, residual, the steps' kinds and lanes)
EDGES = {
    "shr_by_31_32_40": ([AluImmOp(_S.ADD, 7), AluImmOp(_S.SHR, 32),
                         AluImmOp(_S.ADD, -3), AluImmOp(_S.SHR, 40)],
                        2**30, False, None),
    "shr_by_31": ([AluImmOp(_S.SHR, 31)], 2**30, False, None),
    "add_wraps_at_2_31": ([AluImmOp(_S.ADD, 2**31 - 1),
                           AluImmOp(_S.ADD, -2**31),
                           AluImmOp(_S.ADD, 2**31 + 5)], 2**30, False, None),
    "min_max_past_int32": ([AluImmOp(_S.MIN, 2**31 + 9),
                            AluImmOp(_S.MAX, -2**31 - 9),
                            AluImmOp(_S.MAX, 2**32 + 3)], 2**30, False,
                           None),
    "residual_add_pre_shift": ([AluResidualOp(_S.ADD, 3)], 2**31 - 2**20,
                               True, None),
    "residual_min_pre_shift": ([AluResidualOp(_S.MIN, 2)], 2**20, True,
                               None),
    "residual_max_pre_shift": ([AluResidualOp(_S.MAX, 40)], 2**20, True,
                               None),
    "residual_shr_pre_shift": ([AluResidualOp(_S.SHR, 1)], 2**20, True,
                               None),
    "indexed_shr": ([AluIndexedImmOp(_S.SHR, 5, (0, 7, 7, 50, 143, 96))],
                    2**20, False, ("indexed", 16)),
    "indexed_shr_whole_rows": ([AluIndexedImmOp(
        _S.SHR, 3, tuple(i for i, _ in _rows([(2, 0), (40, 0)])))],
        2**20, False, ("indexed", 48)),
    # three vectors in a row, but across two result rows: not whole rows
    "indexed_shr_across_rows": ([AluIndexedImmOp(_S.SHR, 4, (
        _vec_index(0, 1, _BETA, _RH), _vec_index(0, 2, _BETA, _RH),
        _vec_index(1, 0, _BETA, _RH)))], 2**20, False, ("indexed", 16)),
    "max_lattice": ([AluPairOp(_S.MAX, ((0, 5), (0, 6), (0, 9), (1, 5),
                                        (48, 100), (48, 143)))],
                    2**20, False, ("pairs", 16)),
    # destinations fill row 0, sources come from rows 1 and 2: vectors
    "max_lattice_mixed_rows": ([AluPairOp(_S.MAX, tuple(
        (_vec_index(0, j, _BETA, _RH), _vec_index(2 if j == 1 else 1, j,
                                                  _BETA, _RH))
        for j in range(_BETA)))], 2**20, False, ("pairs", 16)),
    "add_lattice": ([AluPairOp(_S.ADD, ((3, 4), (3, 50), (60, 4),
                                        (143, 0)))],
                    2**31 - 2**20, False, ("pairs", 16)),
    "add_lattice_whole_rows": ([AluPairOp(_S.ADD, _rows(
        [(0, 1), (0, 2), (0, 3), (20, 21), (39, 21)])),
        AluIndexedImmOp(_S.SHR, 2, tuple(
            i for i, _ in _rows([(0, 0), (20, 0), (39, 0)])))],
        2**20, False, ("pairs", 48)),
    "sequential_lattice": ([AluPairOp(_S.ADD, ((1, 0), (2, 1), (3, 2),
                                               (3, 2), (50, 3)))],
                           2**28, False, ("pairs_in_order", 16)),
    "sequential_shr_lattice": ([AluPairOp(_S.SHR, ((4, 9), (4, 10)))],
                               2**20, False, ("pairs_in_order", 16)),
}


@pytest.mark.parametrize("name", sorted(EDGES))
def test_device_epilogue_at_the_edges_is_the_reference(name):
    alu_ops, scale, residual, form = EDGES[name]
    rng = np.random.default_rng(len(name))
    A = rng.integers(-128, 128, (_M, _K)).astype(np.int8)
    B = rng.integers(-128, 128, (_K, _N)).astype(np.int8)
    X = rng.integers(-scale, scale, (_M, _N), dtype=np.int64) \
        .astype(np.int32)
    R = (rng.integers(-2**31, 2**31, (_M, _N), dtype=np.int64)
         .astype(np.int32) if residual else None)
    p = dataclasses.replace(plan_pallas(compile_matmul(A, B)),
                            alu_ops=tuple(alu_ops))
    assert (p.alpha, p.beta, p.row_height) == (3, _BETA, _RH)
    for spec in alu_ops:
        if isinstance(spec, AluPairOp) and form[0] == "pairs_in_order":
            assert pair_lattice(spec.op, spec.pairs).sequential
    steps = _device_epilogue_is_the_reference(A, B, X, R, alu_ops, p)
    if form is not None:
        kind, lanes = form
        assert (steps[0].kind, steps[0].lanes) == (kind, lanes)


@pytest.mark.parametrize("net_name", ("lenet5", "resnet8"))
def test_pallas_serve_fetches_only_committed_rows(net_name, nets,
                                                  monkeypatch):
    """Pools and joins run inside the kernel's jitted call: every layer
    comes back as int8 at its valid shape (a pooled layer's kept rows
    only), the skip activations go up with the layer's own, and the
    serve is the batched interpreter's bit for bit."""
    net, images = nets[net_name]
    seen = []
    real = pallas_backend.serve_layer

    def spy(layer, x, res):
        out, report = real(layer, x, res)
        seen.append((layer, res is not None, out))
        return out, report

    monkeypatch.setattr(pallas_backend, "serve_layer", spy)
    got, _ = net.serve(images[:3], backend="pallas")
    want, _ = net.serve(images[:3], backend="batched")
    np.testing.assert_array_equal(got, want)
    kinds = set()
    for layer, joined, out in seen:
        m, n = layer.program.output_meta.valid_shape
        rows = m if layer.keep_rows is None else len(layer.keep_rows)
        assert out.dtype == np.int8 and out.shape == (3, rows, n)
        assert joined == layer.spec.residual_add
        call = kernel_call(layer, 3)
        kinds |= {step.kind for step in call.epilogue or ()}
    assert {"imm", "pairs", "indexed"} <= kinds
    assert ("residual" in kinds) == (net_name == "resnet8")


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
