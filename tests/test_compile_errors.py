"""Typed compiler diagnostics (certification-style traceability).

Unsupported shapes/strides/pool kinds in `compile_layer`/`compile_matmul`
must raise :class:`CompileError` — naming the layer and the violated
constraint — instead of bare asserts or anonymous ValueErrors.  The
``constraint`` identifiers are the stable, greppable part of the
contract; messages may be reworded freely.

Hypothesis-free: part of the tier-1 floor.
"""

import numpy as np
import pytest

from repro.core.errors import CompileError
from repro.core.gemm_compiler import (AluIndexedImmOp, AluPairOp,
                                      AluResidualOp, compile_matmul)
from repro.core.layer_compiler import LayerSpec, compile_layer
from repro.core import isa


def _raises(constraint, fn, *args, **kwargs):
    with pytest.raises(CompileError) as exc:
        fn(*args, **kwargs)
    err = exc.value
    assert err.constraint == constraint, \
        f"expected constraint {constraint!r}, got {err.constraint!r}"
    return err


def test_compile_error_is_a_value_error_and_names_the_layer():
    """Backwards compatibility (existing `except ValueError` call sites)
    + the traceability payload."""
    assert issubclass(CompileError, ValueError)
    err = _raises("conv-input-rank", compile_layer,
                  LayerSpec("c1", "conv", np.zeros((4, 2, 3, 3), np.int8)),
                  np.zeros((2, 8, 8), np.int8))
    assert err.layer == "c1"
    assert "c1" in str(err) and "conv-input-rank" in str(err)


def test_layer_shape_and_stride_diagnostics():
    w = np.zeros((4, 2, 3, 3), np.int8)
    t = np.zeros((1, 2, 8, 8), np.int8)
    _raises("conv-batch-one", compile_layer,
            LayerSpec("c", "conv", w), np.zeros((2, 2, 8, 8), np.int8))
    _raises("conv-weight-rank", compile_layer,
            LayerSpec("c", "conv", np.zeros((4, 18), np.int8)), t)
    _raises("conv-stride", compile_layer,
            LayerSpec("c", "conv", w, stride=0), t)
    _raises("conv-padding", compile_layer,
            LayerSpec("c", "conv", w, padding=-1), t)
    _raises("conv-channels", compile_layer,
            LayerSpec("c", "conv", np.zeros((4, 3, 3, 3), np.int8)), t)
    _raises("conv-kernel-fit", compile_layer,
            LayerSpec("c", "conv", np.zeros((4, 2, 9, 9), np.int8)), t)
    _raises("fc-shape", compile_layer,
            LayerSpec("f", "fc", np.zeros((100, 10), np.int8)),
            np.zeros((1, 64), np.int8))
    _raises("fc-weight-rank", compile_layer,
            LayerSpec("f", "fc", np.zeros((100,), np.int8)),
            np.zeros((1, 100), np.int8))
    _raises("layer-kind", compile_layer,
            LayerSpec("x", "softmax", w), t)


def test_pool_diagnostics():
    w = np.zeros((4, 2, 3, 3), np.int8)
    t = np.zeros((1, 2, 8, 8), np.int8)
    _raises("pool-kind", compile_layer,
            LayerSpec("c", "conv", w, padding=1, pool="avg3x3"), t)
    _raises("pool-needs-conv", compile_layer,
            LayerSpec("f", "fc", np.zeros((128, 10), np.int8),
                      pool="avg2x2"), np.zeros((1, 128), np.int8))
    # valid conv output 7×7 (odd) cannot 2×2-pool
    _raises("pool-even-dims", compile_layer,
            LayerSpec("c", "conv", np.zeros((4, 2, 2, 2), np.int8),
                      pool="max2x2"), t)


def test_strided_geometry_diagnostics():
    """DESIGN.md §Strided-lowering: strides > 2 and stride-2 grids that
    silently drop input pixels must raise — never wrong bytes."""
    rng = np.random.default_rng(3)
    w3 = rng.integers(-4, 5, (4, 2, 3, 3)).astype(np.int8)
    w2 = rng.integers(-4, 5, (4, 2, 2, 2)).astype(np.int8)
    t8 = rng.integers(-16, 17, (1, 2, 8, 8)).astype(np.int8)
    t9 = rng.integers(-16, 17, (1, 2, 9, 9)).astype(np.int8)
    # stride values > 2 are outside the lowering's vocabulary
    _raises("conv-stride-max", compile_layer,
            LayerSpec("c", "conv", w3, stride=3, padding=1), t9)
    _raises("conv-stride-max", compile_layer,
            LayerSpec("c", "conv", w3, stride=4), t9)
    # stride-2 on odd spatial dims without padding: the k2 window grid
    # stops one pixel short of the input edge
    _raises("conv-stride-tiling", compile_layer,
            LayerSpec("c", "conv", w2, stride=2), t9)
    # valid (pad-0) k3/s2 on even dims also leaves a dropped column
    _raises("conv-stride-tiling", compile_layer,
            LayerSpec("c", "conv", w3, stride=2), t8)
    # the supported downsampling geometries compile: k3/s2/p1 halving,
    # the k2/s2 projection shortcut, and valid k3/s2 on odd dims
    for spec, t in ((LayerSpec("ok", "conv", w3, stride=2, padding=1), t8),
                    (LayerSpec("ok", "conv", w2, stride=2), t8),
                    (LayerSpec("ok", "conv", w3, stride=2), t9)):
        layer = compile_layer(spec, t)
        assert (layer.out_h, layer.out_w) == (4, 4)


def test_gap_geometry_diagnostics():
    """Global average pooling needs a square map, takes any position
    count (the ÷H·W beyond its power of two is the requant's scale) and
    never compiles a straddling tree."""
    rng = np.random.default_rng(4)
    w = rng.integers(-4, 5, (4, 2, 1, 1)).astype(np.int8)
    gap = lambda: LayerSpec("g", "conv", w, relu=True, pool="gap")
    _raises("gap-square", compile_layer, gap(),
            rng.integers(-16, 17, (1, 2, 8, 4)).astype(np.int8))
    # a 6×6 map (36 positions) sums every position, then SHR by 5
    t6 = rng.integers(-16, 17, (1, 2, 6, 6)).astype(np.int8)
    layer = compile_layer(gap(), t6)
    acc = np.maximum(np.einsum("chw,fc->fhw", t6[0].astype(np.int64),
                               w[:, :, 0, 0].astype(np.int64)), 0).sum((1, 2))
    np.testing.assert_array_equal(
        layer.ref_output_matrix[0],
        (acc >> (5 + layer.requant_shift)).astype(np.int8))
    # a GAP result too large for one ACC residency refuses to compile
    # (the tree's pair groups may not straddle chunks)
    from repro.core.hwconfig import VTAConfig
    tiny = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                     acc_buff_vectors=32, out_buff_vectors=64,
                     uop_buff_entries=64)
    _raises("alu-pair-group-chunk", compile_layer, gap(),
            rng.integers(-16, 17, (1, 2, 8, 8)).astype(np.int8), cfg=tiny)
    # GAP on fc raises like every pool kind
    _raises("pool-needs-conv", compile_layer,
            LayerSpec("f", "fc", np.zeros((16, 4), np.int8), pool="gap"),
            np.zeros((1, 16), np.int8))


def test_graph_builder_rejects_strided_geometry_early():
    """The graph front end applies the same constraints: stride > 2 at
    node construction, grid tiling at shape inference."""
    from repro.graph import GraphBuilder, infer_shapes
    rng = np.random.default_rng(5)
    w3 = rng.integers(-4, 5, (4, 2, 3, 3)).astype(np.int8)
    bld = GraphBuilder("bad")
    x = bld.input("x", shape=(1, 2, 8, 8))
    _raises("conv-stride-max", bld.conv, "c", x, w3, stride=3)
    v = bld.conv("c", x, w3, stride=2)             # valid k3/s2 on 8×8
    bld.output(v)
    _raises("conv-stride-tiling", infer_shapes, bld.build())

    bld2 = GraphBuilder("bad_gap")
    x = bld2.input("x", shape=(1, 2, 6, 4))
    v = bld2.conv("c", x, rng.integers(-4, 5, (4, 2, 1, 1)).astype(np.int8))
    v = bld2.global_avg_pool("g", v)
    bld2.output(v)
    _raises("gap-square", infer_shapes, bld2.build())


def test_requant_overflow_diagnostic():
    rng = np.random.default_rng(0)
    w = rng.integers(-6, 7, (4, 2, 3, 3)).astype(np.int8)
    t = rng.integers(-64, 65, (1, 2, 8, 8)).astype(np.int8)
    _raises("requant-int8-range", compile_layer,
            LayerSpec("c", "conv", w, requant_shift=0), t)


def test_matmul_diagnostics():
    rng = np.random.default_rng(1)
    A = rng.integers(-4, 5, (8, 6)).astype(np.int8)
    B = rng.integers(-4, 5, (6, 4)).astype(np.int8)
    _raises("gemm-shape", compile_matmul, A, B[:3])
    _raises("bias-xor-preload", compile_matmul, A, B,
            X=np.zeros((8, 4), np.int32), bias=np.zeros((4,), np.int32))
    _raises("alu-index-range", compile_matmul, A, B,
            alu_ops=[AluIndexedImmOp(isa.AluOp.SHR, 1, (10_000,))])
    _raises("alu-index-range", compile_matmul, A, B,
            alu_ops=[AluPairOp(isa.AluOp.ADD, ((0, 10_000),))])


def test_residual_pairing_diagnostics():
    rng = np.random.default_rng(2)
    A = rng.integers(-4, 5, (8, 6)).astype(np.int8)
    B = rng.integers(-4, 5, (6, 4)).astype(np.int8)
    R = np.zeros((8, 4), np.int32)
    _raises("residual-operand-op-pairing", compile_matmul, A, B, residual=R)
    _raises("residual-operand-op-pairing", compile_matmul, A, B,
            alu_ops=[AluResidualOp()])
    _raises("residual-shape", compile_matmul, A, B,
            alu_ops=[AluResidualOp()], residual=np.zeros((4, 8), np.int32))
    _raises("residual-single-op", compile_matmul, A, B,
            alu_ops=[AluResidualOp(), AluResidualOp()], residual=R)

    w = rng.integers(-4, 5, (4, 2, 3, 3)).astype(np.int8)
    t = rng.integers(-32, 33, (1, 2, 8, 8)).astype(np.int8)
    res_spec = LayerSpec("r", "conv", w, padding=1, requant_shift=8,
                         residual_add=True, residual_shift=1)
    _raises("residual-operand-missing", compile_layer, res_spec, t)
    _raises("residual-no-pool", compile_layer,
            LayerSpec("r", "conv", w, padding=1, pool="max2x2",
                      residual_add=True), t,
            residual=np.zeros((1, 4, 8, 8), np.int8))
    _raises("residual-unexpected-operand", compile_layer,
            LayerSpec("c", "conv", w, padding=1, requant_shift=8), t,
            residual=np.zeros((1, 4, 8, 8), np.int8))
    _raises("residual-shape", compile_layer, res_spec, t,
            residual=np.zeros((1, 4, 4, 4), np.int8))
