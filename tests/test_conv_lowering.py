"""Tensor→matrix lowering tests (paper §4.1, Def. 3)."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dependency
from hypothesis import given, settings, strategies as st

from repro.core.conv_lowering import (ConvGeometry, avgpool2x2_plan,
                                      conv2d_reference, global_avgpool_plan,
                                      im2row, im2row_batch, ker2col,
                                      mat2tensor, maxpool2x2_plan,
                                      tensor2mat, flatten_tensor)


def test_lenet_layer1_shapes_verbatim():
    """§4.3: (1,1,32,32) with 5×5 kernels → 784×25 input matrix."""
    t = np.zeros((1, 1, 32, 32), dtype=np.int8)
    A = im2row(t, 5, 5)
    assert A.shape == (784, 25)
    w = np.zeros((6, 1, 5, 5), dtype=np.int8)
    B = ker2col(w)
    assert B.shape == (25, 6)
    # output 784×6 → tensor (1,6,28,28); after pooling 196×6 → (1,6,14,14)
    C = np.zeros((784, 6), dtype=np.int8)
    assert mat2tensor(C, 28, 28).shape == (1, 6, 28, 28)
    assert mat2tensor(np.zeros((196, 6), np.int8), 14, 14).shape == (1, 6, 14, 14)


@given(c=st.integers(1, 4), h=st.integers(3, 12), w=st.integers(3, 12),
       f=st.integers(1, 5), k=st.integers(1, 3), stride=st.integers(1, 2),
       seed=st.integers(0, 2**16))
@settings(max_examples=60, deadline=None)
def test_def3_property(c, h, w, f, k, stride, seed):
    """Def. 3: mat2tensor(im2row(T_A) × ker2col(T_B)) == T_A ⊛ T_B."""
    if k > min(h, w):
        k = min(h, w)
    rng = np.random.default_rng(seed)
    T_A = rng.integers(-64, 64, (1, c, h, w), dtype=np.int64).astype(np.int8)
    T_B = rng.integers(-64, 64, (f, c, k, k), dtype=np.int64).astype(np.int8)
    A = im2row(T_A, k, k, stride)
    B = ker2col(T_B)
    C = A.astype(np.int64) @ B.astype(np.int64)
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    T_C = mat2tensor(C, oh, ow)
    np.testing.assert_array_equal(T_C, conv2d_reference(T_A, T_B, stride))


@given(f=st.integers(1, 6), h=st.integers(1, 8), w=st.integers(1, 8),
       seed=st.integers(0, 1000))
@settings(max_examples=40)
def test_mat2tensor_tensor2mat_inverse(f, h, w, seed):
    rng = np.random.default_rng(seed)
    m = rng.integers(-128, 128, (h * w, f), dtype=np.int64).astype(np.int8)
    np.testing.assert_array_equal(tensor2mat(mat2tensor(m, h, w)), m)


def test_flatten_is_nchw_order():
    t = np.arange(2 * 3 * 4, dtype=np.int8).reshape(1, 2, 3, 4)
    np.testing.assert_array_equal(flatten_tensor(t)[0], np.arange(24))


@given(c=st.integers(1, 4), h=st.integers(3, 10), w=st.integers(3, 10),
       f=st.integers(1, 5), k=st.integers(1, 3), stride=st.integers(1, 2),
       pad=st.integers(0, 2), seed=st.integers(0, 2**16))
@settings(max_examples=60, deadline=None)
def test_def3_property_with_padding(c, h, w, f, k, stride, pad, seed):
    """Def. 3 extended to zero-padded ("same") convolution: the padding is
    materialised host-side, so Def. 3 must keep holding verbatim."""
    rng = np.random.default_rng(seed)
    T_A = rng.integers(-64, 64, (1, c, h, w), dtype=np.int64).astype(np.int8)
    T_B = rng.integers(-64, 64, (f, c, k, k), dtype=np.int64).astype(np.int8)
    A = im2row(T_A, k, k, stride, pad)
    B = ker2col(T_B)
    C = A.astype(np.int64) @ B.astype(np.int64)
    geo = ConvGeometry(c, h, w, k, k, stride, pad)
    T_C = mat2tensor(C, geo.out_h, geo.out_w)
    np.testing.assert_array_equal(T_C, conv2d_reference(T_A, T_B, stride, pad))


def test_same_padding_preserves_spatial_dims():
    """pad=(k-1)//2 with stride 1 keeps H×W (the "same" convolutions the
    YOLO-class workloads need, DESIGN.md §3)."""
    for k in (1, 3, 5, 7):
        geo = ConvGeometry(3, 32, 32, k, k, 1, (k - 1) // 2)
        assert (geo.out_h, geo.out_w) == (32, 32)
    t = np.ones((1, 3, 32, 32), dtype=np.int8)
    assert im2row(t, 5, 5, 1, 2).shape == (1024, 75)


@given(b=st.integers(1, 5), c=st.integers(1, 4), h=st.integers(2, 10),
       w=st.integers(2, 10), kh=st.integers(1, 4), kw=st.integers(1, 4),
       stride=st.integers(1, 3), pad=st.integers(0, 3),
       seed=st.integers(0, 2**16))
@settings(max_examples=80, deadline=None)
def test_im2row_batch_equals_per_image_loop(b, c, h, w, kh, kw, stride, pad,
                                            seed):
    """``im2row_batch`` is elementwise-identical to looping ``im2row``
    over the images, across random strides / paddings / kernel sizes —
    the serving path's batched staging can never drift from the
    single-image lowering (closes the PR 3 coverage gap where only the
    e2e paths exercised it)."""
    if kh > h + 2 * pad:
        kh = h + 2 * pad
    if kw > w + 2 * pad:
        kw = w + 2 * pad
    rng = np.random.default_rng(seed)
    stack = rng.integers(-128, 128, (b, c, h, w),
                         dtype=np.int64).astype(np.int8)
    batched = im2row_batch(stack, kh, kw, stride, pad)
    for i in range(b):
        single = im2row(stack[i:i + 1], kh, kw, stride, pad)
        np.testing.assert_array_equal(batched[i], single)
    geo = ConvGeometry(c, h, w, kh, kw, stride, pad)
    assert batched.shape == (b, geo.n_positions, geo.patch_len)


def test_maxpool_plan_mirrors_avgpool_geometry():
    avg = avgpool2x2_plan(4, 4)
    mx = maxpool2x2_plan(4, 4)
    assert mx.keep_rows == avg.keep_rows
    assert mx.add_pairs == avg.add_pairs      # same windows, MAX instead of ADD
    assert (mx.mode, avg.mode) == ("max", "avg")
    assert mx.out_h == mx.out_w == 2


def test_avgpool_plan_indices():
    plan = avgpool2x2_plan(4, 4)
    assert plan.out_h == plan.out_w == 2
    assert plan.keep_rows == (0, 2, 8, 10)
    # first window accumulates rows 1, 4, 5 into row 0
    assert plan.add_pairs[:3] == ((0, 1), (0, 4), (0, 5))
    assert plan.shr_indices == plan.keep_rows
    assert (plan.div_shift, maxpool2x2_plan(4, 4).div_shift) == (2, 0)


def test_global_avgpool_plan_tree_structure():
    """DESIGN.md §Strided-lowering: log2(H·W) rounds, each with disjoint
    (dst, src) lattices, folding every row into row 0; ÷(H·W) as one SHR."""
    plan = global_avgpool_plan(4, 4)
    assert (plan.out_h, plan.out_w) == (1, 1)
    assert plan.keep_rows == plan.shr_indices == (0,)
    assert (plan.mode, plan.div_shift) == ("gap", 4)
    assert len(plan.rounds) == 4                   # log2(16)
    assert plan.rounds[0] == ((0, 1), (2, 3), (4, 5), (6, 7), (8, 9),
                              (10, 11), (12, 13), (14, 15))
    assert plan.rounds[-1] == ((0, 8),)
    assert plan.add_pairs == tuple(p for r in plan.rounds for p in r)
    for rnd in plan.rounds:                        # disjoint per round
        dsts = [d for d, _ in rnd]
        srcs = [s for _, s in rnd]
        assert len(set(dsts)) == len(dsts)
        assert not set(dsts) & set(srcs)


@given(log_hw=st.integers(0, 3), cols=st.integers(1, 6),
       seed=st.integers(0, 2**16))
@settings(max_examples=40, deadline=None)
def test_global_avgpool_tree_sums_every_row(log_hw, cols, seed):
    """Executing the ADD-pair program in order reduces row 0 to the
    column sum of the whole matrix — for every power-of-two map size."""
    hw = 2 ** log_hw
    plan = global_avgpool_plan(hw, hw)
    rng = np.random.default_rng(seed)
    mat = rng.integers(-10**6, 10**6, (hw * hw, cols)).astype(np.int64)
    expected = mat.sum(axis=0)
    work = mat.copy()
    for dst, src in plan.add_pairs:
        work[dst] += work[src]
    np.testing.assert_array_equal(work[0], expected)
    np.testing.assert_array_equal(expected >> plan.div_shift,
                                  mat.sum(axis=0) >> (2 * log_hw))


def test_global_avgpool_plan_rejects_bad_maps():
    """A map that is not square is refused; one whose position count is
    not a power of two (ResNet-50's 7×7) folds every row into row 0 all
    the same, in ceil(log2(H·W)) disjoint rounds, and divides by the
    power of two below H·W."""
    with pytest.raises(ValueError, match="square"):
        global_avgpool_plan(4, 8)
    for hw in (3, 6, 7):
        plan = global_avgpool_plan(hw, hw)
        n = hw * hw
        assert len(plan.rounds) == (n - 1).bit_length()
        assert plan.div_shift == n.bit_length() - 1
        for rnd in plan.rounds:
            dsts = {d for d, _ in rnd}
            assert len(dsts) == len(rnd) and not dsts & {s for _, s in rnd}
        mat = np.arange(n * 3, dtype=np.int64).reshape(n, 3) ** 2
        work = mat.copy()
        for dst, src in plan.add_pairs:
            work[dst] += work[src]
        np.testing.assert_array_equal(work[0], mat.sum(axis=0))
