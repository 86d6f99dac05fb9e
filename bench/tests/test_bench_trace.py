"""The trace reduction on a small trace recorded on a TPU v5e: half a
second of ``lenet5.online`` run with ``--trace 1``."""

from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "lenet5_online.xplane.pb.gz"


@pytest.fixture(scope="module")
def profile():
    return trace_reduce.load(TRACE)


def test_reduction_reads_the_window_and_the_device(profile):
    s = trace_reduce.reduce_profile(profile)
    assert s is not None
    assert s.window_s == pytest.approx(0.5, rel=0.01)
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.kernel_s["vta_gemm"] <= s.busy_s
    idle = sum(sec for _, sec in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    ops = dict(s.device_ops)
    assert "vta_gemm" in ops and ops["vta_gemm"] <= s.kernel_s["vta_gemm"]
    assert [sec for _, sec in s.device_ops] == \
        sorted((sec for _, sec in s.device_ops), reverse=True)


def test_busy_is_a_union_not_a_sum():
    assert trace_reduce._union([(0, 10), (5, 15), (20, 30)]) == \
        [(0, 15), (20, 30)]


def test_op_names_lose_their_hlo_text():
    assert trace_reduce._op_family(
        "%vta_gemm.1 = s8[32,128]{1,0} custom-call(s8[32,128]{1,0} %a.1)"
    ) == "vta_gemm"
    assert trace_reduce._op_family("fusion.12") == "fusion"


def test_no_window_gives_no_reading(profile):
    class Empty:
        planes = []
    assert trace_reduce.reduce_profile(Empty()) is None


@pytest.mark.parametrize("t, inside", [(5, True), (4.5, False), (-1, False),
                                       (4, True), (7, False)])
def test_idle_is_placed_by_the_serve_span_around_it(t, inside):
    got = trace_reduce._host_activity(t, [(0, 4), (5, 6)])
    assert got.startswith("host inside") is inside
