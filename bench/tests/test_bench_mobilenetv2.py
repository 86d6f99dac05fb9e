"""The mobilenetv2 configuration, its plain reference, and the readers of
the depthwise kernel's roofline share and useful multiply-add share.

The reference's useful work is MobileNetV2 1.0's at its published shapes;
the configuration's geometry is the program's and pins every shift, join
pre-shift and ReLU6 cap the graph has; the reference's depthwise conv is
a direct loop's; and the two readers compute their arithmetic from a
recorded reading, and give nothing where nothing was recorded."""

import gzip
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import harness, layout, refops, stats, trace_reduce

DATA = Path(__file__).parent / "data"
REF = layout.load_module(layout.BENCH / "configs" / "mobilenetv2_ref.py")
ROOFLINE, SHARE = "vta_dwconv_roofline", "dwconv_useful_mac_share.offline"


@pytest.fixture(scope="module")
def cfg():
    return json.loads((layout.BENCH / "configs" / "mobilenetv2.json")
                      .read_text())


def test_useful_work_is_mobilenet_v2s(cfg):
    shapes = REF.gemm_shapes(cfg)
    assert len(shapes) == 53
    assert sum(m * k * n for _, m, k, n in shapes) == 300_774_272
    dw = REF.dw_shapes(cfg)
    assert len(dw) == 17
    assert sum(m * 9 * c for _, _, _, c, m in dw) == 20_716_416
    weights = sum(w.size for w, _ in REF.weights(cfg, 0).values())
    assert weights == 3_469_760


def test_configuration_is_the_programs_geometry_and_pins_everything(cfg):
    from repro.models.mobilenet_v2 import build_mobilenet_v2, linear_layers
    assert cfg["reduced"] == [] and cfg["width"] == 1.0
    assert cfg["layers"] == linear_layers()
    graph = build_mobilenet_v2(REF.weights(cfg, 0), cfg["weight_exps"])
    kinds = {"requant": set(), "add": set(), "relu6": set()}
    for node in graph.nodes.values():
        kinds.get(node.kind, set()).add(node.name)
    assert kinds["requant"] == set(cfg["requant_shifts"])
    assert kinds["add"] == set(cfg["join_pre_shifts"])
    assert kinds["relu6"] == set(cfg["relu6_caps"])
    assert set(cfg["weight_exps"]) == {l["name"] for l in cfg["layers"]}
    # every cap is 6 at an int8 scale
    for cap in cfg["relu6_caps"].values():
        assert cap in (6, 12, 24, 48, 96)


@pytest.mark.parametrize("stride", (1, 2))
def test_reference_depthwise_conv_is_a_direct_loop(stride):
    rng = np.random.default_rng(stride)
    x = rng.integers(-128, 128, (2, 7, 7, 5))          # NHWC
    w = rng.integers(-5, 6, (5, 1, 3, 3)).astype(np.int8)
    b = rng.integers(-64, 65, (5,)).astype(np.int32)
    got = REF.depthwise(x, w, b, stride=stride)
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = (7 - 1) // stride + 1
    assert got.shape == (2, out, out, 5)
    for i in range(out):
        for j in range(out):
            for c in range(5):
                win = xp[:, stride * i:stride * i + 3,
                         stride * j:stride * j + 3, c]
                np.testing.assert_array_equal(
                    got[:, i, j, c], (win * w[c, 0]).sum(axis=(1, 2)) + b[c])


@pytest.mark.parametrize("stride", (1, 2))
def test_reference_dense_convs_are_refops_conv(stride):
    rng = np.random.default_rng(10 + stride)
    x = rng.integers(-128, 128, (2, 4, 9, 9))
    w = rng.integers(-5, 6, (6, 4, 3, 3)).astype(np.int8)
    w1 = rng.integers(-5, 6, (6, 4, 1, 1)).astype(np.int8)
    b = rng.integers(-64, 65, (6,)).astype(np.int32)
    nhwc = x.transpose(0, 2, 3, 1)
    np.testing.assert_array_equal(
        REF.dense3x3(nhwc, w, b, stride=stride).transpose(0, 3, 1, 2),
        refops.conv(x, w, b, stride=stride, padding=1))
    np.testing.assert_array_equal(
        REF.pointwise(nhwc, w1, b, stride=stride).transpose(0, 3, 1, 2),
        refops.conv(x, w1, b, stride=stride))


def _reading(cfg, device_ops, spans, window=(0.0, 10.0)):
    """A run's readings as the roofline reader takes them."""
    cell = types.SimpleNamespace(ref_module=lambda: REF, config=cfg)
    served = types.SimpleNamespace(
        spans_between=lambda lo, hi: [(s, 1.0) for s in spans])
    trace = types.SimpleNamespace(device_ops=device_ops)
    peak = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(cell=cell, served=served, trace=trace,
                                 peak=peak, window=window)


def test_roofline_reader_reads_the_depthwise_op_family(cfg):
    spans = [(0.0, 1.0, 8, 8), (1.0, 2.0, 8, 5)]
    taken = 0.02
    r = _reading(cfg, [("fusion", 1.0), ("vta_dwconv", taken)], spans)
    least = sum(stats.least_time_s(
        2 * b * m * 9 * c, b * h * w * c + 13 * c + b * m * c, r.peak)
        for *_, b in spans for _, h, w, c, m in REF.dw_shapes(cfg))
    got = layout.metric_reader(ROOFLINE)(r)
    assert got == pytest.approx(100 * least / taken)
    assert 0 < got < 100
    # no depthwise op in the trace, no trace, or no depthwise shapes
    assert layout.metric_reader(ROOFLINE)(
        _reading(cfg, [("vta_gemm", 1.0)], spans)) is None
    r.trace = None
    assert layout.metric_reader(ROOFLINE)(r) is None
    r = _reading(cfg, [("vta_dwconv", taken)], spans)
    r.cell.ref_module = lambda: types.SimpleNamespace()
    assert layout.metric_reader(ROOFLINE)(r) is None


def _lay_out(monkeypatch, tmp_path, xplane: bytes):
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "t.xplane.pb").write_bytes(xplane)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)


def test_share_reader_gives_nothing_where_no_depthwise_call_ran(
        monkeypatch, tmp_path):
    """A trace of a program with spans and no depthwise call (lenet5,
    recorded on a v5e)."""
    _lay_out(monkeypatch, tmp_path, gzip.decompress(
        (DATA / "lenet5_online_spans.xplane.pb.gz").read_bytes()))
    assert layout.metric_reader(SHARE)(None) is None


def test_share_reader_reads_a_recorded_serve(monkeypatch, tmp_path):
    """A small MobileNetV2's pallas serve of 4 images recorded on the CPU
    inside the benchmark's window: the reader is Σ useful ÷ Σ issued of
    the depthwise calls, each useful rows·M·9·C and each issued what
    ``vta_dwconv`` runs."""
    import jax
    from repro.kernels.vta_dwconv import dw_blocks
    from repro.models import mobilenet_v2 as mb
    net, _ = mb.compile_mobilenet_v2(width=0.5, image_size=32)
    images = np.concatenate([mb.synthetic_image(s, 32) for s in range(4)])
    net.serve(images, backend="pallas")               # warm every shape
    log_dir = tmp_path / "profile"
    with jax.profiler.trace(str(log_dir)):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            net.serve(images, backend="pallas")
    path, = log_dir.glob("plugins/profile/*/*.xplane.pb")
    _lay_out(monkeypatch, tmp_path / "laid", path.read_bytes())
    useful = issued = 0
    for layer in net.layers:
        if layer.spec.groups == 1:
            continue
        g = layer.geometry
        d = dw_blocks(4, g.in_channels, g.in_h, g.in_w, 3, 3, g.stride, 1)
        useful += 4 * layer.macs
        issued += d.issued_macs
    got = layout.metric_reader(SHARE)(None)
    assert got == pytest.approx(100 * useful / issued)
    assert 0 < got < 100
