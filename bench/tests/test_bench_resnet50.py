"""The resnet50 configuration, its plain reference, and the readers of
the pool span and the kernel calls' byte counts.

The reference's useful work is ResNet-50 v1.5's at its published shapes;
the configuration's geometry is the program's and pins every shift the
graph has; the reference's float32 convolutions equal the float64 ones of
``refops`` wherever their bound lets them run; and the two readers read a
serve recorded on the CPU, and nothing from a program that records neither
the span nor the counts."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, layout, refops, trace_reduce

DATA = Path(__file__).parent / "data"
REF = layout.load_module(layout.BENCH / "configs" / "resnet50_ref.py")
READERS = ("pool_ms_per_image.offline", "transfer_mb_per_image.offline")


@pytest.fixture(scope="module")
def cfg():
    return json.loads((layout.BENCH / "configs" / "resnet50.json")
                      .read_text())


def test_useful_work_is_resnet50s(cfg):
    shapes = REF.gemm_shapes(cfg)
    assert len(shapes) == 54
    assert sum(m * k * n for _, m, k, n in shapes) == 4_089_184_256
    assert sum(k * n for _, _, k, n in shapes) == 25_502_912


def test_configuration_is_the_programs_geometry_and_pins_every_shift(cfg):
    from repro.models.resnet50 import build_resnet50, linear_layers
    assert cfg["reduced"] == []
    assert cfg["layers"] == linear_layers()
    graph = build_resnet50(refops.draw_weights(cfg, 0), cfg["weight_exps"])
    kinds = {"requant": set(), "add": set()}
    for node in graph.nodes.values():
        kinds.get(node.kind, set()).add(node.name)
    assert kinds["requant"] == set(cfg["requant_shifts"])
    assert kinds["add"] == set(cfg["join_pre_shifts"])
    assert set(cfg["weight_exps"]) == {l["name"] for l in cfg["layers"]}


@pytest.mark.parametrize("k, stride, padding, hi", [
    (7, 2, 3, 64), (3, 1, 1, 128), (3, 2, 1, 128), (1, 2, 0, 128),
    (1, 1, 0, 128),
    (3, 1, 1, 2 ** 14)])            # past 2**24: the float64 path
def test_reference_conv_is_refops_conv(k, stride, padding, hi):
    """The reference's NHWC convolution against ``refops``' NCHW one."""
    rng = np.random.default_rng(k * 10 + stride)
    x = rng.integers(-hi, hi, (2, 5, 9, 9))
    w = rng.integers(-5, 6, (4, 5, k, k)).astype(np.int8)
    b = rng.integers(-64, 65, (4,)).astype(np.int32)
    got = REF.conv(x.transpose(0, 2, 3, 1), w, b, stride=stride,
                   padding=padding)
    np.testing.assert_array_equal(
        got.transpose(0, 3, 1, 2),
        refops.conv(x, w, b, stride=stride, padding=padding))
    xf = rng.integers(-128, 128, (3, 40))
    wf = rng.integers(-5, 6, (40, 7))
    np.testing.assert_array_equal(REF.fc(xf, wf, b[:1].repeat(7)),
                                  refops.fc(xf, wf, b[:1].repeat(7)))


def test_reference_max_pool_skips_the_padding():
    x = -np.arange(2 * 5 * 4 * 3).reshape(2, 5, 4, 3) - 1  # all negative
    got = REF.max3x3s2(x)
    assert got.shape == (2, 3, 2, 3)
    # the largest value of a window is its first position inside the map:
    # rows 0-1 and columns 0-1 for the first, rows 3-4 and 1-3 for the last
    np.testing.assert_array_equal(got[:, 0, 0], x[:, 0, 0])
    np.testing.assert_array_equal(got[:, 2, 1], x[:, 3, 1])


def _lay_out(monkeypatch, tmp_path, xplane: bytes):
    """Put a trace where a ``--trace 1`` run leaves its trace."""
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "t.xplane.pb").write_bytes(xplane)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_where_the_program_records_neither(
        metric, monkeypatch, tmp_path):
    """A trace of a program with spans but no ``vta.pool`` span and no
    byte counts (recorded on a v5e before either existed)."""
    _lay_out(monkeypatch, tmp_path, gzip.decompress(
        (DATA / "lenet5_online_spans.xplane.pb.gz").read_bytes()))
    assert layout.metric_reader(metric)(None) is None


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """One engine batch of 4 lenet5 requests on the pallas path, recorded
    on the CPU inside the benchmark's window span; with the pool rows and
    the bytes each kernel call moves, as the program counts them."""
    import jax
    from repro.core.network_compiler import compile_network
    from repro.core.pallas_backend import plan_pallas
    from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                    synthetic_digit)
    from repro.serving.vta import (BatchPolicy, VTAServingEngine,
                                   request_images, serve_all)
    net = compile_network(lenet5_specs(lenet5_random_weights(0)),
                          synthetic_digit(0))
    images = request_images(net, 4, seed=2)
    net.serve(images, backend="pallas")              # warm every shape
    engine = VTAServingEngine(
        net, policy=BatchPolicy(max_batch=4, max_wait_s=0.5, max_depth=8),
        backends=("pallas",)).start()
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            serve_all(engine, images)
        engine.shutdown()
    path, = log_dir.glob("plugins/profile/*/*.xplane.pb")
    pooled = sum(4 * plan_pallas(layer.program).pool_rows
                 for layer in net.layers)
    return path.read_bytes(), net, pooled


def test_readers_read_a_recorded_serve(cpu_trace, monkeypatch, tmp_path):
    from bench import span_reduce
    xplane, net, pooled = cpu_trace
    _lay_out(monkeypatch, tmp_path, xplane)
    s = span_reduce.of_run()
    assert s.images() == 4
    assert s.count("vta.pool", "positions") == pooled > 0
    assert layout.metric_reader(READERS[0])(None) > 0
    # each call sends its stacked input, its weights and bias, and fetches
    # its result: the weights at least once per layer
    weights = sum(layer.weight_matrix.size for layer in net.layers)
    mb = layout.metric_reader(READERS[1])(None)
    assert mb * 1e6 * 4 >= weights
