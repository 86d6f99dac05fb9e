"""The benchmark's arithmetic on synthetic inputs: useful work from the
layer shapes, percentiles, rates, rooflines, the peak table, the traffic
generator and the reference's integer operations."""

import json

import numpy as np
import pytest

from bench import device, layout, loadgen, refops, stats


def _cfg(name):
    return json.loads((layout.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, macs", [("lenet5", 416_520),
                                        ("resnet8", 13_550_208)])
def test_useful_macs_per_image(name, macs):
    ref = layout.load_module(layout.BENCH / "configs" / f"{name}_ref.py")
    assert sum(m * k * n for _, m, k, n in ref.gemm_shapes(_cfg(name))) == macs


def test_nearest_rank():
    xs = [float(i) for i in range(1, 101)]
    assert stats.nearest_rank(xs, 50) == 50.0
    assert stats.nearest_rank(xs, 95) == 95.0
    assert stats.nearest_rank(xs, 0) == 1.0
    assert stats.nearest_rank([1.0, 2.0, 3.0], 99.9) == 3.0
    with pytest.raises(ValueError):
        stats.nearest_rank([], 50)


def test_rate_roofline_and_mfu():
    peak = {"int8_ops_per_s": 400e12, "hbm_bytes_per_s": 800e9}
    assert stats.rate(300, 10.0) == 30.0
    # compute-bound: 4e12 ops take 10 ms; 8 MB would take 10 us
    assert stats.least_time_s(4e12, 8e6, peak) == pytest.approx(0.01)
    # memory-bound: 8e9 bytes take 10 ms
    assert stats.least_time_s(4e6, 8e9, peak) == pytest.approx(0.01)
    assert stats.share_pct(0.01, 0.04) == pytest.approx(25.0)
    assert stats.share_pct(0.01, 0.0) is None
    assert stats.mfu_pct(2e6, 1000.0, peak) == pytest.approx(5e-4)
    assert stats.mfu_pct(2e6, 0.0, peak) is None


def test_peaks_table():
    assert device.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        device.peaks_for("TPU v99")


def test_poisson_schedule_is_seeded_with_a_fixed_count():
    a = loadgen.poisson_arrival_times(200.0, 10.0, 7)
    b = loadgen.poisson_arrival_times(200.0, 10.0, 7)
    c = loadgen.poisson_arrival_times(200.0, 10.0, 2 ** 33 + 7)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 2000
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 10.0


def test_image_order_visits_the_pool():
    order = loadgen.image_order(-3, 256, 600)
    assert sorted(order[:256]) == list(range(256))
    assert len(order) == 600


def test_trunc8_and_conv_against_loops():
    assert list(refops.trunc8(np.array([127, 128, -129, 300, -1]))) == \
        [127, -128, 127, 44, -1]
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, (2, 3, 7, 7))
    w = rng.integers(-8, 8, (4, 3, 3, 3))
    b = rng.integers(-9, 9, (4,))
    got = refops.conv(x, w, b, stride=2, padding=1)
    xp = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    want = np.zeros_like(got)
    for n in range(2):
        for f in range(4):
            for i in range(got.shape[2]):
                for j in range(got.shape[3]):
                    want[n, f, i, j] = b[f] + int(
                        (xp[n, :, 2 * i:2 * i + 3, 2 * j:2 * j + 3]
                         * w[f]).sum())
    assert np.array_equal(got, want)


def test_low_precision_drops_bits():
    act, wgt = refops.low_precision(4)
    assert list(act(np.array([127, -128, 15, 16]))) == [112, -128, 0, 16]
    assert list(wgt(np.array([16, -16, 3]))) == [16, -16, 0]
    assert list(wgt(np.array([5, -5]))) == [5, -5]


def test_served_counts_pad_rows():
    from bench.served import real_rows
    a, b = np.zeros(1), np.zeros(1)
    assert real_rows([a, b, b, b]) == 2
    assert real_rows([a, b]) == 2
    assert real_rows([a]) == 1
