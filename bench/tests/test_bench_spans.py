"""The span reduction (``bench/span_reduce.py``) and the readers of the
program's spans, on small traces recorded on a TPU v5e: half a second of
``lenet5.online`` run with ``--trace 1`` on a program that records spans,
and the older trace of a program that records none."""

import gzip
from pathlib import Path

import pytest

from bench import harness, layout, span_reduce, trace_reduce

DATA = Path(__file__).parent / "data"
SPANS = DATA / "lenet5_online_spans.xplane.pb.gz"
NO_SPANS = DATA / "lenet5_online.xplane.pb.gz"
READERS = ("stage_ms_per_image.offline", "codec_ms_per_image.offline",
           "epilogue_ms_per_image.offline",
           "kernel_call_ms_per_image.offline",
           "mxu_useful_mac_share.offline",
           "idle_outside_spans_share.offline", "execute_ms_p50.online",
           "kernel_call_share.online", "batch_fill.online")


@pytest.fixture(scope="module")
def profile():
    return trace_reduce.load(SPANS)


@pytest.fixture(scope="module")
def summary(profile):
    s = span_reduce.summarize(profile)
    assert s is not None
    return s


def test_the_window_and_every_serving_span_are_read(summary):
    assert summary.window_s == pytest.approx(0.5, rel=0.01)
    assert {"engine.execute", "engine.batch_form", "vta.serve", "vta.layer",
            "vta.stage", "vta.decode", "vta.kernel", "vta.kernel.put",
            "vta.kernel.dispatch", "vta.kernel.fetch", "vta.epilogue",
            "vta.encode", "vta.readout"} <= set(summary.spans)
    for name, st in summary.spans.items():
        assert 0 <= st.self_s <= st.total_s + 1e-12, name
        assert st.count > 0 and st.longest_s > 0, name


def test_self_time_is_time_less_the_spans_inside(summary):
    sp = summary.spans
    inside = sum(sp[n].total_s for n in ("vta.kernel.put",
                                         "vta.kernel.dispatch",
                                         "vta.kernel.fetch"))
    assert sp["vta.kernel"].total_s - sp["vta.kernel"].self_s == \
        pytest.approx(inside, rel=1e-9)
    for leaf in ("vta.kernel.put", "vta.kernel.dispatch", "vta.stage",
                 "vta.epilogue"):
        assert sp[leaf].self_s == pytest.approx(sp[leaf].total_s,
                                                rel=1e-12)


def test_counts_are_summed_by_their_share_in_the_window(summary):
    real = summary.count("engine.execute", "real")
    rows = summary.count("engine.execute", "rows")
    assert 0 < real <= rows
    assert summary.images() == real
    useful = summary.count("vta.layer", "useful_macs")
    issued = summary.count("vta.kernel.dispatch", "issued_macs")
    assert 0 < useful < issued
    assert len(summary.spans["engine.execute"].durations) <= \
        summary.spans["engine.execute"].count


def test_idle_is_placed_under_a_program_span_or_none(profile, summary):
    s = trace_reduce.reduce_profile(profile)
    assert sum(summary.idle.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert set(summary.idle) <= set(summary.spans) | {span_reduce.NO_SPAN}
    assert summary.idle.get("vta.kernel.fetch", 0) > 0


def test_nesting_gives_the_innermost_span_at_each_moment():
    spans = [(0, 10, "vta.serve", None), (2, 5, "vta.layer", None),
             (3, 4, "vta.kernel", None), (5, 10, "vta.layer", None)]
    closed, pieces = span_reduce._nest(spans)
    assert [(a, b, name) for a, b, _, _, name in pieces] == [
        (0, 2, "vta.serve"), (2, 3, "vta.layer"), (3, 4, "vta.kernel"),
        (4, 5, "vta.layer"), (5, 10, "vta.layer")]
    children = {span[:2]: [c[:2] for c in kids] for span, kids in closed}
    assert children[(0, 10)] == [(2, 5), (5, 10)]
    assert children[(2, 5)] == [(3, 4)]


def test_the_deepest_span_on_any_thread_owns_the_moment():
    a = [(0, 10, 1, 0, "engine.execute"), (10, 20, 1, 0, "engine.execute")]
    b = [(5, 15, 2, 5, "vta.stage")]
    c = [(12, 14, 2, 12, "vta.decode")]
    owners = span_reduce._owners([a, b, c])
    assert owners == [(0, 5, "engine.execute"), (5, 12, "vta.stage"),
                      (12, 14, "vta.decode"), (14, 15, "vta.stage"),
                      (15, 20, "engine.execute")]
    idle = span_reduce._idle_by_owner([(-5e9, 3e9)], [
        (0, 1e9, "vta.stage"), (2e9, 4e9, "vta.decode")])
    assert dict(idle) == {"vta.stage": 1.0, "vta.decode": 1.0,
                          span_reduce.NO_SPAN: 6.0}


def _as_run_trace(monkeypatch, tmp_path, gz: Path):
    """Lay ``gz`` out where a ``--trace 1`` run leaves its trace."""
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "t.xplane.pb").write_bytes(gzip.decompress(gz.read_bytes()))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)


@pytest.mark.parametrize("metric", READERS)
def test_each_reader_reads_the_programs_spans(metric, monkeypatch,
                                              tmp_path):
    _as_run_trace(monkeypatch, tmp_path, SPANS)
    value = layout.metric_reader(metric)(None)
    assert value is not None and value >= 0
    if not metric.startswith("idle_outside"):
        assert value > 0
    if metric.endswith(("share.offline", "share.online", "fill.online")):
        assert value <= 100


@pytest.mark.parametrize("metric", READERS)
def test_each_reader_gives_nothing_without_program_spans(metric,
                                                          monkeypatch,
                                                          tmp_path):
    _as_run_trace(monkeypatch, tmp_path, NO_SPANS)
    assert layout.metric_reader(metric)(None) is None
