"""The benchmark's own clock: when a serve call returns is when its rows
were answered, read from the very arrays the engine handed it; the
window's rate and latencies follow from those times alone."""

import gc

import numpy as np
import pytest

from bench import harness, layout, loadgen
from bench.served import ServedNet, real_rows


class _Net:
    def serve(self, images, backend):
        return np.stack([np.asarray(i)[0] for i in images]), []


class _Clock:
    def __init__(self, *times):
        self.times = list(times)

    def __call__(self):
        return self.times.pop(0)


def test_returned_at_is_the_serve_calls_return_for_that_very_row():
    a, b = np.zeros((1, 4)), np.ones((1, 4))
    served = ServedNet(_Net(), clock=_Clock(1.0, 1.25))
    served.serve([a, b, b], backend="pallas")      # b pads the batch
    assert served.returned_at(a) == served.returned_at(b) == 1.25
    assert served.returned_at(a.copy()) is None    # the same bytes, not the row
    assert served.spans == [(1.0, 1.25, 3, 2)]
    assert real_rows([a, b, b]) == 2


def _readings(requests, window=(10.0, 20.0)):
    return harness.Readings(None, window, requests, None, None, [], None)


def test_rate_and_latency_end_where_the_benchmark_saw_the_answer():
    reqs = [loadgen.Request(0, 10.0), loadgen.Request(1, 19.0),
            loadgen.Request(2, 19.5)]
    reqs[0].answered_t = 10.05
    reqs[1].answered_t = 20.5          # due in the window, answered after
    r = _readings(reqs)                # reqs[2]: never answered
    assert r.images_per_s == pytest.approx(0.1)
    lat = r.latencies_ms()
    assert lat[:2] == pytest.approx([50.0, 1500.0])
    assert lat[2] == pytest.approx(1e3 * (20.0 + harness.DRAIN_S - 19.5))


def test_answer_no_serve_call_returned_is_a_harness_error():
    class Ticket:
        image = np.zeros((1, 4))

        def result(self, timeout):
            return np.zeros(10)

    req = loadgen.Request(0, 0.0, ticket=Ticket())
    with pytest.raises(RuntimeError, match="no serve call"):
        harness._drain([req], ServedNet(_Net()), 1.0)


def test_host_watch_names_collections_and_the_longest_serve():
    served = ServedNet(_Net(), clock=_Clock(1.0, 1.5, 3.0, 3.1))
    watch = harness.HostWatch()
    gc.collect()
    served.serve([np.zeros((1, 4))], backend="pallas")
    served.serve([np.zeros((1, 4))], backend="pallas")
    line = watch.stop(served, (0.0, 4.0))
    assert watch._on_gc not in gc.callbacks
    assert "longest serve call 500.0 ms" in line
    assert "no serve call running 1500.0 ms" in line
    assert int(line.split(" gc collections")[0]) >= 1


def test_per_layer_metric_must_list_its_cells():
    assert layout.reports({"name": "setup_s"}, "any.cell")
    assert not layout.reports({"name": "m", "moves": "x",
                               "workloads": ["a.b"]}, "any.cell")
    with pytest.raises(ValueError, match="lists no workloads"):
        layout.reports({"name": "m", "moves": "x"}, "any.cell")
