"""Virtual-clock serving simulation tests (DESIGN.md §Serving,
EXPERIMENTS.md §Serving-latency).

The determinism contracts behind the ``servelat/*`` benchmark rows: the
discrete-event simulation of the engine's own batching policy replays
bit-identically for a given seed (trace + summary), seeded
load generators are pure functions of their seed, the closed-loop source
bounds concurrency by construction, padding follows the compiled-shape
ladder, and the metrics audit catches the accounting violations it
claims to (exercised both positively and negatively).

Hypothesis-free: tier-1 floor.
"""

import numpy as np
import pytest

from repro.core.network_compiler import compile_network
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                synthetic_digit)
from repro.serving.vta import (BatchPolicy, ClosedLoopSource, PoissonSource,
                               RequestRecord, ServiceModel, ServingMetrics,
                               VirtualClock, calibrate_service_model,
                               nearest_rank, pad_ladder, padded_size,
                               poisson_arrival_times, ready_count,
                               request_images, simulate)

MODEL = ServiceModel(base_s=0.004, per_image_s=0.001)


@pytest.fixture(scope="module")
def lenet():
    return compile_network(lenet5_specs(lenet5_random_weights(0)),
                           synthetic_digit(0))


# ---------------------------------------------------------------------------
# Clock + policy primitives
# ---------------------------------------------------------------------------

def test_virtual_clock_is_monotonic():
    clock = VirtualClock()
    assert clock.now() == 0.0
    clock.advance_to(1.5)
    clock.advance_to(1.5)                       # no-op advance is fine
    assert clock.now() == 1.5
    with pytest.raises(ValueError, match="backward"):
        clock.advance_to(1.0)


def test_pad_ladder_and_padded_size():
    assert pad_ladder(8) == (1, 2, 4, 8)
    assert pad_ladder(1) == (1,)
    ladder = pad_ladder(6)                      # non-pow2 cap joins ladder
    assert ladder == (1, 2, 4, 6)
    assert padded_size(3, ladder) == 4
    assert padded_size(5, ladder) == 6
    assert padded_size(1, ladder) == 1
    with pytest.raises(ValueError):
        padded_size(7, ladder)


def test_ready_count_policy_matrix():
    policy = BatchPolicy(max_batch=4, max_wait_s=0.01)
    # a full batch dispatches regardless of age
    assert ready_count(9, 5.0, 5.0, policy) == 4
    # young + under-full: wait
    assert ready_count(2, 5.0, 5.005, policy) == 0
    # aged past max_wait (float-exact boundary): dispatch what's there
    assert ready_count(2, 5.0, 5.0 + policy.max_wait_s, policy) == 2
    # closed drain flushes immediately
    assert ready_count(2, 5.0, 5.0, policy, closed=True) == 2
    assert ready_count(0, 0.0, 0.0, policy, closed=True) == 0
    # max_wait=0 dispatches every arrival at once
    eager = BatchPolicy(max_batch=4, max_wait_s=0.0)
    assert ready_count(1, 7.0, 7.0, eager) == 1


def test_batch_policy_validation():
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=0)
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=4, max_wait_s=-1.0)
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=4, max_depth=0)


# ---------------------------------------------------------------------------
# Seeded load generation
# ---------------------------------------------------------------------------

def test_poisson_arrivals_are_seed_deterministic():
    a = poisson_arrival_times(200.0, 50, seed=7)
    b = poisson_arrival_times(200.0, 50, seed=7)
    assert a == b
    assert a != poisson_arrival_times(200.0, 50, seed=8)
    assert all(t1 < t2 for t1, t2 in zip(a, a[1:]))
    with pytest.raises(ValueError):
        poisson_arrival_times(0.0, 10, seed=0)


def test_closed_loop_source_issues_exactly_n():
    src = ClosedLoopSource(3, 10, think_s=0.01)
    arrivals = src.initial_arrivals()
    assert len(arrivals) == 3                   # one in flight per client
    fired = {rid for _, rid in arrivals}
    t = 0.0
    while len(fired) < 10:
        t += 0.01
        for _, rid in src.on_complete(min(fired), t):
            assert rid not in fired
            fired.add(rid)
    assert src.on_complete(9, t + 1.0) == []    # budget exhausted
    assert fired == set(range(10))


def test_closed_loop_source_rejects_zero_retry():
    with pytest.raises(ValueError, match="retry_s"):
        ClosedLoopSource(2, 4, retry_s=0.0)


# ---------------------------------------------------------------------------
# Discrete-event simulation determinism
# ---------------------------------------------------------------------------

def _run(seed, **kw):
    policy = kw.pop("policy", BatchPolicy(max_batch=4, max_wait_s=0.01,
                                          max_depth=16))
    return simulate(PoissonSource(kw.pop("rate", 600.0),
                                  kw.pop("n", 80), seed=seed),
                    policy, MODEL, slo_s=kw.pop("slo_s", 0.05), **kw)


def test_same_seed_replays_bit_identically():
    a, b = _run(42, workers=2), _run(42, workers=2)
    assert a.trace() == b.trace()
    assert a.metrics.summary() == b.metrics.summary()
    assert a.metrics.audit() == [] and b.metrics.audit() == []


def test_different_seed_diverges():
    assert _run(42).trace() != _run(43).trace()


def test_simulated_execution_matches_direct_serve(lenet):
    """DES with net attached really executes batches: outputs must be
    bit-identical to a direct NetworkProgram.serve of the same images."""
    images = request_images(lenet, 10, seed=3)
    result = simulate(PoissonSource(500.0, 10, seed=5, images=images),
                      BatchPolicy(max_batch=4, max_wait_s=0.01),
                      MODEL, workers=2, net=lenet)
    direct, _ = lenet.serve(images)
    assert sorted(result.outputs) == list(range(10))
    for rid, out in result.outputs.items():
        np.testing.assert_array_equal(out, direct[rid])
    assert result.metrics.audit() == []


def test_overload_sheds_with_backpressure_accounting():
    """Offered load far above capacity: rejections occur and the counters
    conserve (submitted == completed + rejected)."""
    result = _run(1, rate=5000.0, n=200,
                  policy=BatchPolicy(max_batch=2, max_wait_s=0.001,
                                     max_depth=4))
    s = result.metrics.summary()
    assert s["rejected"] > 0
    assert s["submitted"] == s["completed"] + s["rejected"]
    assert result.metrics.drained()
    assert result.metrics.audit() == []


def test_heavy_backlog_fills_batches():
    """Under sustained overload every non-tail batch forms at max_batch."""
    result = _run(2, rate=5000.0, n=120)
    sizes = [r.batch_size for r in result.records]
    assert max(sizes) == 4
    full = sum(1 for n in sizes if n == 4)
    assert full >= 0.8 * len(sizes)


def test_sim_respects_padding_ladder():
    result = _run(3, rate=900.0, n=60,
                  policy=BatchPolicy(max_batch=8, max_wait_s=0.004,
                                     max_depth=64))
    ladder = pad_ladder(8)
    for r in result.records:
        assert r.padded_size in ladder
        assert r.padded_size == padded_size(r.batch_size, ladder)


def test_max_wait_zero_sim_never_batches_waiting_requests():
    """max_wait=0 with a free worker dispatches each arrival alone."""
    result = simulate(PoissonSource(10.0, 20, seed=9),
                      BatchPolicy(max_batch=8, max_wait_s=0.0),
                      ServiceModel(base_s=1e-4, per_image_s=1e-5),
                      workers=4)
    assert all(r.batch_size == 1 for r in result.records)


def test_closed_loop_bounds_concurrency():
    """At most ``clients`` requests are ever in flight: count overlapping
    enqueue→complete intervals."""
    clients = 3
    result = simulate(ClosedLoopSource(clients, 30, think_s=0.001),
                      BatchPolicy(max_batch=4, max_wait_s=0.002),
                      MODEL, workers=2)
    assert len(result.records) == 30
    events = []
    for r in result.records:
        events.append((r.enqueue_t, 1))
        events.append((r.complete_t, -1))
    in_flight = peak = 0
    for _, delta in sorted(events, key=lambda e: (e[0], e[1])):
        in_flight += delta
        peak = max(peak, in_flight)
    assert peak <= clients
    assert result.metrics.audit() == []


def test_slo_counter_matches_recount():
    result = _run(4, slo_s=1e-6)                # impossible SLO
    s = result.metrics.summary()
    assert s["slo_violations"] == s["completed"] > 0
    assert result.metrics.audit() == []         # recount agrees


def test_service_model_calibration_is_usable(lenet):
    model = calibrate_service_model(lenet, batch=4, repeats=1)
    assert model.base_s > 0
    assert model.per_image_s >= 0
    assert model.service_s(4) >= model.service_s(1)


# ---------------------------------------------------------------------------
# Metrics: percentiles + audit negative coverage
# ---------------------------------------------------------------------------

def test_nearest_rank_percentiles():
    vals = [float(i) for i in range(1, 11)]     # 1..10
    assert nearest_rank(vals, 50) == 5.0
    assert nearest_rank(vals, 95) == 10.0
    assert nearest_rank(vals, 99) == 10.0
    assert nearest_rank(vals, 0) == 1.0
    assert nearest_rank([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        nearest_rank([], 50)


def _record(rid=0, enq=0.0, disp=0.1, comp=0.2, batch=1, padded=1):
    return RequestRecord(rid=rid, enqueue_t=enq, dispatch_t=disp,
                         complete_t=comp, batch_size=batch,
                         padded_size=padded, backend="batched", worker=0)


def test_audit_flags_violations():
    m = ServingMetrics(slo_s=0.05)
    m.on_submit()
    m.observe(_record(rid=1, disp=0.2, comp=0.1))     # non-monotonic
    errs = m.audit()
    assert any("non-monotonic" in e for e in errs)
    # the SLO counter itself agrees with the recount — no such error
    assert not any("slo_violations" in e for e in errs)

    m2 = ServingMetrics()
    m2.on_submit()
    m2.observe(_record(rid=2))
    m2.observe(_record(rid=2))                        # duplicate + over-count
    errs2 = m2.audit()
    assert any("twice" in e for e in errs2)
    assert any("over-accounted" in e for e in errs2)

    m3 = ServingMetrics()
    m3.on_submit()
    m3.observe(_record(rid=3, batch=4, padded=2))     # batch > padded
    assert any("padded" in e for e in m3.audit())


def test_metrics_summary_and_drained():
    m = ServingMetrics(slo_s=0.15)
    for i in range(4):
        m.on_submit()
    m.on_reject()
    for i in range(3):
        m.observe(_record(rid=i, enq=float(i), disp=i + 0.05,
                          comp=i + 0.1 * (i + 1), batch=3, padded=4))
    assert m.drained()
    s = m.summary()
    assert s["completed"] == 3 and s["rejected"] == 1
    assert s["slo_violations"] == 2                   # 0.2s and 0.3s > 0.15s
    assert s["mean_batch_occupancy"] == 3.0
    assert s["mean_padded_size"] == 4.0
    assert m.audit() == []
