"""End-to-end batched serving tests (DESIGN.md §Batching).

``NetworkProgram.serve`` must produce, for every request in the batch,
exactly the bytes the per-image paths produce: the compiler's reference
(``verify``), the per-image ``serve_one`` on both simulator backends, and
the integer model reference.  Serving twice must reuse the cached
instruction plans — compile-once/serve-many asserted via plan identity.

Hypothesis-free: tier-1 floor.
"""

import numpy as np
import pytest

from repro.core.fast_simulator import plan_for
from repro.core.network_compiler import compile_network
from repro.core.simulator import (decode_out_region, decode_out_region_batch,
                                  make_simulator, run_program,
                                  run_program_batch)
from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                reference_forward_int8, synthetic_digit)

BATCH = 8


@pytest.fixture(scope="module")
def lenet():
    weights = lenet5_random_weights(seed=0)
    net = compile_network(lenet5_specs(weights), synthetic_digit(0))
    return weights, net


@pytest.fixture(scope="module")
def cifar():
    from repro.models.cifar_cnn import (calibrate_shifts,
                                        cifar_cnn_random_weights,
                                        cifar_cnn_specs,
                                        synthetic_cifar_image)
    weights = cifar_cnn_random_weights(seed=0)
    shifts = calibrate_shifts(
        weights, [synthetic_cifar_image(s) for s in range(1, 3)])
    net = compile_network(cifar_cnn_specs(weights, shifts),
                          synthetic_cifar_image(0))
    return weights, net


def _digits(n, seed=42):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# LeNet-5
# ---------------------------------------------------------------------------

def test_lenet_serve_matches_per_image_exactly(lenet):
    weights, net = lenet
    imgs = _digits(BATCH)
    outs, reports = net.serve(imgs)
    assert outs.shape[0] == BATCH
    assert len(reports) == len(net.layers)
    shifts = [l.requant_shift for l in net.layers]
    for b, img in enumerate(imgs):
        np.testing.assert_array_equal(
            outs[b], net.serve_one(img, backend="fast"),
            err_msg=f"request {b}: batched != looped fast")
        ref, _ = reference_forward_int8(weights, img, shifts)
        np.testing.assert_array_equal(outs[b], ref)
    # one request cross-checked against the per-struct oracle interpreter
    np.testing.assert_array_equal(outs[0],
                                  net.serve_one(imgs[0], backend="oracle"))


def test_lenet_serve_matches_verify_on_reference_input(lenet):
    """Serving the compile-time input must reproduce ``verify()``'s
    output (the compiler's own reference path)."""
    _, net = lenet
    expected, _ = net.verify(backend="fast")
    outs, _ = net.serve([net.input_tensor] * BATCH)
    for b in range(BATCH):
        np.testing.assert_array_equal(outs[b], expected)


def test_lenet_serve_reuses_cached_plans(lenet):
    """Compile-once/serve-many: the per-layer instruction plans must be
    the *same objects* across serve calls (no recompilation)."""
    _, net = lenet
    imgs = _digits(4, seed=3)
    net.serve(imgs)
    plans_first = net.plans()
    net.serve(imgs)
    plans_second = net.plans()
    assert all(a is b for a, b in zip(plans_first, plans_second))
    assert len(plans_first) == len(net.layers)
    # the plan the batched engine used is the one cached on the program
    assert all(plan_for(l.program) is p
               for l, p in zip(net.layers, plans_first))


@pytest.mark.parametrize("backend", ["batched", "pallas"])
def test_lenet_serve_reuses_its_stack_without_carrying_rows_over(lenet,
                                                                 backend):
    """A thread serves into the largest stack it has served with, rewritten
    from the DRAM image each time: a smaller batch after a larger one,
    and a batch on another thread, answer as on a fresh network, and the
    answers handed back are not views of the stack."""
    import threading
    weights, _ = lenet
    net = compile_network(lenet5_specs(weights), synthetic_digit(0))
    fresh = compile_network(lenet5_specs(weights), synthetic_digit(0))
    big, small = _digits(4, seed=5), _digits(3, seed=6)
    first, _ = net.serve(big, backend=backend)
    stack = net._stacks.stack
    second, _ = net.serve(small, backend=backend)
    assert net._stacks.stack is stack and len(stack) == len(big)
    np.testing.assert_array_equal(second, fresh.serve(small)[0])
    assert not np.shares_memory(first, stack)
    np.testing.assert_array_equal(first, fresh.serve(big)[0])
    other = {}
    worker = threading.Thread(target=lambda: other.update(
        out=net.serve(small, backend=backend)[0],
        stack=net._stacks.stack))
    worker.start()
    worker.join()
    assert other["stack"] is not stack
    np.testing.assert_array_equal(other["out"], second)


def test_lenet_serve_report_totals(lenet):
    """Batched reports carry batch totals: loop counts are batch × the
    single-image program counts."""
    _, net = lenet
    _, reports = net.serve(_digits(BATCH, seed=5))
    for layer, rep in zip(net.layers, reports):
        assert rep.gemm_loops == BATCH * layer.program.gemm_loops()
        assert rep.insn_executed == len(layer.program.instructions)
    assert sum(r.gemm_loops for r in reports) == BATCH * 2942   # §5.1


def test_lenet_serve_accepts_stacked_array(lenet):
    _, net = lenet
    imgs = _digits(6, seed=9)
    outs_list, _ = net.serve(imgs)
    outs_arr, _ = net.serve(np.concatenate(imgs, axis=0))   # (6, 1, 32, 32)
    np.testing.assert_array_equal(outs_list, outs_arr)
    with pytest.raises(ValueError):
        net.serve([])
    with pytest.raises(ValueError):
        net.serve(np.zeros((4, 3, 5), dtype=np.int8))
    # wrong channel count: staged bytes don't fit the compiled INP region
    with pytest.raises(ValueError):
        net.serve([np.zeros((1, 3, 32, 32), dtype=np.int8)])


# ---------------------------------------------------------------------------
# CIFAR CNN (multi-chunk, padded conv, max pool, uop waves)
# ---------------------------------------------------------------------------

def test_cifar_serve_matches_per_image_exactly(cifar):
    from repro.models.cifar_cnn import reference_forward_int8 as cifar_ref
    weights, net = cifar
    assert max(net.chunks_per_layer()) > 1      # the multi-chunk workload
    rng = np.random.default_rng(21)
    imgs = [rng.integers(-64, 64, (1, 3, 32, 32)).astype(np.int8)
            for _ in range(BATCH)]
    outs, reports = net.serve(imgs)
    shifts = [l.requant_shift for l in net.layers]
    for b, img in enumerate(imgs):
        np.testing.assert_array_equal(
            outs[b], net.serve_one(img, backend="fast"),
            err_msg=f"request {b}: batched != looped fast")
        ref, _ = cifar_ref(weights, img, shifts)
        np.testing.assert_array_equal(outs[b], ref)
    for layer, rep in zip(net.layers, reports):
        assert rep.gemm_loops == BATCH * layer.program.gemm_loops()


def test_cifar_serve_reuses_cached_plans(cifar):
    _, net = cifar
    rng = np.random.default_rng(23)
    imgs = [rng.integers(-64, 64, (1, 3, 32, 32)).astype(np.int8)
            for _ in range(2)]
    net.serve(imgs)
    first = net.plans()
    net.serve(imgs)
    assert all(a is b for a, b in zip(first, net.plans()))


# ---------------------------------------------------------------------------
# Program-level batched dispatch (simulator.py)
# ---------------------------------------------------------------------------

def test_run_program_batch_replicates_single_image(lenet):
    _, net = lenet
    prog = net.layers[0].program
    out_single, _ = run_program(prog, backend="fast")
    outs, rep = run_program_batch(prog, batch=3)
    assert outs.shape == (3,) + out_single.shape
    for b in range(3):
        np.testing.assert_array_equal(outs[b], out_single)
    assert rep.gemm_loops == 3 * prog.gemm_loops()
    # uniform dispatch: backend="batched" on the single-image entry point
    out_b, _ = run_program(prog, backend="batched")
    np.testing.assert_array_equal(out_b, out_single)
    with pytest.raises(ValueError):
        run_program_batch(prog)          # neither batch nor stack
    with pytest.raises(ValueError):
        run_program_batch(prog, batch=2,
                          dram_stack=np.zeros((3, 8), dtype=np.uint8))


def test_decode_out_region_batch_matches_single(lenet):
    _, net = lenet
    prog = net.layers[0].program
    image = prog.dram_image()
    sim = make_simulator(prog.config, image, backend="fast")
    sim.run(prog.instructions)
    single = decode_out_region(prog, sim.dram)
    stacked = decode_out_region_batch(prog, np.stack([sim.dram, sim.dram]))
    np.testing.assert_array_equal(stacked[0], single)
    np.testing.assert_array_equal(stacked[1], single)


def test_make_simulator_batched_backend_selection():
    from repro.core.fast_simulator import BatchFastSimulator
    from repro.core.hwconfig import vta_default
    cfg = vta_default()
    sim = make_simulator(cfg, np.zeros((2, 64), dtype=np.uint8),
                         backend="batched")
    assert isinstance(sim, BatchFastSimulator)
