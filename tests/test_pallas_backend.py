"""The Pallas backend contract (DESIGN.md §2).

Three layers of pinning:

* **kernel ↔ compiler semantics** — ``ops.vta_matmul`` (both the real
  Pallas kernel in interpret mode and the XLA reference) against a numpy
  transcription of ``gemm_compiler``'s requant reference: bias → ReLU →
  arithmetic-SHR → int8 commit, on random int8 tiles, under *both*
  ``saturate`` settings.  This is the differential test that pins the
  relu-vs-SHR order, the floor rounding of SHR, and the
  truncate-vs-saturate commit.
* **layer level** — one-layer networks served by
  ``serve(backend="pallas")`` bit-identical to the oracle's ``verify`` and
  to the batched interpreter, on a fused program (bias, ReLU, SHR in the
  kernel), a general one (pool pairs and indexed SHR in the host
  epilogue) and a multi-chunk one; the typed refusals of the simulator
  entry points, which do not run the kernel.
* **network level** — LeNet-5 and resnet8 served end-to-end on
  ``backend="pallas"`` match the simulators bit for bit.

Skips cleanly when jax is unavailable (the backend itself degrades to a
typed ``CompileError`` with constraint ``pallas-jax-missing``).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

from repro.core.dram import DramAllocator                       # noqa: E402
from repro.core.errors import CompileError                      # noqa: E402
from repro.core.gemm_compiler import _wrap_int32                # noqa: E402
from repro.core.hwconfig import VTAConfig, vta_default          # noqa: E402
from repro.core.layer_compiler import LayerSpec                 # noqa: E402
from repro.core.layout import truncate_int8                     # noqa: E402
from repro.core.network_compiler import compile_network         # noqa: E402
from repro.core.pallas_backend import (plan_pallas,             # noqa: E402
                                       serving_lowering)
from repro.core.program import VTAProgram                       # noqa: E402
from repro.core.simulator import (BACKENDS, make_simulator,     # noqa: E402
                                  run_program, run_program_batch)
from repro.kernels import ops as kernel_ops                     # noqa: E402


# ---------------------------------------------------------------------------
# Kernel ↔ compiler requant semantics (the PR's drift-pinning differential)
# ---------------------------------------------------------------------------

def _requant_reference(a, b, bias, *, relu, shift, saturate):
    """``gemm_compiler``'s requant semantics in plain numpy: int32-wrapped
    GEMM + preload, ReLU *before* SHR, floor-rounding arithmetic shift,
    then the commit (truncation or the saturation upgrade)."""
    acc = _wrap_int32(a.astype(np.int64) @ b.astype(np.int64))
    if bias is not None:
        acc = _wrap_int32(acc.astype(np.int64) + bias.astype(np.int64))
    if relu:
        acc = np.maximum(acc, 0)
    if shift:
        acc = _wrap_int32(acc.astype(np.int64) >> shift)
    if saturate:
        return np.clip(acc, -128, 127).astype(np.int8)
    return truncate_int8(acc)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_kernel_matches_compiler_requant_semantics(backend):
    """Random int8 tiles × {bias, relu, shift} × both saturate settings:
    the kernel epilogue must equal the compiler's requant reference
    elementwise.  The pallas leg runs the real kernel body in interpret
    mode (multi-K-block shapes included)."""
    rng = np.random.default_rng(808)
    shapes = [(16, 16, 16), (1, 129, 130), (40, 300, 24)]
    if backend == "xla":            # the lowered reference is cheap — fuzz
        shapes += [(5, 7, 3), (64, 64, 64), (33, 257, 65)]
    for m, k, n in shapes:
        a = rng.integers(-128, 128, (m, k)).astype(np.int8)
        b = rng.integers(-128, 128, (k, n)).astype(np.int8)
        bias = rng.integers(-(2 ** 20), 2 ** 20, (n,)).astype(np.int32)
        for use_bias in (False, True):
            for relu in (False, True):
                for shift in (0, 5):
                    for saturate in (False, True):
                        got = np.asarray(kernel_ops.vta_matmul(
                            jnp.asarray(a), jnp.asarray(b),
                            jnp.asarray(bias) if use_bias else None,
                            relu=relu, shift=shift, saturate=saturate,
                            backend=backend))
                        want = _requant_reference(
                            a, b, bias if use_bias else None,
                            relu=relu, shift=shift, saturate=saturate)
                        np.testing.assert_array_equal(
                            got, want,
                            err_msg=f"{backend} {(m, k, n)} bias={use_bias} "
                                    f"relu={relu} shift={shift} "
                                    f"saturate={saturate}")


def test_saturate_and_truncation_disagree_only_out_of_range():
    """The documented tolerance contract: the two commits agree wherever
    the requant ACC already fits int8 and differ (clip vs low-8-bits)
    outside — i.e. saturation is an upgrade, not a different epilogue."""
    rng = np.random.default_rng(809)
    a = rng.integers(-128, 128, (32, 64)).astype(np.int8)
    b = rng.integers(-128, 128, (64, 32)).astype(np.int8)
    acc = _wrap_int32(a.astype(np.int64) @ b.astype(np.int64))
    trunc = np.asarray(kernel_ops.vta_matmul(
        jnp.asarray(a), jnp.asarray(b), saturate=False, backend="pallas"))
    sat = np.asarray(kernel_ops.vta_matmul(
        jnp.asarray(a), jnp.asarray(b), saturate=True, backend="pallas"))
    in_range = (acc >= -128) & (acc <= 127)
    assert not in_range.all(), "tiles too small to exercise the contract"
    np.testing.assert_array_equal(trunc[in_range], sat[in_range])
    np.testing.assert_array_equal(sat, np.clip(acc, -128, 127))
    np.testing.assert_array_equal(trunc, truncate_int8(acc))


# ---------------------------------------------------------------------------
# Layer level: one-layer networks on the serving path
# ---------------------------------------------------------------------------

# the tiny SRAM of test_batched_conformance: §3.3 multi-chunk programs
_SMALL_CFG = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                       acc_buff_vectors=64, out_buff_vectors=64,
                       uop_buff_entries=32)


def _fc_net(seed=810):
    """An fc layer whose program is bias + ReLU + SHR: the fused form."""
    rng = np.random.default_rng(seed)
    spec = LayerSpec("fc", "fc",
                     rng.integers(-128, 128, (34, 19)).astype(np.int8),
                     rng.integers(-1000, 1000, (19,)).astype(np.int32),
                     relu=True)
    return compile_network([spec], rng.integers(-128, 128, (1, 34))
                           .astype(np.int8))


def _pool_net(seed, cfg=None):
    """A padded conv whose 2×2 average pool lowers to pair ADDs and an
    indexed SHR: the general form, finished in the host epilogue."""
    rng = np.random.default_rng(seed)
    spec = LayerSpec("c", "conv",
                     rng.integers(-8, 8, (8, 3, 3, 3)).astype(np.int8),
                     rng.integers(-100, 100, (8,)).astype(np.int32),
                     padding=1, relu=True, pool="avg2x2")
    return compile_network([spec], rng.integers(-32, 64, (1, 3, 12, 12))
                           .astype(np.int8), cfg=cfg)


def _images(net, seed, n=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(-64, 64, net.input_tensor.shape).astype(np.int8)
            for _ in range(n)]


def _served_three_ways(net, images):
    """A pallas serve of the compile-time input and ``images``: the first
    answer is the oracle's ``verify``, all are the batched interpreter's."""
    want, _ = net.verify(backend="oracle")
    batch = [net.input_tensor] + images
    got, reports = net.serve(batch, backend="pallas")
    np.testing.assert_array_equal(got[0], want)
    ref, ref_reports = net.serve(batch, backend="batched")
    np.testing.assert_array_equal(got, ref)
    assert [r.gemm_loops for r in reports] == \
        [r.gemm_loops for r in ref_reports]
    return reports


def test_fused_program_bit_identical_to_oracle():
    """A bias+relu+shr program — the whole epilogue fuses into the
    kernel, and its answers equal the oracle's."""
    net = _fc_net()
    layer, = net.layers
    p = plan_pallas(layer.program)
    assert p.fused and p.relu and p.shift > 0
    assert serving_lowering(layer).fused
    reports = _served_three_ways(net, _images(net, 1))
    assert reports[0].gemm_loops == 4 * layer.program.gemm_loops()


def test_general_program_bit_identical_to_oracle():
    """Pair + indexed ops (the pool lowering shapes) force the
    kernel-GEMM + vectorised-TensorAlu path."""
    net = _pool_net(811)
    layer, = net.layers
    kinds = {type(op).__name__ for op in layer.program.alu_ops}
    assert {"AluPairOp", "AluIndexedImmOp"} <= kinds
    assert not plan_pallas(layer.program).fused
    assert not serving_lowering(layer).fused
    _served_three_ways(net, _images(net, 2))


def test_multi_chunk_program_bit_identical():
    """Tiny SRAM → §3.3 multi-chunk instruction stream; the pallas
    lowering works from the compiler's metadata, so the chunking must be
    invisible."""
    net = _pool_net(812, cfg=_SMALL_CFG)
    assert net.layers[0].n_chunks > 1
    _served_three_ways(net, _images(net, 3))


def test_run_program_batch_pallas_matches_batched():
    """Distinct images in one pallas serve: row for row the batched
    interpreter's answers, and the batch-total counters."""
    net = _fc_net(814)
    images = _images(net, 4, n=4)
    got, reports = net.serve(images, backend="pallas")
    want, _ = net.serve(images, backend="batched")
    np.testing.assert_array_equal(got, want)
    assert len({g.tobytes() for g in got}) == len(images)
    assert reports[0].gemm_loops == 4 * net.layers[0].program.gemm_loops()


# ---------------------------------------------------------------------------
# Dispatch + typed error contracts (satellite: stable constraint ids)
# ---------------------------------------------------------------------------

def test_make_simulator_dispatch():
    """The simulator entry points interpret instruction streams; each
    refuses ``backend="pallas"`` with a typed error that names the one
    pallas path."""
    assert BACKENDS == ("oracle", "fast", "batched")
    net = _fc_net()
    prog = net.layers[0].program
    cfg = vta_default()
    refusals = {
        "make_simulator": lambda: make_simulator(
            cfg, np.zeros(1024, np.uint8), backend="pallas"),
        "make_simulator (stack)": lambda: make_simulator(
            cfg, np.zeros((2, 1024), np.uint8), backend="pallas"),
        "run_program": lambda: run_program(prog, backend="pallas"),
        "run_program_batch": lambda: run_program_batch(
            prog, batch=2, backend="pallas"),
        "run_functional": lambda: net.run_functional(backend="pallas"),
        "serve_one": lambda: net.serve_one(net.input_tensor,
                                           backend="pallas"),
    }
    for entry, call in refusals.items():
        with pytest.raises(CompileError,
                           match=r'serve\(backend="pallas"\)') as exc:
            call()
        assert exc.value.constraint == (
            "serve-one-backend" if entry == "serve_one"
            else "simulator-backend"), entry


def test_kernel_constraint_ids():
    a = jnp.zeros((16, 16), jnp.int8)
    b_bad = jnp.zeros((8, 16), jnp.int8)
    with pytest.raises(CompileError) as exc:
        kernel_ops.vta_matmul(a, b_bad)
    assert exc.value.constraint == "kernel-gemm-shape"
    from repro.kernels.vta_gemm import vta_gemm
    with pytest.raises(CompileError) as exc:
        vta_gemm(a, jnp.zeros((16, 16), jnp.int8), block_m=256)
    assert exc.value.constraint == "kernel-block-divisibility"
    with pytest.raises(ValueError, match="kernel backend"):
        kernel_ops.vta_matmul(a, jnp.zeros((16, 16), jnp.int8),
                              backend="cuda")
    assert issubclass(CompileError, ValueError)   # catchable either way


def test_non_compiler_program_raises_typed_error():
    """Hand-written streams carry no compiler metadata — the backend must
    refuse with the stable constraint id, not misexecute."""
    prog = VTAProgram(config=vta_default(), allocator=DramAllocator())
    with pytest.raises(CompileError) as exc:
        plan_pallas(prog)
    assert exc.value.constraint == "pallas-program-metadata"


def test_unsupported_observability_raises():
    """Per-instruction fault hooks have no meaning on a fused kernel
    call — a loud error, not a silent no-op."""
    net = _fc_net()
    with pytest.raises(ValueError, match="fault_hook"):
        net.serve(_images(net, 5, n=2), backend="pallas",
                  fault_hook=lambda sim, k, i: None)


# ---------------------------------------------------------------------------
# Network-level end-to-end (the tentpole's acceptance)
# ---------------------------------------------------------------------------

def _compiled_lenet():
    from repro.models.lenet import (calibrate_shifts, lenet5_random_weights,
                                    lenet5_specs)
    from repro.core.network_compiler import compile_network
    weights = lenet5_random_weights(seed=0)
    rng = np.random.default_rng(7)
    cal = [rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
           for _ in range(4)]
    shifts = calibrate_shifts(weights, cal)
    return compile_network(lenet5_specs(weights, shifts),
                           np.zeros((1, 1, 32, 32), np.int8))


def test_lenet5_serving_bit_identical():
    net = _compiled_lenet()
    rng = np.random.default_rng(817)
    img = rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
    out_p1, _ = net.serve([img], backend="pallas")
    np.testing.assert_array_equal(out_p1[0],
                                  net.serve_one(img, backend="fast"))
    batch = np.stack([rng.integers(0, 128, (1, 1, 32, 32)).astype(np.int8)
                      for _ in range(3)])
    out_b, _ = net.serve(batch)
    out_pb, reps = net.serve(batch, backend="pallas")
    np.testing.assert_array_equal(out_pb, out_b)
    assert len(reps) == len(net.layers)


def test_serve_rejects_bad_backend_and_guarded_pallas():
    net = _compiled_lenet()
    batch = np.zeros((2, 1, 1, 32, 32), np.int8)
    with pytest.raises(ValueError, match="backend"):
        net.serve(batch, backend="fast")
    class _Policy:          # shape-only stand-in; rejected before use
        pass
    with pytest.raises(ValueError, match="guarded"):
        net.serve(batch, backend="pallas", guard=_Policy())


def test_resnet8_serving_bit_identical():
    """Residual joins, stride-2 chunks and the GAP pair tree all ride the
    general epilogue path end to end, at B = 1."""
    from repro.models.resnet8 import compile_resnet8, synthetic_image
    net, _ = compile_resnet8()
    img = synthetic_image(5)
    out, _ = net.serve([img], backend="pallas")
    np.testing.assert_array_equal(out[0], net.serve_one(img, backend="fast"))
