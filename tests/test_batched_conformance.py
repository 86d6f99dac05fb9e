"""Differential conformance suite: the batched runtime vs the per-image
oracle (DESIGN.md §Batching).

The contract under test: executing one compiled :class:`InstructionPlan`
over a ``(batch, nbytes)`` DRAM stack (:func:`repro.core.fast_simulator.
run_batch` / :class:`BatchFastSimulator`) is **bit-identical** to looping
the single-image oracle interpreter over the stack's rows — on the full
DRAM image, every SRAM buffer's end state, the instruction trace, and the
report counters (batch totals = sums of the per-image oracle reports).

Coverage: random ``compile_matmul`` programs with random batch sizes
(1–16), multi-chunk plans, LOAD_UOP wave streaming, padded-conv/max-pool
layer programs, stride-2 downsampling convs and global-avg-pool tree
reductions (DESIGN.md §Strided-lowering), and handcrafted streams whose
UOP/WGT DRAM regions differ *per batch row* (driving the non-uniform
general paths the serving workload never hits).

Every drawn workload is additionally recompiled with
``schedule="pipelined"`` (DESIGN.md §Pipeline): the double-buffered
stream must pass the full validator (dep-token dry run + concurrent
hazard check), stay batch == oracle-loop bit-identical, and produce the
serialized program's OUT bytes on all three backends.

The seeded fuzz below is hypothesis-free (tier-1 floor); an equivalent
hypothesis property runs when the optional dependency is installed.
"""

import numpy as np
import pytest

from repro.core import isa
from repro.core.fast_simulator import (BatchFastSimulator, FastSimulator,
                                       plan_for, run_batch)
from repro.core.gemm_compiler import (AluImmOp, AluIndexedImmOp, AluPairOp,
                                      compile_matmul)
from repro.core.hwconfig import VTAConfig, vta_default
from repro.core.layer_compiler import LayerSpec, compile_layer
from repro.core.simulator import FunctionalSimulator
from repro.harden.guards import validate_program

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:          # optional dev dependency
    HAS_HYPOTHESIS = False

_SUM_FIELDS = ("gemm_loops", "gemm_reset_loops", "alu_loops",
               "dram_bytes_read", "dram_bytes_written")


# ---------------------------------------------------------------------------
# The conformance oracle
# ---------------------------------------------------------------------------

def varied_stack(prog, rng, batch, vary=("inp", "acc")):
    """Per-request DRAM stack: row 0 keeps the compiled image, rows 1..
    get random bytes in the ``vary`` regions (INP/ACC vary per request in
    serving; varying WGT/UOP drives the non-uniform batch paths)."""
    base = prog.dram_image()
    stack = np.broadcast_to(base, (batch, base.size)).copy()
    for b in range(1, batch):
        for name in vary:
            if name not in prog.regions:
                continue
            region = prog.regions[name]
            start = region.phys_addr - prog.allocator.offset
            stack[b, start:start + region.nbytes] = rng.integers(
                0, 256, region.nbytes, dtype=np.uint8)
    return stack


def assert_batch_matches_oracle_loop(cfg, instructions, stack, *,
                                     plan=None):
    """Run the batch engine once and the oracle per row; every observable
    must match bit-for-bit.  Returns the batched report."""
    bsim = BatchFastSimulator(cfg, stack, trace=True)
    rep_b = bsim.run(instructions, plan=plan)
    totals = {f: 0 for f in _SUM_FIELDS}
    for b in range(stack.shape[0]):
        osim = FunctionalSimulator(cfg, stack[b], trace=True)
        rep_o = osim.run(instructions)
        np.testing.assert_array_equal(
            bsim.dram[b], osim.dram, err_msg=f"DRAM row {b} diverged")
        np.testing.assert_array_equal(bsim.uop_buf[b], osim.uop_buf)
        np.testing.assert_array_equal(bsim.inp_buf[b], osim.inp_buf)
        np.testing.assert_array_equal(bsim.wgt_buf[b], osim.wgt_buf)
        np.testing.assert_array_equal(bsim.acc_buf[b], osim.acc_buf)
        np.testing.assert_array_equal(bsim.out_buf[b], osim.out_buf)
        assert rep_o.insn_executed == rep_b.insn_executed
        assert rep_o.insn_trace == rep_b.insn_trace
        for f in _SUM_FIELDS:
            totals[f] += getattr(rep_o, f)
    for f in _SUM_FIELDS:            # batch totals == oracle-loop sums
        assert getattr(rep_b, f) == totals[f], f
    return rep_b


# ---------------------------------------------------------------------------
# Pipelined-schedule conformance (DESIGN.md §Pipeline)
# ---------------------------------------------------------------------------

def _out_bytes_after(prog, backend):
    """Execute ``prog`` on one backend; return its OUT region bytes (the
    decoded-result source, layout-independent of the chunk plan)."""
    if backend == "batched":
        sim = BatchFastSimulator(prog.config, prog.dram_image()[None].copy())
        sim.run(prog.instructions, plan=plan_for(prog))
        dram = sim.dram[0]
    else:
        cls = FunctionalSimulator if backend == "oracle" else FastSimulator
        sim = cls(prog.config, prog.dram_image())
        sim.run(prog.instructions)
        dram = sim.dram
    region = prog.regions["out"]
    start = region.phys_addr - prog.allocator.offset
    return dram[start:start + region.nbytes].copy()


def assert_pipelined_variant_conforms(prog_s, prog_p, rng, batch=3):
    """The §Pipeline contract for one drawn workload: the pipelined
    stream passes the full validator (including the concurrent-hazard
    check), stays batch == oracle-loop bit-identical on a varied stack,
    and matches the serialized OUT bytes on every backend."""
    assert prog_p.schedule == "pipelined", "expected a pipelined stream"
    validate_program(prog_p)
    stack = varied_stack(prog_p, rng, batch)
    assert_batch_matches_oracle_loop(prog_p.config, prog_p.instructions,
                                     stack, plan=plan_for(prog_p))
    ref = _out_bytes_after(prog_s, "oracle")
    for backend in ("oracle", "fast", "batched"):
        np.testing.assert_array_equal(
            _out_bytes_after(prog_p, backend), ref,
            err_msg=f"pipelined {backend} diverged from serialized")


def _random_alu_ops(rng):
    ops = []
    if rng.random() < 0.5:
        ops.append(AluImmOp.relu())
    if rng.random() < 0.5:
        ops.append(AluImmOp(isa.AluOp.ADD, int(rng.integers(-200, 200))))
    if rng.random() < 0.4:
        ops.append(AluImmOp(isa.AluOp.MIN, int(rng.integers(0, 128))))
    if rng.random() < 0.5:
        ops.append(AluImmOp.shr(int(rng.integers(1, 8))))
    return ops


# ---------------------------------------------------------------------------
# Seeded differential fuzz (hypothesis-free tier-1 floor)
# ---------------------------------------------------------------------------

def test_fuzz_random_programs_random_batch_sizes():
    """Random shapes / ALU post-ops / X preloads × batch sizes 1–16."""
    rng = np.random.default_rng(303)
    for case in range(8):
        m, k, n = (int(rng.integers(1, 50)) for _ in range(3))
        A = rng.integers(-128, 128, (m, k)).astype(np.int8)
        B = rng.integers(-128, 128, (k, n)).astype(np.int8)
        X = None
        if rng.random() < 0.4:
            X = rng.integers(-10**6, 10**6, (m, n)).astype(np.int32)
        ops = _random_alu_ops(rng)
        prog = compile_matmul(A, B, X=X, alu_ops=ops)
        batch = int(rng.integers(1, 17))
        stack = varied_stack(prog, rng, batch)
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_matmul(A, B, X=X, alu_ops=ops,
                                schedule="pipelined")
        assert_pipelined_variant_conforms(prog, prog_p, rng)


def test_fuzz_varied_weights_drive_nonuniform_gemm():
    """Rows with *different* WGT bytes: the uniform-weights latch must
    drop and the per-image weight gather must still match the oracle."""
    rng = np.random.default_rng(304)
    for case in range(4):
        m, k, n = (int(rng.integers(4, 40)) for _ in range(3))
        A = rng.integers(-128, 128, (m, k)).astype(np.int8)
        B = rng.integers(-128, 128, (k, n)).astype(np.int8)
        ops = _random_alu_ops(rng)
        prog = compile_matmul(A, B, alu_ops=ops)
        stack = varied_stack(prog, rng, int(rng.integers(2, 9)),
                             vary=("inp", "acc", "wgt"))
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_matmul(A, B, alu_ops=ops, schedule="pipelined")
        assert_pipelined_variant_conforms(prog, prog_p, rng)


_SMALL_CFG = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                       acc_buff_vectors=64, out_buff_vectors=64,
                       uop_buff_entries=32)


def test_fuzz_multi_chunk_programs_batched():
    """Tiny SRAM forces §3.3 multi-chunk plans; batched == oracle loop."""
    rng = np.random.default_rng(305)
    for case in range(3):
        m = int(rng.integers(30, 80))
        k = int(rng.integers(20, 60))
        n = int(rng.integers(17, 50))
        A = rng.integers(-64, 64, (m, k)).astype(np.int8)
        B = rng.integers(-64, 64, (k, n)).astype(np.int8)
        ops = _random_alu_ops(rng)
        prog = compile_matmul(A, B, alu_ops=ops, cfg=_SMALL_CFG)
        assert prog.chunk_plan.n_chunks > 1
        stack = varied_stack(prog, rng, int(rng.integers(2, 7)))
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_matmul(A, B, alu_ops=ops, cfg=_SMALL_CFG,
                                schedule="pipelined")
        assert_pipelined_variant_conforms(prog, prog_p, rng)


def test_fuzz_uop_wave_streaming_batched():
    """Programs streaming LOAD_UOP waves mid-execution: the cached plan
    must observe the refilled slots identically on every batch row."""
    rng = np.random.default_rng(306)
    for uop_entries in (8, 16):
        cfg = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                        acc_buff_vectors=64, out_buff_vectors=64,
                        uop_buff_entries=uop_entries)
        m = int(rng.integers(34, 70))
        k = int(rng.integers(20, 50))
        n = int(rng.integers(10, 34))
        A = rng.integers(-64, 64, (m, k)).astype(np.int8)
        B = rng.integers(-64, 64, (k, n)).astype(np.int8)
        rh = 16
        n_vec = -(-m // rh) * -(-n // rh) * rh
        idx = tuple(int(v) for v in rng.choice(n_vec, size=n_vec // 2,
                                               replace=False))
        ops = [AluImmOp.relu(), AluIndexedImmOp(isa.AluOp.ADD, 3, idx)]
        prog = compile_matmul(A, B, cfg=cfg, alu_ops=ops)
        n_uop_loads = sum(1 for i in prog.instructions
                          if isinstance(i, isa.MemInsn)
                          and i.memory_type == isa.MemId.UOP)
        assert n_uop_loads > 1, "expected multi-wave streaming"
        stack = varied_stack(prog, rng, int(rng.integers(2, 7)))
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_matmul(A, B, cfg=cfg, alu_ops=ops,
                                schedule="pipelined")
        assert_pipelined_variant_conforms(prog, prog_p, rng)


def test_padded_conv_and_pool_pairs_batched():
    """Same-padded conv + 2×2 max/avg pooling layers (multi-chunk): the
    pair/indexed ALU programs must be bit-exact across the batch."""
    rng = np.random.default_rng(307)
    cfg = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                    acc_buff_vectors=128, out_buff_vectors=128,
                    uop_buff_entries=256)
    for pool in ("max2x2", "avg2x2"):
        spec = LayerSpec(
            name=f"c_{pool}", kind="conv",
            weights=rng.integers(-8, 8, (8, 3, 3, 3)).astype(np.int8),
            bias=rng.integers(-100, 100, (8,)).astype(np.int32),
            padding=1, relu=True, pool=pool)
        inp = rng.integers(-32, 64, (1, 3, 12, 12)).astype(np.int8)
        layer = compile_layer(spec, inp, cfg=cfg)
        assert layer.n_chunks > 1
        prog = layer.program
        stack = varied_stack(prog, rng, 5)
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_layer(spec, inp, cfg=cfg,
                               schedule="pipelined").program
        assert_pipelined_variant_conforms(prog, prog_p, rng)


def test_fuzz_strided_conv_programs_batched():
    """Stride-2 downsampling convs (k3/s2/p1 halving and k2/s2 projection
    geometry, DESIGN.md §Strided-lowering) drawn at random: the batched
    runtime must match the per-image oracle bit for bit."""
    rng = np.random.default_rng(308)
    for case in range(6):
        c = int(rng.integers(1, 5))
        f = int(rng.integers(1, 9))
        hw = int(rng.choice([8, 12, 16]))
        k, pad = (3, 1) if rng.random() < 0.5 else (2, 0)
        spec = LayerSpec(
            f"s2_{case}", "conv",
            rng.integers(-8, 8, (f, c, k, k)).astype(np.int8),
            rng.integers(-100, 100, (f,)).astype(np.int32),
            stride=2, padding=pad, relu=bool(rng.integers(2)))
        inp = rng.integers(-32, 64, (1, c, hw, hw)).astype(np.int8)
        layer = compile_layer(spec, inp)
        assert (layer.out_h, layer.out_w) == (hw // 2, hw // 2)
        prog = layer.program
        stack = varied_stack(prog, rng, int(rng.integers(2, 7)))
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_layer(spec, inp, schedule="pipelined").program
        assert_pipelined_variant_conforms(prog, prog_p, rng)


def test_fuzz_gap_reduction_programs_batched():
    """Global-avg-pool tree reductions: log2(H·W) ADD-pair rounds + one
    SHR over the surviving row, including a β-chunked result (the tree
    pins α into one chunk; the block columns still tile) and a program
    small enough that its pair uops stream in LOAD_UOP waves."""
    rng = np.random.default_rng(309)
    cfgs = (vta_default(),
            VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                      acc_buff_vectors=64, out_buff_vectors=64,
                      uop_buff_entries=32))
    for case in range(6):
        cfg = cfgs[case % 2]
        c = int(rng.integers(1, 5))
        if case % 2 == 0:
            f, hw = int(rng.integers(1, 9)), int(rng.choice([4, 8]))
        else:                                  # β-chunked under the tiny ACC
            f, hw = int(rng.integers(60, 90)), 4
        spec = LayerSpec(
            f"gap_{case}", "conv",
            rng.integers(-6, 7, (f, c, 1, 1)).astype(np.int8),
            rng.integers(-50, 50, (f,)).astype(np.int32),
            relu=bool(rng.integers(2)), pool="gap")
        inp = rng.integers(-32, 64, (1, c, hw, hw)).astype(np.int8)
        layer = compile_layer(spec, inp, cfg=cfg)
        assert layer.keep_rows == (0,)
        if case % 2 == 1:
            assert layer.n_chunks > 1          # β tiles, α stays whole
        prog = layer.program
        stack = varied_stack(prog, rng, int(rng.integers(2, 7)))
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_layer(spec, inp, cfg=cfg,
                               schedule="pipelined").program
        assert_pipelined_variant_conforms(prog, prog_p, rng)


# ---------------------------------------------------------------------------
# Handcrafted per-row UOP/WGT divergence (non-uniform general paths)
# ---------------------------------------------------------------------------

def _uop_word(acc, inp, wgt):
    return acc | (inp << 11) | (wgt << 22)


def _handcrafted_stream(nu):
    """LOAD UOP/INP/WGT/ACC → GEMM reset → GEMM → ALU imm → ALU pair →
    STORE OUT.  All dep flags zero (single-stream execution).  Logical
    DRAM bases are in per-kind struct units over one 16 KiB image."""
    return [
        isa.MemInsn(isa.Opcode.LOAD, isa.MemId.UOP, sram_base=0,
                    dram_base=0, y_size=1, x_size=nu, x_stride=nu),
        isa.MemInsn(isa.Opcode.LOAD, isa.MemId.INP, sram_base=0,
                    dram_base=64, y_size=2, x_size=4, x_stride=6,
                    x_pad_0=1, y_pad_1=1),
        isa.MemInsn(isa.Opcode.LOAD, isa.MemId.WGT, sram_base=0,
                    dram_base=8, y_size=1, x_size=2, x_stride=2),
        isa.MemInsn(isa.Opcode.LOAD, isa.MemId.ACC, sram_base=0,
                    dram_base=64, y_size=2, x_size=8, x_stride=20),
        isa.GemInsn(reset=1, uop_bgn=0, uop_end=nu, iter_out=1, iter_in=2,
                    acc_factor_in=4),
        isa.GemInsn(uop_bgn=0, uop_end=nu, iter_out=2, iter_in=2,
                    acc_factor_out=8, acc_factor_in=4,
                    inp_factor_out=2, inp_factor_in=1,
                    wgt_factor_out=1),
        isa.AluInsn(alu_opcode=isa.AluOp.ADD, uop_bgn=0, uop_end=nu,
                    iter_out=2, iter_in=1, dst_factor_out=8,
                    use_imm=1, imm=5),
        # dst from uop[0] (0..15), src from uop[1] (0..7): overlapping →
        # the sequential (oracle-order) fallback on every backend
        isa.AluInsn(alu_opcode=isa.AluOp.ADD, uop_bgn=0, uop_end=nu,
                    iter_out=1, iter_in=1),
        isa.MemInsn(isa.Opcode.STORE, isa.MemId.OUT, sram_base=0,
                    dram_base=512, y_size=1, x_size=16, x_stride=16),
        isa.FinishInsn(),
    ]


def _handcrafted_stack(rng, batch, nu, *, vary_uops, vary_wgt):
    cfg = vta_default()
    stack = np.zeros((batch, 16384), dtype=np.uint8)
    for b in range(batch):
        salt = b if vary_uops else 0
        words = np.array([_uop_word((k + salt) % 16,
                                    (k * 3 + salt) % 8,
                                    (k + salt) % 2)
                          for k in range(nu)], dtype="<u4")
        stack[b, :nu * 4] = words.view(np.uint8)
        wsalt = rng.integers(0, 256, 2 * 256, dtype=np.uint8)
        stack[b, 2048:2048 + 2 * 256] = wsalt if vary_wgt else 0
        stack[b, 1024:1024 + 16 * 16] = rng.integers(
            0, 256, 256, dtype=np.uint8)          # INP always per-row
        stack[b, 4096:4096 + 28 * 64] = rng.integers(
            0, 256, 28 * 64, dtype=np.uint8)      # ACC always per-row
    if not vary_wgt:
        stack[:, 2048:2048 + 2 * 256] = rng.integers(
            0, 256, 2 * 256, dtype=np.uint8)[None]
    return cfg, stack


@pytest.mark.parametrize("vary_uops,vary_wgt", [
    (True, True),       # fully divergent rows: general paths everywhere
    (False, True),      # shared lattice, per-row weight gather
    (False, False),     # uniform: shared fast paths
])
def test_handcrafted_per_row_uop_wgt_divergence(vary_uops, vary_wgt):
    rng = np.random.default_rng(99)
    nu = 24
    cfg, stack = _handcrafted_stack(rng, batch=6, nu=nu,
                                    vary_uops=vary_uops, vary_wgt=vary_wgt)
    insns = _handcrafted_stream(nu)
    rep = assert_batch_matches_oracle_loop(cfg, insns, stack)
    assert rep.gemm_loops == 6 * 2 * 2 * nu      # batch × iter lattice


def test_uniformity_latch_observed():
    """The latch must be True for identical rows and drop when a load
    reads per-row bytes."""
    rng = np.random.default_rng(7)
    nu = 8
    cfg, stack = _handcrafted_stack(rng, batch=4, nu=nu,
                                    vary_uops=True, vary_wgt=True)
    sim = BatchFastSimulator(cfg, stack)
    sim.run(_handcrafted_stream(nu))
    assert not sim._uniform["uop"] and not sim._uniform["wgt"]
    cfg, stack = _handcrafted_stack(rng, batch=4, nu=nu,
                                    vary_uops=False, vary_wgt=False)
    sim = BatchFastSimulator(cfg, stack)
    sim.run(_handcrafted_stream(nu))
    assert sim._uniform["uop"] and sim._uniform["wgt"]


def test_extreme_values_at_f32_exactness_boundary():
    """Worst-case int8 magnitudes ((-128)·(-128) products) with contraction
    lengths at and just past the float32-exactness limit: the fused BLAS
    path runs at its bound and the fallback takes over beyond it, both
    bit-identical to the oracle.  Regression for the 127·128 vs 128·128
    product-bound error."""
    rng = np.random.default_rng(404)
    for k in (1024, 1040):            # c·bs == 1024 (limit), 1040 (beyond)
        A = np.full((16, k), -128, dtype=np.int8)
        B = np.full((k, 16), -128, dtype=np.int8)
        A[0, :7] = 127                # mix in the positive extreme
        B[:5, 3] = 127
        prog = compile_matmul(A, B)
        stack = varied_stack(prog, rng, 3)
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))


# ---------------------------------------------------------------------------
# run_batch API
# ---------------------------------------------------------------------------

def test_run_batch_returns_stack_and_batch_totals():
    rng = np.random.default_rng(11)
    A = rng.integers(-64, 64, (24, 24)).astype(np.int8)
    B = rng.integers(-64, 64, (24, 24)).astype(np.int8)
    prog = compile_matmul(A, B, alu_ops=[AluImmOp.relu()])
    batch = 3
    stack = varied_stack(prog, rng, batch)
    out_stack, rep = run_batch(prog.config, stack, prog.instructions,
                               plan=plan_for(prog))
    assert out_stack.shape == stack.shape
    assert rep.gemm_loops == batch * prog.gemm_loops()
    # batch of one over the unmodified image == the single-image program
    one, rep1 = run_batch(prog.config, prog.dram_image()[None],
                          prog.instructions)
    single = FunctionalSimulator(prog.config, prog.dram_image())
    single.run(prog.instructions)
    np.testing.assert_array_equal(one[0], single.dram)
    assert rep1.gemm_loops == prog.gemm_loops()


def test_batched_rejects_bad_stacks():
    cfg = vta_default()
    with pytest.raises(ValueError):
        BatchFastSimulator(cfg, np.zeros(64, dtype=np.uint8))
    with pytest.raises(TypeError):
        BatchFastSimulator(cfg, np.zeros((2, 64), dtype=np.int8))


# ---------------------------------------------------------------------------
# Hypothesis property (skips cleanly when the dependency is absent)
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(m=st.integers(1, 40), k=st.integers(1, 40), n=st.integers(1, 40),
           batch=st.integers(1, 16), seed=st.integers(0, 2**31 - 1),
           relu=st.booleans(), shr=st.integers(0, 6))
    def test_hypothesis_run_batch_bit_identical(m, k, n, batch, seed,
                                                relu, shr):
        rng = np.random.default_rng(seed)
        A = rng.integers(-128, 128, (m, k)).astype(np.int8)
        B = rng.integers(-128, 128, (k, n)).astype(np.int8)
        ops = ([AluImmOp.relu()] if relu else []) + \
            ([AluImmOp.shr(shr)] if shr else [])
        prog = compile_matmul(A, B, alu_ops=ops)
        stack = varied_stack(prog, rng, batch)
        assert_batch_matches_oracle_loop(prog.config, prog.instructions,
                                         stack, plan=plan_for(prog))
        prog_p = compile_matmul(A, B, alu_ops=ops, schedule="pipelined")
        assert_pipelined_variant_conforms(prog, prog_p, rng)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_hypothesis_run_batch_bit_identical():
        pass
