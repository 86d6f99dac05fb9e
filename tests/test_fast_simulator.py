"""Differential tests: the vectorized fast-path simulator vs the oracle.

The fast simulator (:mod:`repro.core.fast_simulator`) must be *bit-exact*
against the per-struct Python interpreter on every observable: the decoded
output matrix, the full DRAM image, and the SimReport counters (GeMM/ALU
loop counts, DRAM traffic, instruction trace).  These tests fuzz random
``compile_matmul`` programs (shapes, ALU post-ops, multi-chunk plans),
exercise the pair/indexed ALU forms (including vector-pair SHR), padding
loads, hazard detection, and the LeNet-5 end-to-end chain.

Deliberately hypothesis-free: this suite is part of the tier-1 floor and
must run in minimal environments.
"""

import numpy as np
import pytest

from repro.core import isa
from repro.core.fast_simulator import FastSimulator, compile_plan, plan_for
from repro.core.gemm_compiler import (AluImmOp, AluIndexedImmOp, AluPairOp,
                                      compile_matmul)
from repro.core.hwconfig import VTAConfig, vta_default, vta_tpu
from repro.core.simulator import (FunctionalSimulator, VTAHazardError,
                                  make_simulator, run_program,
                                  verify_program)

_REPORT_FIELDS = ("gemm_loops", "gemm_reset_loops", "alu_loops",
                  "dram_bytes_read", "dram_bytes_written", "insn_executed",
                  "insn_trace")


def assert_backends_identical(prog):
    """Run both backends over the program's DRAM image; every observable
    must match bit-for-bit."""
    oracle = FunctionalSimulator(prog.config, prog.dram_image(), trace=True)
    rep_o = oracle.run(prog.instructions)
    fast = FastSimulator(prog.config, prog.dram_image(), trace=True)
    rep_f = fast.run(prog.instructions)
    np.testing.assert_array_equal(oracle.dram, fast.dram,
                                  err_msg="DRAM image diverged")
    for field in _REPORT_FIELDS:
        assert getattr(rep_o, field) == getattr(rep_f, field), field
    # SRAM end state (stronger than the DRAM check alone)
    np.testing.assert_array_equal(oracle.acc_buf, fast.acc_buf)
    np.testing.assert_array_equal(oracle.inp_buf, fast.inp_buf)
    np.testing.assert_array_equal(oracle.wgt_buf, fast.wgt_buf)
    np.testing.assert_array_equal(oracle.uop_buf, fast.uop_buf)
    return rep_o


# ---------------------------------------------------------------------------
# Differential fuzz over compile_matmul programs
# ---------------------------------------------------------------------------

def _random_alu_ops(rng):
    ops = []
    if rng.random() < 0.5:
        ops.append(AluImmOp.relu())
    if rng.random() < 0.5:
        ops.append(AluImmOp(isa.AluOp.ADD, int(rng.integers(-200, 200))))
    if rng.random() < 0.5:
        ops.append(AluImmOp(isa.AluOp.MIN, int(rng.integers(0, 128))))
    if rng.random() < 0.5:
        ops.append(AluImmOp.shr(int(rng.integers(1, 8))))
    return ops


def test_fuzz_matmul_programs():
    """Random shapes / X preloads / ALU post-ops: fast == oracle."""
    rng = np.random.default_rng(2026)
    for case in range(20):
        m, k, n = (int(rng.integers(1, 70)) for _ in range(3))
        A = rng.integers(-128, 128, (m, k)).astype(np.int8)
        B = rng.integers(-128, 128, (k, n)).astype(np.int8)
        X = None
        if rng.random() < 0.4:
            X = rng.integers(-10**6, 10**6, (m, n)).astype(np.int32)
        prog = compile_matmul(A, B, X=X, alu_ops=_random_alu_ops(rng))
        assert_backends_identical(prog)
        verify_program(prog, backend="fast")


def test_fuzz_multi_chunk_programs():
    """Tiny SRAM forces multi-chunk plans (§3.3 repetition): fast == oracle."""
    rng = np.random.default_rng(7)
    cfg = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                    acc_buff_vectors=64, out_buff_vectors=64,
                    uop_buff_entries=32)
    for case in range(6):
        m = int(rng.integers(20, 100))
        k = int(rng.integers(20, 100))
        n = int(rng.integers(20, 80))
        A = rng.integers(-128, 128, (m, k)).astype(np.int8)
        B = rng.integers(-128, 128, (k, n)).astype(np.int8)
        prog = compile_matmul(A, B, alu_ops=_random_alu_ops(rng), cfg=cfg)
        report = assert_backends_identical(prog)
        assert report.gemm_loops == prog.gemm_loops()
        verify_program(prog, backend="fast")


def test_tpu_profile_fast_backend():
    """block_size=128 exercises the chunked einsum path."""
    rng = np.random.default_rng(3)
    A = rng.integers(-16, 16, (130, 200)).astype(np.int8)
    B = rng.integers(-16, 16, (200, 140)).astype(np.int8)
    prog = compile_matmul(A, B, alu_ops=[AluImmOp.relu()], cfg=vta_tpu())
    assert_backends_identical(prog)


# ---------------------------------------------------------------------------
# ALU pair / indexed forms — incl. the vector-pair SHR regression test
# ---------------------------------------------------------------------------

def test_alu_vector_pair_shr():
    """SHR in vector-pair form: acc[dst] >>= (acc[src] & 31), per lane.

    Regression for the dead conditional in the oracle's SHR handling — the
    imm and vector-pair branches were textually identical; this pins the
    vector-pair semantics on both backends against a numpy reference.
    """
    rng = np.random.default_rng(17)
    A = rng.integers(0, 8, (16, 16)).astype(np.int8)
    B = rng.integers(0, 8, (16, 16)).astype(np.int8)
    # acc rows hold A·B >= 0; shift row 0 by row 1's low 5 bits, etc.
    pairs = ((0, 1), (2, 3), (5, 4))
    prog = compile_matmul(A, B, alu_ops=[AluPairOp(isa.AluOp.SHR, pairs)])
    assert_backends_identical(prog)
    out, _ = run_program(prog)
    acc = A.astype(np.int64) @ B.astype(np.int64)
    for dst, src in pairs:
        acc[dst] = acc[dst] >> (acc[src] & 31)
    np.testing.assert_array_equal(
        out, (acc & 0xFF).astype(np.uint8).view(np.int8))
    verify_program(prog, backend="fast")


def test_alu_pair_and_indexed_program():
    """Pool-style program: ADD pairs into a base row + indexed SHR."""
    rng = np.random.default_rng(23)
    A = rng.integers(-16, 16, (32, 16)).astype(np.int8)
    B = rng.integers(-16, 16, (16, 16)).astype(np.int8)
    pairs = tuple((dst, src) for dst in (0, 4, 8)
                  for src in (dst + 1, dst + 2, dst + 3))
    prog = compile_matmul(A, B, alu_ops=[
        AluPairOp(isa.AluOp.ADD, pairs),
        AluIndexedImmOp(isa.AluOp.SHR, 2, (0, 4, 8)),
    ])
    assert_backends_identical(prog)
    verify_program(prog, backend="fast")


def test_alu_pair_read_after_write_falls_back():
    """A pair chain whose source is an earlier destination (read-after-
    write) must take the sequential fallback and still match the oracle."""
    rng = np.random.default_rng(31)
    A = rng.integers(-8, 8, (16, 16)).astype(np.int8)
    B = rng.integers(-8, 8, (16, 16)).astype(np.int8)
    # acc[1] += acc[2]; acc[0] += acc[1]  — second pair reads the first's dst
    prog = compile_matmul(A, B, alu_ops=[
        AluPairOp(isa.AluOp.ADD, ((1, 2), (0, 1)))])
    assert_backends_identical(prog)
    verify_program(prog, backend="fast")


# ---------------------------------------------------------------------------
# Multi-chunk indexed/pair ALU programs + uop-wave streaming (DESIGN.md §3)
# ---------------------------------------------------------------------------

_SMALL_CFG = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                       acc_buff_vectors=64, out_buff_vectors=64,
                       uop_buff_entries=32)


def _count_uop_loads(prog):
    return sum(1 for i in prog.instructions
               if isinstance(i, isa.MemInsn)
               and i.memory_type == isa.MemId.UOP)


def test_fuzz_multi_chunk_indexed_and_pair_programs():
    """Indexed-imm and pair ALU programs on multi-chunk results — the
    first NotImplementedError ceiling of PR 1.  Pairs are confined to one
    block row/col (always chunk-safe); indices scatter everywhere."""
    rng = np.random.default_rng(2027)
    for case in range(6):
        m = int(rng.integers(40, 100))
        k = int(rng.integers(20, 80))
        n = int(rng.integers(17, 60))
        A = rng.integers(-64, 64, (m, k)).astype(np.int8)
        B = rng.integers(-64, 64, (k, n)).astype(np.int8)
        rh = 16
        alpha = -(-m // rh)
        beta = -(-n // rh)
        n_vec = alpha * beta * rh
        idx = tuple(int(v) for v in
                    rng.choice(n_vec, size=min(n_vec, 40), replace=False))
        pairs = []
        for _ in range(10):
            br = int(rng.integers(0, alpha))
            bc = int(rng.integers(0, beta))
            w0, w1 = rng.choice(rh, size=2, replace=False)
            base = (br * beta + bc) * rh
            pairs.append((base + int(w0), base + int(w1)))
        prog = compile_matmul(
            A, B, cfg=_SMALL_CFG,
            alu_ops=[AluImmOp.relu(),
                     AluPairOp(isa.AluOp.ADD, tuple(pairs)),
                     AluIndexedImmOp(isa.AluOp.SHR, 2, idx)])
        assert prog.chunk_plan.n_chunks > 1
        assert_backends_identical(prog)
        verify_program(prog, backend="fast")


def test_multi_chunk_cross_row_pairs_align_chunk_boundaries():
    """Pairs that span block rows force the planner to cut only at
    group-aligned boundaries; both ends stay in one ACC window."""
    rng = np.random.default_rng(5)
    A = rng.integers(-64, 64, (80, 48)).astype(np.int8)
    B = rng.integers(-64, 64, (48, 16)).astype(np.int8)
    cfg = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=8,
                    acc_buff_vectors=32, out_buff_vectors=32,
                    uop_buff_entries=64)
    rh = 16
    pairs = ((0 * rh + 15, 1 * rh + 0), (2 * rh + 3, 3 * rh + 3))
    prog = compile_matmul(A, B, cfg=cfg,
                          alu_ops=[AluPairOp(isa.AluOp.MAX, pairs)])
    assert prog.chunk_plan.n_chunks > 1
    # every chunk boundary falls between the (0,1) and (2,3) groups
    starts = [s for s, _ in prog.chunk_plan.alpha_segs]
    assert all(s not in (1, 3) for s in starts)
    assert_backends_identical(prog)
    verify_program(prog, backend="fast")


def test_unsplittable_pair_group_is_a_clear_error():
    """A pair group wider than any admissible chunk raises ValueError
    (not a silent wrong answer, not NotImplementedError)."""
    rng = np.random.default_rng(6)
    A = rng.integers(-64, 64, (80, 48)).astype(np.int8)
    B = rng.integers(-64, 64, (48, 16)).astype(np.int8)
    cfg = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                    acc_buff_vectors=16, out_buff_vectors=16,
                    uop_buff_entries=32)   # alpha_c == 1 block row
    with pytest.raises(ValueError, match="spans more than one SRAM chunk"):
        compile_matmul(A, B, cfg=cfg,
                       alu_ops=[AluPairOp(isa.AluOp.ADD, ((15, 16),))])


def test_fuzz_uop_wave_streaming():
    """Programs whose uop lists exceed the buffer stream LOAD_UOP waves —
    the second NotImplementedError ceiling of PR 1.  Fast == oracle on
    every observable, including the extra LOAD UOP traffic."""
    rng = np.random.default_rng(2028)
    for uop_entries in (8, 12, 20):
        cfg = VTAConfig(inp_buff_vectors=64, wgt_buff_matrices=4,
                        acc_buff_vectors=64, out_buff_vectors=64,
                        uop_buff_entries=uop_entries)
        m = int(rng.integers(40, 90))
        k = int(rng.integers(20, 60))
        n = int(rng.integers(10, 40))
        A = rng.integers(-64, 64, (m, k)).astype(np.int8)
        B = rng.integers(-64, 64, (k, n)).astype(np.int8)
        rh = 16
        n_vec = -(-m // rh) * -(-n // rh) * rh
        idx = tuple(int(v) for v in rng.choice(n_vec, size=n_vec // 2,
                                               replace=False))
        prog = compile_matmul(A, B, cfg=cfg,
                              alu_ops=[AluImmOp.relu(),
                                       AluIndexedImmOp(isa.AluOp.ADD, 3, idx)])
        assert _count_uop_loads(prog) > 1, "expected multi-wave streaming"
        assert len(prog.uops) > uop_entries
        assert_backends_identical(prog)
        verify_program(prog, backend="fast")


def test_uop_wave_alu_list_split_across_waves():
    """One indexed ALU op bigger than the whole buffer splits into several
    AluInsns with interleaved LOAD_UOPs; total loop count is preserved."""
    rng = np.random.default_rng(9)
    A = rng.integers(-16, 16, (32, 16)).astype(np.int8)
    B = rng.integers(-16, 16, (16, 16)).astype(np.int8)
    cfg = VTAConfig(inp_buff_vectors=2048, wgt_buff_matrices=1024,
                    acc_buff_vectors=2048, out_buff_vectors=2048,
                    uop_buff_entries=8)
    idx = tuple(range(32))
    prog = compile_matmul(A, B, cfg=cfg,
                          alu_ops=[AluIndexedImmOp(isa.AluOp.SHR, 1, idx)])
    alus = [i for i in prog.instructions if isinstance(i, isa.AluInsn)]
    assert len(alus) > 1
    assert sum(a.loop_count for a in alus) == len(idx)
    assert_backends_identical(prog)
    verify_program(prog, backend="fast")


def test_padded_conv_max_pool_layer_multi_chunk():
    """Same-padded conv + 2×2 max pool compiled multi-chunk: the MAX pair
    program is re-indexed per chunk and bit-exact on both backends."""
    from repro.core.layer_compiler import LayerSpec, compile_layer, verify_layer
    rng = np.random.default_rng(44)
    cfg = VTAConfig(inp_buff_vectors=256, wgt_buff_matrices=64,
                    acc_buff_vectors=128, out_buff_vectors=128,
                    uop_buff_entries=256)
    for pool in ("max2x2", "avg2x2"):
        spec = LayerSpec(
            name=f"c_{pool}", kind="conv",
            weights=rng.integers(-8, 8, (8, 3, 3, 3)).astype(np.int8),
            bias=rng.integers(-100, 100, (8,)).astype(np.int32),
            padding=1, relu=True, pool=pool)
        inp = rng.integers(-32, 64, (1, 3, 16, 16)).astype(np.int8)
        layer = compile_layer(spec, inp, cfg=cfg)
        assert layer.n_chunks > 1
        assert layer.out_h == layer.out_w == 8   # same padding halved once
        rep_o = verify_layer(layer)
        rep_f = verify_layer(layer, backend="fast")
        assert rep_o.gemm_loops == rep_f.gemm_loops
        assert rep_o.alu_loops == rep_f.alu_loops


# ---------------------------------------------------------------------------
# LOAD padding, hazards, plan caching, backend plumbing
# ---------------------------------------------------------------------------

def test_load_with_padding_matches_oracle():
    """Handcrafted LOAD with x/y zero-padding on both sides."""
    cfg = vta_default()
    rng = np.random.default_rng(5)
    dram = rng.integers(0, 256, 4096).astype(np.uint8)
    insns = [
        isa.MemInsn(isa.Opcode.LOAD, isa.MemId.INP, sram_base=3, dram_base=2,
                    y_size=3, x_size=4, x_stride=6,
                    y_pad_0=1, y_pad_1=2, x_pad_0=1, x_pad_1=2),
        isa.FinishInsn(),
    ]
    oracle = FunctionalSimulator(cfg, dram)
    fast = FastSimulator(cfg, dram)
    rep_o = oracle.run(insns)
    rep_f = fast.run(insns)
    np.testing.assert_array_equal(oracle.inp_buf, fast.inp_buf)
    assert rep_o.dram_bytes_read == rep_f.dram_bytes_read


def test_degenerate_store_is_a_noop_on_both_backends():
    """y_size=0 STOREs move nothing; neither backend may raise."""
    cfg = vta_default()
    dram = np.zeros(4096, dtype=np.uint8)
    insns = [
        isa.MemInsn(isa.Opcode.STORE, isa.MemId.OUT, sram_base=0,
                    dram_base=100, y_size=0, x_size=4, x_stride=4),
        isa.FinishInsn(),
    ]
    oracle = FunctionalSimulator(cfg, dram)
    fast = FastSimulator(cfg, dram)
    rep_o = oracle.run(insns)
    rep_f = fast.run(insns)
    np.testing.assert_array_equal(oracle.dram, fast.dram)
    assert rep_o.dram_bytes_written == rep_f.dram_bytes_written == 0


def test_verify_layer_on_both_backends():
    """compile_layer → verify_layer: conv with ReLU + avg-pool exercises
    the pair/indexed ALU program on the fast path."""
    from repro.core.layer_compiler import LayerSpec, compile_layer, verify_layer
    rng = np.random.default_rng(41)
    spec = LayerSpec(name="c1", kind="conv",
                     weights=rng.integers(-8, 8, (6, 1, 5, 5)).astype(np.int8),
                     bias=rng.integers(-100, 100, (6,)).astype(np.int32),
                     relu=True, pool="avg2x2")
    inp = rng.integers(0, 64, (1, 1, 12, 12)).astype(np.int8)
    layer = compile_layer(spec, inp)
    rep_o = verify_layer(layer)
    rep_f = verify_layer(layer, backend="fast")
    assert rep_o.gemm_loops == rep_f.gemm_loops
    assert rep_o.alu_loops == rep_f.alu_loops


def test_fast_backend_detects_hazards():
    """Dropping a push flag trips the shared token checker on both paths."""
    rng = np.random.default_rng(1)
    A = rng.integers(-64, 64, (16, 16)).astype(np.int8)
    B = rng.integers(-64, 64, (16, 16)).astype(np.int8)
    prog = compile_matmul(A, B)
    for i in prog.instructions:
        if isinstance(i, isa.MemInsn) and i.memory_type == isa.MemId.WGT:
            i.dep.push_next = 0
    sim = FastSimulator(prog.config, prog.dram_image())
    with pytest.raises(VTAHazardError):
        sim.run(prog.instructions)


def test_plan_is_cached_on_program():
    rng = np.random.default_rng(9)
    A = rng.integers(-64, 64, (16, 16)).astype(np.int8)
    B = rng.integers(-64, 64, (16, 16)).astype(np.int8)
    prog = compile_matmul(A, B)
    plan1 = plan_for(prog)
    plan2 = plan_for(prog)
    assert plan1 is plan2
    assert plan1.n_insns == len(prog.instructions)
    # a plan compiled standalone matches the cached one's shape
    assert compile_plan(prog.config, prog.instructions).n_insns == \
        plan1.n_insns
    # replacing an instruction object invalidates the cached plan
    prog.instructions[0] = isa.MemInsn(
        isa.Opcode.LOAD, isa.MemId.UOP,
        sram_base=0, dram_base=prog.instructions[0].dram_base,
        y_size=1, x_size=len(prog.uops), x_stride=len(prog.uops))
    assert plan_for(prog) is not plan1


def test_make_simulator_backend_selection():
    cfg = vta_default()
    dram = np.zeros(64, dtype=np.uint8)
    assert isinstance(make_simulator(cfg, dram), FunctionalSimulator)
    assert isinstance(make_simulator(cfg, dram, backend="fast"),
                      FastSimulator)
    with pytest.raises(ValueError):
        make_simulator(cfg, dram, backend="warp")


def test_run_program_backends_agree():
    rng = np.random.default_rng(12)
    A = rng.integers(-64, 64, (24, 40)).astype(np.int8)
    B = rng.integers(-64, 64, (40, 24)).astype(np.int8)
    prog = compile_matmul(A, B, alu_ops=[AluImmOp.relu()])
    out_o, rep_o = run_program(prog)
    out_f, rep_f = run_program(prog, backend="fast")
    np.testing.assert_array_equal(out_o, out_f)
    assert rep_o.gemm_loops == rep_f.gemm_loops


# ---------------------------------------------------------------------------
# LeNet-5 end-to-end on the fast backend
# ---------------------------------------------------------------------------

def test_lenet5_chain_fast_backend():
    from repro.core.network_compiler import compile_network
    from repro.models.lenet import (lenet5_random_weights, lenet5_specs,
                                    synthetic_digit)
    net = compile_network(lenet5_specs(lenet5_random_weights(0)),
                          synthetic_digit(0))
    out_o, reps_o = net.run_functional(check_chaining=False)
    out_f, reps_f = net.run_functional(check_chaining=False, backend="fast")
    np.testing.assert_array_equal(out_o, out_f)
    assert [r.gemm_loops for r in reps_o] == [r.gemm_loops for r in reps_f]
    assert sum(r.gemm_loops for r in reps_f) == 2942      # §5.1
    assert [r.dram_bytes_total for r in reps_o] == \
        [r.dram_bytes_total for r in reps_f]
    net.verify(backend="fast")


@pytest.mark.parametrize("channels, rows, cfg", [
    (48, 100, vta_default()),
    (64, 40, VTAConfig(inp_buff_vectors=512, wgt_buff_matrices=32,
                       acc_buff_vectors=64, out_buff_vectors=64,
                       uop_buff_entries=128))])
def test_band_programs_run_identically(channels, rows, cfg):
    """A grouped GEMM (a depthwise conv's band, ``compile_matmul(band=9)``)
    is ordinary VTA instructions: both backends agree with the oracle,
    and it issues the band's GEMM loops alone."""
    from repro.core.conv_lowering import depthwise_ker2col
    rng = np.random.default_rng(channels)
    A = rng.integers(-128, 128, (rows, 9 * channels)).astype(np.int8)
    W = rng.integers(-8, 9, (channels, 1, 3, 3)).astype(np.int8)
    B = depthwise_ker2col(W)
    bias = rng.integers(-500, 500, channels).astype(np.int32)
    ops = [AluImmOp.shr(5), AluImmOp.relu(), AluImmOp(isa.AluOp.MIN, 48)]
    prog = compile_matmul(A, B, bias=bias, alu_ops=ops, cfg=cfg, band=9)
    rep = assert_backends_identical(prog)
    verify_program(prog, backend="oracle")
    verify_program(prog, backend="fast")
    plan = prog.chunk_plan
    assert rep.gemm_loops == plan.alpha * plan.row_height * 9 * plan.beta
    dense = compile_matmul(A, B, bias=bias, alu_ops=ops, cfg=cfg)
    assert dense.gemm_loops() == rep.gemm_loops * plan.beta
    np.testing.assert_array_equal(prog.expected_out, dense.expected_out)
