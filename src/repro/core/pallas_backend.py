"""The Pallas backend: compiled VTA programs on the fused TPU kernel.

The fourth backend (DESIGN.md §2): where ``oracle``/``fast``/``batched``
*interpret* the instruction stream, this backend executes the *semantics* a
compiled :class:`~repro.core.program.VTAProgram` encodes — one
``kernels.vta_gemm`` MXU call per program (compiled on the TPU; interpreted
on the CPU the tests run on, so they run the same kernel body) plus a
bit-exact TensorAlu epilogue —
and commits the result to the same DRAM OUT region the simulators write.
Because it reads the INP/WGT/ACC/RES segments and writes OUT bytes through
the §3.2 layout (block-major vectors), it is a drop-in
``make_simulator(backend="pallas")`` engine: ``run_program``,
``NetworkProgram.run_functional/serve_one/serve`` and the differential
conformance suite drive it unchanged, and multi-chunk / LOAD_UOP-wave /
pipelined programs come along for free (chunking is an SRAM-residency
concern; the DRAM-level semantics this backend reproduces are identical).

Semantics contract (pinned by ``tests/test_pallas_backend.py``):

* ``saturate=False`` (default) — faithful §2.1 truncation; OUT bytes are
  **bit-identical** to the oracle for every compiled program (fuzzed in
  ``tests/test_batched_conformance.py``).
* ``saturate=True`` — the kernel's deliberate int8-saturation upgrade; OUT
  equals ``clip(acc, -128, 127)`` of the oracle's pre-truncation ACC.

When the program's ALU epilogue is exactly the fused-kernel form
(``[relu?][shr?]`` with a row-broadcast bias) the whole layer runs inside
``vta_gemm``; richer programs (pool pair lattices, indexed SHR, residual
ADD) run the GEMM on the kernel and the remaining TensorAlu ops as the
vectorised int32 epilogue below, which mirrors ``gemm_compiler``'s
reference semantics op for op (wraparound included).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from . import isa
from .errors import CompileError
from .gemm_compiler import (AluImmOp, AluIndexedImmOp, AluPairOp,
                            AluResidualOp, _wrap_int32)
from .hwconfig import VTAConfig
from .layout import truncate_int8
from .simulator import SimReport
from repro.trace import span

try:  # jax + the kernels layer are optional at import time (clean skips)
    import jax  # noqa: F401
    import jax.numpy as jnp
    HAS_PALLAS = True
    _IMPORT_ERROR = None
except Exception as exc:  # pragma: no cover - exercised only without jax
    HAS_PALLAS = False
    _IMPORT_ERROR = exc


def _require_pallas() -> None:
    if not HAS_PALLAS:  # pragma: no cover - exercised only without jax
        raise CompileError(
            f"the pallas backend needs jax ({_IMPORT_ERROR});"
            f" use backend='fast' or 'oracle'",
            constraint="pallas-jax-missing")


# ---------------------------------------------------------------------------
# Program lowering (cached on the program, like fast_simulator.plan_for)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PallasPlan:
    """Geometry + epilogue lowering for one compiled program.

    ``fused`` marks ALU programs of the exact kernel-epilogue form
    (``[relu?][shr?]``): those run entirely inside ``vta_gemm``.  Region
    offsets are relative to the allocator-local DRAM image, byte sizes
    derived from the §3.2 block grid (α×λ×β, ``row_height``)."""

    alpha: int
    lam: int
    beta: int
    row_height: int
    block_size: int
    valid_shape: Tuple[int, int]
    alu_ops: Tuple
    fused: bool
    relu: bool
    shift: int
    # (byte offset, byte size) per region; None when the program has none
    inp: Tuple[int, int]
    wgt: Tuple[int, int]
    out: Tuple[int, int]
    acc: Optional[Tuple[int, int]]
    res: Optional[Tuple[int, int]]

    @property
    def padded_shape(self) -> Tuple[int, int]:
        return (self.alpha * self.row_height, self.beta * self.block_size)


def _fused_form(alu_ops) -> Optional[Tuple[bool, int]]:
    """``(relu, shift)`` when the epilogue is the kernel-fusable subset."""
    relu, shift = False, 0
    stage = 0                       # 0 = expect relu or shr, 1 = expect shr
    for spec in alu_ops:
        if not isinstance(spec, AluImmOp):
            return None
        if spec.op == isa.AluOp.MAX and spec.imm == 0 and stage == 0:
            relu, stage = True, 1
        elif spec.op == isa.AluOp.SHR and spec.imm >= 0:
            if shift:               # two SHRs do not fuse into one
                return None
            shift, stage = spec.imm, 2
        else:
            return None
    return relu, shift


def plan_pallas(prog) -> PallasPlan:
    """Lower ``prog`` for the pallas backend; cached on the program (the
    compile-once/serve-many contract shared with ``plan_for``)."""
    plan = getattr(prog, "_pallas_plan", None)
    if plan is not None:
        return plan
    if prog.chunk_plan is None or prog.output_meta is None \
            or prog.alu_ops is None:
        raise CompileError(
            f"program {prog.name!r} was not produced by compile_matmul; "
            f"the pallas backend lowers compiler metadata (chunk plan, "
            f"output meta, ALU spec), not raw instruction streams",
            constraint="pallas-program-metadata")
    cfg: VTAConfig = prog.config
    cp = prog.chunk_plan
    bs = cfg.block_size
    alpha, lam, beta, rh = cp.alpha, cp.lam, cp.beta, cp.row_height

    def _span(key: str, nbytes: int) -> Tuple[int, int]:
        region = prog.regions[key]
        return region.phys_addr - prog.allocator.offset, nbytes

    fused = _fused_form(prog.alu_ops)
    plan = PallasPlan(
        alpha=alpha, lam=lam, beta=beta, row_height=rh, block_size=bs,
        valid_shape=tuple(prog.output_meta.valid_shape),
        alu_ops=tuple(prog.alu_ops),
        fused=fused is not None,
        relu=fused[0] if fused else False,
        shift=fused[1] if fused else 0,
        inp=_span("inp", alpha * lam * rh * bs),
        wgt=_span("wgt", lam * beta * bs * bs),
        out=_span("out", alpha * beta * rh * bs),
        acc=(_span("acc", alpha * beta * rh * bs * 4)
             if "acc" in prog.regions else None),
        res=(_span("res", alpha * beta * rh * bs * 4)
             if "res" in prog.regions else None))
    prog._pallas_plan = plan
    return plan


@dataclasses.dataclass(frozen=True)
class KernelCall:
    """The unpadded operands and epilogue of one ``ops.vta_matmul`` call."""

    m: int
    k: int
    n: int
    bias: bool
    relu: bool
    shift: int
    out_dtype: object

    def matmul_args(self, sharding=None):
        """``(operand shapes, static kwargs)`` of the one jitted call
        (``ops._padded_vta_gemm``: pads, ``vta_gemm``, slice) that
        ``ops.vta_matmul`` makes for this call (serving's truncating commit,
        ``vta_matmul``'s default blocks, ``interpret`` left to the caller)
        — what its ``lower`` takes."""
        shapes = [((self.m, self.k), jnp.int8), ((self.k, self.n), jnp.int8),
                  ((self.n,), jnp.int32) if self.bias else None]
        return _shape_structs(shapes, sharding), dict(
            relu=self.relu, shift=self.shift, saturate=False,
            out_dtype=self.out_dtype, block_m=256, block_n=256, block_k=256)

    def vta_gemm_args(self, sharding=None):
        """``(operand shapes, static kwargs)`` of the padded ``vta_gemm``
        call inside :meth:`matmul_args`'s program — what ``vta_gemm.lower``
        takes."""
        from repro.kernels.ops import gemm_blocks
        g = gemm_blocks(self.m, self.k, self.n)
        shapes = [((g.m, g.k), jnp.int8), ((g.k, g.n), jnp.int8),
                  ((g.n,), jnp.int32) if self.bias else None]
        _, statics = self.matmul_args()
        statics.update(block_m=g.block_m, block_n=g.block_n,
                       block_k=g.block_k)
        return _shape_structs(shapes, sharding), statics


def _shape_structs(shapes, sharding):
    return [jax.ShapeDtypeStruct(*s, sharding=sharding) if s else None
            for s in shapes]


def kernel_call(p: PallasPlan, batch: int) -> KernelCall:
    """The kernel call serving makes for ``p`` over a stack of ``batch``
    compiled images (shared weights, broadcast bias, zero pad rows): the
    whole program inside the kernel when its epilogue fuses, else the bare
    int32 GEMM that :func:`apply_alu_epilogue` finishes.  The chip-compile
    rehearsal compiles exactly these calls."""
    mp, np_ = p.padded_shape
    shape = dict(m=batch * mp, k=p.lam * p.block_size, n=np_)
    if p.fused:
        return KernelCall(**shape, bias=p.acc is not None, relu=p.relu,
                          shift=p.shift, out_dtype=jnp.int8)
    return KernelCall(**shape, bias=False, relu=False, shift=0,
                      out_dtype=jnp.int32)


# ---------------------------------------------------------------------------
# §3.2 layout codecs over a (B, nbytes) DRAM stack (B = 1 for one image)
# ---------------------------------------------------------------------------

def _decode_inp(stack: np.ndarray, p: PallasPlan) -> np.ndarray:
    """INP bytes → (B, α·rh, λ·bs) int8 padded A."""
    start, size = p.inp
    raw = stack[:, start:start + size].view(np.int8)
    b = stack.shape[0]
    blocks = raw.reshape(b, p.alpha, p.lam, p.row_height, p.block_size)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(
        b, p.alpha * p.row_height, p.lam * p.block_size)


def _decode_wgt(stack: np.ndarray, p: PallasPlan) -> np.ndarray:
    """WGT bytes (blocks stored transposed, §3.2) → (B, λ·bs, β·bs) int8."""
    start, size = p.wgt
    raw = stack[:, start:start + size].view(np.int8)
    b, bs = stack.shape[0], p.block_size
    blocks = raw.reshape(b, p.lam, p.beta, bs, bs)   # each block is Bᵀ
    return blocks.transpose(0, 1, 4, 2, 3).reshape(
        b, p.lam * bs, p.beta * bs)


def _decode_acc32(stack: np.ndarray, p: PallasPlan,
                  span: Tuple[int, int]) -> np.ndarray:
    """ACC/RES bytes → (B, α·rh, β·bs) int32 (X preload / residual)."""
    start, size = span
    raw = stack[:, start:start + size].view("<i4")
    b = stack.shape[0]
    blocks = raw.reshape(b, p.alpha, p.beta, p.row_height, p.block_size)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(
        b, p.alpha * p.row_height, p.beta * p.block_size)


def _encode_out(stack: np.ndarray, p: PallasPlan, out: np.ndarray) -> None:
    """(B, α·rh, β·bs) int8 result → OUT bytes, committed in place."""
    start, size = p.out
    b = stack.shape[0]
    blocks = out.reshape(b, p.alpha, p.row_height, p.beta, p.block_size)
    raw = np.ascontiguousarray(blocks.transpose(0, 1, 3, 2, 4))
    stack[:, start:start + size] = raw.reshape(b, -1).view(np.uint8)


def _to_vectors(mat: np.ndarray, p: PallasPlan) -> np.ndarray:
    """(B, H, W) → (B, n_vec, bs) block-major result vectors."""
    b = mat.shape[0]
    blocks = mat.reshape(b, p.alpha, p.row_height, p.beta, p.block_size)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(
        b, p.alpha * p.beta * p.row_height, p.block_size)


def _to_matrix(vec: np.ndarray, p: PallasPlan) -> np.ndarray:
    b = vec.shape[0]
    blocks = vec.reshape(b, p.alpha, p.beta, p.row_height, p.block_size)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(
        b, p.alpha * p.row_height, p.beta * p.block_size)


# ---------------------------------------------------------------------------
# The TensorAlu epilogue, vectorised over the batch (oracle semantics)
# ---------------------------------------------------------------------------

def _imm_apply(sel64: np.ndarray, op: isa.AluOp, imm: int) -> np.ndarray:
    if op == isa.AluOp.MIN:
        return np.minimum(sel64, imm)
    if op == isa.AluOp.MAX:
        return np.maximum(sel64, imm)
    if op == isa.AluOp.ADD:
        return sel64 + imm
    if op == isa.AluOp.SHR:
        return sel64 >> imm
    raise CompileError(f"unsupported ALU immediate op {op!r}",
                       constraint="pallas-alu-op")


def _pair_apply(vec: np.ndarray, op: isa.AluOp,
                pairs: Tuple[Tuple[int, int], ...]) -> np.ndarray:
    """``vec[:, dst] = op(vec[:, dst], vec[:, src])`` per pair, in pair
    order.  Disjoint dst/src lattices (every pool/GAP lowering) vectorise
    with duplicate-merging ufuncs — exact for ADD (mod-2³² congruence) and
    MIN/MAX (idempotent merges); anything order-dependent falls back to the
    sequential oracle loop."""
    dst = np.fromiter((d for d, _ in pairs), dtype=np.int64, count=len(pairs))
    src = np.fromiter((s for _, s in pairs), dtype=np.int64, count=len(pairs))
    sequential = (np.intersect1d(dst, src).size > 0
                  or (op not in (isa.AluOp.ADD, isa.AluOp.MIN, isa.AluOp.MAX)
                      and len(np.unique(dst)) != len(dst)))
    if sequential:
        out = vec.copy()
        for d, s in pairs:
            a = out[:, d].astype(np.int64)
            b = out[:, s].astype(np.int64)
            if op == isa.AluOp.MIN:
                r = np.minimum(a, b)
            elif op == isa.AluOp.MAX:
                r = np.maximum(a, b)
            elif op == isa.AluOp.ADD:
                r = a + b
            elif op == isa.AluOp.SHR:
                r = a >> (b & 31)
            else:
                raise CompileError(f"unsupported ALU pair op {op!r}",
                                   constraint="pallas-alu-op")
            out[:, d] = _wrap_int32(r)
        return out
    gathered = vec[:, src].astype(np.int64)
    acc = vec.astype(np.int64)
    idx = (slice(None), dst)
    if op == isa.AluOp.ADD:
        np.add.at(acc, idx, gathered)
    elif op == isa.AluOp.MAX:
        np.maximum.at(acc, idx, gathered)
    elif op == isa.AluOp.MIN:
        np.minimum.at(acc, idx, gathered)
    else:                                       # SHR with unique dst
        acc[idx] = acc[idx] >> (gathered & 31)
    out = vec.copy()
    touched = np.unique(dst)
    out[:, touched] = _wrap_int32(acc[:, touched])
    return out


def apply_alu_epilogue(vec: np.ndarray, alu_ops,
                       res_vec: Optional[np.ndarray]) -> np.ndarray:
    """The full TensorAlu program over (B, n_vec, bs) int32 vectors —
    op-for-op the semantics of ``gemm_compiler.reference_result``."""
    for spec in alu_ops:
        if isinstance(spec, AluImmOp):
            vec = _wrap_int32(_imm_apply(vec.astype(np.int64), spec.op,
                                         spec.imm))
        elif isinstance(spec, AluIndexedImmOp):
            idx = np.asarray(spec.indices, dtype=np.int64)
            vec = vec.copy()
            vec[:, idx] = _wrap_int32(
                _imm_apply(vec[:, idx].astype(np.int64), spec.op, spec.imm))
        elif isinstance(spec, AluPairOp):
            vec = _pair_apply(vec, spec.op, spec.pairs)
        elif isinstance(spec, AluResidualOp):
            if res_vec is None:
                raise CompileError(
                    "AluResidualOp requires a staged residual operand",
                    constraint="residual-operand-missing")
            r = res_vec.astype(np.int64)
            if spec.pre_shift:
                r = _wrap_int32(r >> spec.pre_shift).astype(np.int64)
            a = vec.astype(np.int64)
            if spec.op == isa.AluOp.MIN:
                m = np.minimum(a, r)
            elif spec.op == isa.AluOp.MAX:
                m = np.maximum(a, r)
            elif spec.op == isa.AluOp.ADD:
                m = a + r
            elif spec.op == isa.AluOp.SHR:
                m = a >> (r & 31)
            else:
                raise CompileError(
                    f"unsupported residual ALU op {spec.op!r}",
                    constraint="pallas-alu-op")
            vec = _wrap_int32(m)
        else:
            raise CompileError(f"unknown ALU spec {type(spec).__name__}",
                               constraint="pallas-alu-op")
    return vec


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def _kernel_gemm(a: np.ndarray, b: np.ndarray, bias: Optional[np.ndarray],
                 *, relu: bool, shift: int, saturate: bool, out_dtype,
                 gemm_backend: str) -> np.ndarray:
    """One fused-kernel call (the MXU leg).  ``gemm_backend`` is forwarded
    to ``ops.vta_matmul``: "pallas" (what serving uses) runs the real
    kernel, compiled on the TPU and interpreted only on the CPU; "xla" the
    semantically identical lowered reference; "auto" picks per platform."""
    from repro.kernels import ops as kernel_ops
    with span("vta.kernel"):
        with span("vta.kernel.put"):
            operands = jax.device_put((a, b, bias))
        out = kernel_ops.vta_matmul(
            *operands, relu=relu, shift=shift, saturate=saturate,
            out_dtype=out_dtype, backend=gemm_backend)
        with span("vta.kernel.fetch"):
            return np.asarray(out)      # read-only, as jax buffers are


def _commit_int8(acc: np.ndarray, saturate: bool) -> np.ndarray:
    """ACC → OUT commit: §2.1 truncation, or the saturation upgrade."""
    if saturate:
        return np.clip(acc, -128, 127).astype(np.int8)
    return truncate_int8(acc)


def _execute_stack(prog, stack: np.ndarray, *, saturate: bool,
                   gemm_backend: str) -> SimReport:
    """Run ``prog`` over every DRAM row of ``stack``, writing OUT bytes in
    place.  Weight-uniform batches collapse to a single stacked kernel
    call; varied weights (conformance fuzz) fall back to a per-row GEMM."""
    _require_pallas()
    p = plan_pallas(prog)
    b = stack.shape[0]
    mp, np_ = p.padded_shape
    m, n = p.valid_shape
    with span("vta.decode"):
        a = _decode_inp(stack, p)                   # (B, Mp, Kp)
        w = _decode_wgt(stack, p)                   # (B, Kp, Np)
        x = _decode_acc32(stack, p, p.acc) if p.acc else None
        res = _decode_acc32(stack, p, p.res) if p.res else None
        uniform_w = b == 1 or bool((w == w[0]).all())

        # A row-broadcast preload (the bias form every compiled layer
        # uses) fuses into the kernel.  The kernel broadcasts the bias to
        # *every* row including the §3.2 padding rows, where the oracle
        # adds the stored X pad rows instead — fusing therefore also
        # requires A's pad rows to be zero (true for every compiled image;
        # the conformance fuzz violates it with random bytes and takes the
        # general path), so the pad rows' oracle value is exactly 0 and
        # can be committed directly.  Pad *columns* need no special-casing
        # in either form: the kernel computes them from the same decoded
        # WGT/bias bytes the oracle reads.
        bias = None
        fuse_bias = x is None
        if x is not None and p.fused:
            rows_equal = bool((x[:, :m] == x[:, :1]).all())
            x_pad_zero = bool((x[:, m:] == 0).all())
            a_pad_zero = bool((a[:, m:] == 0).all())
            if rows_equal and x_pad_zero and a_pad_zero:
                bias, fuse_bias = x[:, 0], True
        uniform_bias = bias is None or b == 1 or bool((bias == bias[0]).all())

    if p.fused and fuse_bias:
        # -- whole program inside the kernel --------------------------------
        if uniform_w and uniform_bias:
            out = _kernel_gemm(
                a.reshape(b * mp, -1), w[0],
                bias[0] if bias is not None else None,
                relu=p.relu, shift=p.shift, saturate=saturate,
                out_dtype=jnp.int8, gemm_backend=gemm_backend)
            out = out.reshape(b, mp, np_)
        else:
            out = np.stack([
                _kernel_gemm(a[i], w[i],
                             bias[i] if bias is not None else None,
                             relu=p.relu, shift=p.shift, saturate=saturate,
                             out_dtype=jnp.int8, gemm_backend=gemm_backend)
                for i in range(b)])
        if bias is not None and m < mp:
            out = np.require(out, requirements="W")
            out[:, m:, :] = 0          # oracle pad rows: 0·B + 0 preload
    else:
        # -- kernel GEMM + vectorised TensorAlu epilogue --------------------
        if uniform_w:
            acc = _kernel_gemm(a.reshape(b * mp, -1), w[0], None,
                               relu=False, shift=0, saturate=False,
                               out_dtype=jnp.int32,
                               gemm_backend=gemm_backend).reshape(b, mp, np_)
        else:
            acc = np.stack([
                _kernel_gemm(a[i], w[i], None, relu=False, shift=0,
                             saturate=False, out_dtype=jnp.int32,
                             gemm_backend=gemm_backend)
                for i in range(b)])
        with span("vta.epilogue"):
            if x is not None:                       # ACC preload (C = A·B+X)
                acc = _wrap_int32(acc.astype(np.int64)
                                  + x.astype(np.int64))
            vec = _to_vectors(acc, p)
            res_vec = _to_vectors(res, p) if res is not None else None
            vec = apply_alu_epilogue(vec, p.alu_ops, res_vec)
            out = _commit_int8(_to_matrix(vec, p), saturate)

    with span("vta.encode"):
        _encode_out(stack, p, out)
    report = SimReport()
    report.gemm_loops = b * prog.gemm_loops()
    report.alu_loops = b * prog.alu_loops()
    return report


# ---------------------------------------------------------------------------
# Simulator-shaped engines (make_simulator / run_instructions dispatch)
# ---------------------------------------------------------------------------

class PallasSimulator:
    """Drop-in engine for one DRAM image: ``.run_program(prog)`` executes
    the compiled program on the fused kernel and commits OUT into
    ``self.dram`` — the same observable contract as the simulators."""

    is_batch = False

    def __init__(self, cfg: VTAConfig, dram: np.ndarray, *,
                 saturate: bool = False, gemm_backend: str = "pallas",
                 copy_dram: bool = True, trace: bool = False,
                 count_overflows: bool = False):
        if trace or count_overflows:
            raise ValueError(
                "the pallas backend executes programs as fused kernel "
                "calls; per-instruction trace/overflow accounting needs a "
                "simulator backend (oracle/fast/batched)")
        _require_pallas()
        self.cfg = cfg
        self.dram = np.array(dram, dtype=np.uint8, copy=copy_dram)
        self.saturate = saturate
        self.gemm_backend = gemm_backend

    def run_program(self, prog, *, fault_hook=None) -> SimReport:
        if fault_hook is not None:
            raise ValueError(
                "fault_hook requires per-instruction execution; the pallas "
                "backend has no instruction stream to hook (use "
                "backend='oracle'/'fast'/'batched' for injection)")
        stack = self.dram.reshape(1, -1)
        report = _execute_stack(prog, stack, saturate=self.saturate,
                                gemm_backend=self.gemm_backend)
        self.dram = stack.reshape(-1)
        return report

    def run(self, instructions, *, plan=None, fault_hook=None) -> SimReport:
        raise CompileError(
            "the pallas backend lowers compiled programs, not raw "
            "instruction streams; call run_program(prog) (run_instructions "
            "dispatches automatically when a program is passed)",
            constraint="pallas-program-metadata")


class BatchPallasSimulator(PallasSimulator):
    """The batch-axis variant over a ``(batch, nbytes)`` DRAM stack —
    weight-uniform batches execute as one stacked kernel call."""

    is_batch = True

    def __init__(self, cfg: VTAConfig, dram_stack: np.ndarray, **kw):
        super().__init__(cfg, np.atleast_2d(dram_stack), **kw)

    def run_program(self, prog, *, fault_hook=None) -> SimReport:
        if fault_hook is not None:
            raise ValueError(
                "fault_hook requires per-instruction execution; the pallas "
                "backend has no instruction stream to hook (use "
                "backend='oracle'/'fast'/'batched' for injection)")
        return _execute_stack(prog, self.dram, saturate=self.saturate,
                              gemm_backend=self.gemm_backend)


def run_program_pallas(prog, *, saturate: bool = False,
                       gemm_backend: str = "pallas"
                       ) -> Tuple[np.ndarray, SimReport]:
    """Convenience driver: execute one compiled program on the pallas
    backend; returns the decoded unpadded (M, N) result + report."""
    from .simulator import decode_out_region
    sim = PallasSimulator(prog.config, prog.dram_image(), saturate=saturate,
                          gemm_backend=gemm_backend, copy_dram=False)
    report = sim.run_program(prog)
    return decode_out_region(prog, sim.dram), report
