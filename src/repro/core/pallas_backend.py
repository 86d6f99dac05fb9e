"""The Pallas backend: compiled VTA layers served on the fused TPU kernel.

Where ``oracle``/``fast``/``batched`` *interpret* a compiled program's
instruction stream, this backend runs the *semantics* the compiler
recorded beside it (DESIGN.md §2): ``NetworkProgram.serve(backend=
"pallas")`` calls :func:`serve_layer` for each layer.  It sends the
layer's int8 activations, and the kernel's one jitted call
(``ops.staged_vta_matmul``) stages A from them on the device and runs
``kernels.vta_gemm`` — compiled on the TPU, interpreted on the CPU the
tests run on, so they run the same kernel body.  W, the fused bias and the
band gather are read once per program from its own segments
(:func:`serving_lowering`) and put on the device once
(:func:`serving_constants`).  No DRAM image is built or decoded per
request.

When the layer's ALU program is exactly the fused-kernel form
(``[relu?][shr?]`` with a row-broadcast bias) the whole layer runs inside
``vta_gemm``; richer programs (pool pair lattices, indexed SHR, residual
ADD) run the GEMM on the kernel and the remaining TensorAlu ops as the
vectorised int32 epilogue below (:func:`apply_alu_epilogue`), which
mirrors ``gemm_compiler``'s reference semantics op for op (wraparound
included).  The commit is the §2.1 truncation, so a pallas serve is bit
for bit the simulators' (pinned by ``tests/test_pallas_backend.py`` and
``tests/test_staged_serving.py``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Tuple

import numpy as np

from . import isa
from .errors import CompileError
from .gemm_compiler import (AluImmOp, AluIndexedImmOp, AluPairOp,
                            AluResidualOp, _wrap_int32, apply_pairs,
                            pair_lattice)
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


# plans and what serving holds for them are made once, whichever serving
# thread asks first
_LOCK = threading.RLock()


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
    # (byte offset, byte size) of the WGT and ACC segments serving reads
    # once; ``acc`` is None when the program has no X preload
    wgt: Tuple[int, int]
    acc: Optional[Tuple[int, int]]
    # the pair ops' lattices, and the result rows (one image's input
    # positions) the pool reads: the pooling part of the epilogue
    pair_lattices: Tuple = dataclasses.field(default=(), compare=False)
    pool_rows: int = 0
    # what serving keeps for this program: :func:`serving_lowering` and
    # its device copy, :func:`serving_constants`
    held: dict = dataclasses.field(default_factory=dict, compare=False,
                                   repr=False)

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
    if plan is None:
        with _LOCK:                 # serving threads share programs
            plan = getattr(prog, "_pallas_plan", None)
            if plan is None:
                plan = prog._pallas_plan = _lower(prog)
    return plan


def _lower(prog) -> PallasPlan:
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
    pair_ops = [spec for spec in prog.alu_ops
                if isinstance(spec, AluPairOp)]
    pooled = np.unique(np.concatenate(
        [np.asarray(spec.pairs, np.int64).reshape(-1) for spec in pair_ops]
        or [np.zeros(0, np.int64)]))
    return PallasPlan(
        alpha=alpha, lam=lam, beta=beta, row_height=rh, block_size=bs,
        valid_shape=tuple(prog.output_meta.valid_shape),
        alu_ops=tuple(prog.alu_ops),
        fused=fused is not None,
        relu=fused[0] if fused else False,
        shift=fused[1] if fused else 0,
        wgt=_span("wgt", lam * beta * bs * bs),
        acc=(_span("acc", alpha * beta * rh * bs * 4)
             if "acc" in prog.regions else None),
        pair_lattices=tuple(pair_lattice(spec.op, spec.pairs)
                            for spec in pair_ops),
        # a vector index → its result row (block-major, §3.2)
        pool_rows=len(np.unique(pooled // (beta * rh) * rh + pooled % rh)))


@dataclasses.dataclass(frozen=True)
class KernelCall:
    """One served layer's kernel call over a batch: the activation stack
    it sends, how the device stages A from it (``ops.stage_rows``), and
    the padded GEMM ``(m, k) @ (k, n)`` with its epilogue."""

    x: Tuple[int, ...]            # (B, C, H, W) conv input, (B, D) fc
    conv: Optional[Tuple[int, int, int, int]]   # (kh, kw, stride, pad)
    gather: int                   # band rows (max3x3s2), 0 for none
    rows: int                     # A's rows per image, padded
    k: int
    n: int
    bias: bool
    relu: bool
    shift: int
    out_dtype: object

    @property
    def m(self) -> int:
        return self.x[0] * self.rows

    def statics(self) -> dict:
        """The static kwargs of ``ops.staged_vta_matmul`` for this call
        (serving's truncating commit, the default blocks)."""
        return dict(conv=self.conv, rows=self.rows, cols=self.k,
                    relu=self.relu, shift=self.shift, saturate=False,
                    out_dtype=self.out_dtype)

    def matmul_args(self, sharding=None):
        """``(operand shapes, static kwargs)`` of the one jitted call
        serving makes (``ops._staged_vta_gemm``: staging, pads,
        ``vta_gemm``, slice; ``interpret`` left to the caller) — what its
        ``lower`` takes."""
        shapes = [(self.x, jnp.int8),
                  ((self.gather,), jnp.int32) if self.gather else None,
                  ((self.k, self.n), jnp.int8),
                  ((self.n,), jnp.int32) if self.bias else None]
        return _shape_structs(shapes, sharding), dict(
            self.statics(), block_m=256, block_n=256, block_k=256)

    def vta_gemm_args(self, sharding=None):
        """``(operand shapes, static kwargs)`` of the padded ``vta_gemm``
        call inside :meth:`matmul_args`'s program — what ``vta_gemm.lower``
        takes."""
        from repro.kernels.ops import gemm_blocks
        g = gemm_blocks(self.m, self.k, self.n)
        shapes = [((g.m, g.k), jnp.int8), ((g.k, g.n), jnp.int8),
                  ((g.n,), jnp.int32) if self.bias else None]
        return _shape_structs(shapes, sharding), dict(
            relu=self.relu, shift=self.shift, saturate=False,
            out_dtype=self.out_dtype, block_m=g.block_m, block_n=g.block_n,
            block_k=g.block_k)


def _shape_structs(shapes, sharding):
    return [jax.ShapeDtypeStruct(*s, sharding=sharding) if s else None
            for s in shapes]


def _layer_gather(layer) -> Optional[np.ndarray]:
    """The layer's band layout of A's rows (``row_gather``), or None."""
    from .layer_compiler import row_gather
    geo = layer.geometry
    if geo is None:
        return None
    return row_gather(layer.spec, geo, layer.program.config.block_size)


def kernel_call(layer, batch: int) -> KernelCall:
    """The kernel call ``serve(backend="pallas")`` makes for the compiled
    ``layer`` over ``batch`` images: A staged on the device from the
    activations, the whole program inside the kernel when its epilogue
    fuses, else the bare int32 GEMM that :func:`apply_alu_epilogue`
    finishes.  The chip-compile rehearsal compiles exactly these calls."""
    p = plan_pallas(layer.program)
    geo = layer.geometry
    gather = _layer_gather(layer)
    mp, np_ = p.padded_shape
    shape = dict(
        x=((batch, layer.input_matrix.shape[1]) if geo is None else
           (batch, geo.in_channels, geo.in_h, geo.in_w)),
        conv=None if geo is None else (geo.kh, geo.kw, geo.stride, geo.pad),
        gather=0 if gather is None else len(gather),
        rows=mp, k=p.lam * p.block_size, n=np_)
    if serving_lowering(layer).fused:
        return KernelCall(**shape, bias=p.acc is not None, relu=p.relu,
                          shift=p.shift, out_dtype=jnp.int8)
    return KernelCall(**shape, bias=False, relu=False, shift=0,
                      out_dtype=jnp.int32)


@dataclasses.dataclass(frozen=True)
class ServingLowering:
    """What every ``serve(backend="pallas")`` of one compiled layer uses
    besides its activations, decoded once from the program's own WGT and
    ACC segments: ``w`` ``(λ·bs, β·bs)`` int8, the fused
    row-broadcast ``bias`` ``(β·bs,)`` int32 or None, the int32 X
    ``preload`` ``(1, α·rh, β·bs)`` the host epilogue adds where the
    layer does not run whole in the kernel, and the band ``gather``."""

    w: np.ndarray
    bias: Optional[np.ndarray]
    preload: Optional[np.ndarray]
    gather: Optional[np.ndarray]
    fused: bool


def _held(p: PallasPlan, key: str, make):
    """``p.held[key]``, made once under a lock (serving threads share
    programs)."""
    found = p.held.get(key)
    if found is None:
        with _LOCK:
            found = p.held.get(key)
            if found is None:
                found = p.held[key] = make()
    return found


def serving_lowering(layer) -> ServingLowering:
    """The layer's :class:`ServingLowering`, cached on its plan.

    A compiled image's X preload is the bias broadcast to every valid row
    and zero in the pad rows, and the activations staged on the device
    give A zero pad rows, so such a preload fuses into the kernel (its
    pad rows read 0·B + 0 as the oracle's do, and are sliced off).  A
    preload of any other form stays with the host epilogue."""
    p = plan_pallas(layer.program)

    def make():
        row = layer.program.dram_image()[None]
        x = _decode_acc32(row, p) if p.acc else None
        bias, fused = None, p.fused
        if x is not None and fused:
            m = p.valid_shape[0]
            if (x[:, :m] == x[:, :1]).all() and (x[:, m:] == 0).all():
                bias, x = x[0, 0], None
            else:
                fused = False
        gather = _layer_gather(layer)
        return ServingLowering(
            w=_decode_wgt(row, p)[0], bias=bias, preload=x,
            gather=None if gather is None else gather.astype(np.int32),
            fused=fused)
    return _held(p, "lowering", make)


def serving_constants(layer) -> Tuple:
    """``(w, bias, gather)`` of :func:`serving_lowering` on the device:
    put once per program, inside a ``vta.const.put`` span."""
    low = serving_lowering(layer)

    def make():
        consts = (low.w, low.bias, low.gather)
        sent = sum(c.nbytes for c in consts if c is not None)
        with span("vta.const.put", bytes=sent):
            return tuple(jax.device_put(consts))
    return _held(plan_pallas(layer.program), "device", make)


# ---------------------------------------------------------------------------
# §3.2 layout: the program's WGT and ACC segments, read once per program,
# and the result's block-major vectors
# ---------------------------------------------------------------------------

def _decode_wgt(stack: np.ndarray, p: PallasPlan) -> np.ndarray:
    """WGT bytes (blocks stored transposed, §3.2) → (B, λ·bs, β·bs) int8."""
    start, size = p.wgt
    raw = stack[:, start:start + size].view(np.int8)
    b, bs = stack.shape[0], p.block_size
    blocks = raw.reshape(b, p.lam, p.beta, bs, bs)   # each block is Bᵀ
    return blocks.transpose(0, 1, 4, 2, 3).reshape(
        b, p.lam * bs, p.beta * bs)


def _decode_acc32(stack: np.ndarray, p: PallasPlan) -> np.ndarray:
    """ACC bytes → (B, α·rh, β·bs) int32 (the X preload)."""
    start, size = p.acc
    raw = stack[:, start:start + size].view("<i4")
    b = stack.shape[0]
    blocks = raw.reshape(b, p.alpha, p.beta, p.row_height, p.block_size)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(
        b, p.alpha * p.row_height, p.beta * p.block_size)


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


def _add32(a: np.ndarray, b) -> np.ndarray:
    """int32 addition with the ALU's wraparound, done in uint32 (whose
    overflow is defined) — equal to the int64 sum wrapped to int32."""
    return (a.view(np.uint32) + np.asarray(b, np.int32).view(np.uint32)) \
        .view(np.int32)


def _imm32(vec: np.ndarray, op: isa.AluOp, imm: int) -> np.ndarray:
    """An immediate op over int32 vectors without widening them: MIN/MAX
    and an arithmetic SHR are exact in int32 (a shift past 31 leaves the
    sign, as in int64), ADD wraps as the ALU does; anything else takes
    the int64 path."""
    if op == isa.AluOp.ADD:
        return _add32(vec, np.int64(imm).astype(np.int32))
    if op in (isa.AluOp.MIN, isa.AluOp.MAX) and -2 ** 31 <= imm < 2 ** 31:
        return (np.minimum if op == isa.AluOp.MIN else np.maximum)(
            vec, np.int32(imm))
    if op == isa.AluOp.SHR and imm >= 0:
        return vec >> np.int32(min(imm, 31))
    return _wrap_int32(_imm_apply(vec.astype(np.int64), op, imm))


def apply_alu_epilogue(vec: np.ndarray, p: PallasPlan,
                       res_vec: Optional[np.ndarray]) -> np.ndarray:
    """``p``'s whole TensorAlu program over (B, n_vec, bs) int32 vectors —
    op-for-op the semantics of ``gemm_compiler.reference_result``.  The
    pooling part (the pair ops and what follows them) runs inside a
    ``vta.pool`` span that counts the input positions it reads."""
    ops = p.alu_ops
    lattices = iter(p.pair_lattices)
    first_pair = next((i for i, spec in enumerate(ops)
                       if isinstance(spec, AluPairOp)), len(ops))
    vec = _alu_ops(vec, ops[:first_pair], res_vec, lattices)
    if first_pair < len(ops):
        with span("vta.pool", positions=len(vec) * p.pool_rows):
            vec = _alu_ops(vec, ops[first_pair:], res_vec, lattices)
    return vec


def _alu_ops(vec: np.ndarray, alu_ops, res_vec: Optional[np.ndarray],
             lattices) -> np.ndarray:
    for spec in alu_ops:
        if isinstance(spec, AluImmOp):
            vec = _imm32(vec, spec.op, spec.imm)
        elif isinstance(spec, AluIndexedImmOp):
            idx = np.asarray(spec.indices, dtype=np.int64)
            vec = vec.copy()
            vec[:, idx] = _imm32(vec[:, idx], spec.op, spec.imm)
        elif isinstance(spec, AluPairOp):
            vec = apply_pairs(vec, spec.op, next(lattices))
        elif isinstance(spec, AluResidualOp):
            if res_vec is None:
                raise CompileError(
                    "AluResidualOp requires a staged residual operand",
                    constraint="residual-operand-missing")
            r = res_vec
            if spec.pre_shift:
                r = _imm32(r, isa.AluOp.SHR, spec.pre_shift)
            if spec.op == isa.AluOp.MIN:
                vec = np.minimum(vec, r)
            elif spec.op == isa.AluOp.MAX:
                vec = np.maximum(vec, r)
            elif spec.op == isa.AluOp.ADD:
                vec = _add32(vec, r)
            elif spec.op == isa.AluOp.SHR:
                vec = vec >> (r & 31)
            else:
                raise CompileError(
                    f"unsupported residual ALU op {spec.op!r}",
                    constraint="pallas-alu-op")
        else:
            raise CompileError(f"unknown ALU spec {type(spec).__name__}",
                               constraint="pallas-alu-op")
    return vec


# ---------------------------------------------------------------------------
# One served layer
# ---------------------------------------------------------------------------

def _epilogue(acc: np.ndarray, p: PallasPlan, x: Optional[np.ndarray],
              res: Optional[np.ndarray]) -> np.ndarray:
    """The int32 GEMM result ``(B, α·rh, β·bs)`` through the X preload and
    the TensorAlu program to the committed int8 result (§2.1 truncation)."""
    with span("vta.epilogue"):
        if x is not None:                           # ACC preload (C = A·B+X)
            acc = _add32(acc, x)
        vec = _to_vectors(acc, p)
        res_vec = _to_vectors(res, p) if res is not None else None
        vec = apply_alu_epilogue(vec, p, res_vec)
        return truncate_int8(_to_matrix(vec, p))


def _report(prog, b: int) -> SimReport:
    report = SimReport()
    report.gemm_loops = b * prog.gemm_loops()
    report.alu_loops = b * prog.alu_loops()
    return report


def serve_layer(layer, x: np.ndarray, res: Optional[np.ndarray]
                ) -> Tuple[np.ndarray, SimReport]:
    """One layer of ``serve(backend="pallas")``, no DRAM image: ``x`` is
    the stacked int8 activations (``(B, C, H, W)``, or ``(B, D)`` for an fc
    layer), ``res`` the int32 residual operand ``(B, α·rh, β·bs)`` or None.
    The device stages A from ``x`` inside the kernel's jitted call; W, the
    bias and the band gather are already there (:func:`serving_constants`).
    Returns the committed ``(B, M, N)`` int8 result at its valid shape."""
    _require_pallas()
    from repro.kernels import ops as kernel_ops
    p = plan_pallas(layer.program)
    b = x.shape[0]
    call = kernel_call(layer, b)
    if x.shape != call.x:
        raise ValueError(
            f"layer {layer.spec.name!r}: activations of shape {x.shape[1:]}, "
            f"compiled for {call.x[1:]} — request shape does not match the "
            f"compiled geometry")
    w, bias, gather = serving_constants(layer)
    with span("vta.kernel"):
        with span("vta.kernel.put", bytes=x.nbytes):
            x = jax.device_put(x)
        out = kernel_ops.staged_vta_matmul(x, gather, w, bias,
                                           **call.statics())
        with span("vta.kernel.fetch", bytes=out.size * out.dtype.itemsize):
            out = np.asarray(out)       # read-only, as jax buffers are
    out = out.reshape(b, call.rows, call.n)
    low = serving_lowering(layer)
    if not low.fused:
        out = _epilogue(out, p, low.preload, res)
    m, n = p.valid_shape
    return out[:, :m, :n], _report(layer.program, b)
