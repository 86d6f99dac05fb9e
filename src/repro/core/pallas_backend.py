"""The Pallas backend: compiled VTA layers served on the fused TPU kernel.

Where ``oracle``/``fast``/``batched`` *interpret* a compiled program's
instruction stream, this backend runs the *semantics* the compiler
recorded beside it (DESIGN.md §2): ``NetworkProgram.serve(backend=
"pallas")`` calls :func:`serve_layer` for each layer.  It sends the
layer's int8 activations (and the skip's, at a join), and the kernel's one
jitted call (``ops.staged_vta_matmul``) stages A from them on the device,
runs ``kernels.vta_gemm`` — compiled on the TPU, interpreted on the CPU
the tests run on, so they run the same kernel body — and returns the
committed int8 rows the host reads.  W, the fused bias, the band gather
and the epilogue's operands are read once per program from its own
segments and ALU spec (:func:`serving_lowering`) and put on the device
once (:func:`serving_constants`).  No DRAM image is built or decoded per
request, and no int32 result comes back.

When the layer's ALU program is exactly the fused-kernel form
(``[relu?][shr?]`` with a row-broadcast bias) the whole layer runs inside
``vta_gemm``; richer programs (pool pair lattices, indexed SHR, residual
ADD, ReLU6's MIN) run the GEMM on the kernel and the TensorAlu program
after it in the same call (``ops.alu_epilogue``), op for op
``gemm_compiler``'s reference semantics (wraparound included).  A
depthwise layer's grouped GEMM runs as ``kernels.vta_dwconv`` on the VPU,
from the layer's taps, with its bias, its epilogue after it.  The commit
is the §2.1 truncation, so a pallas serve is bit for bit the simulators'
(pinned by
``tests/test_pallas_backend.py`` and ``tests/test_staged_serving.py``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Tuple

import numpy as np

from . import isa
from .errors import CompileError
from .gemm_compiler import (AluImmOp, AluIndexedImmOp, AluPairOp,
                            AluResidualOp, pair_lattice)
from .hwconfig import VTAConfig
from .simulator import SimReport
from repro.trace import span

try:  # jax + the kernels layer are optional at import time (clean skips)
    import jax  # noqa: F401
    import jax.numpy as jnp
    from repro.kernels.ops import AluStep
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
    """Geometry + ALU spec of one compiled program.

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
    band: int = 0                   # a grouped GEMM's (ChunkPlan.band)
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
    return PallasPlan(
        alpha=alpha, lam=lam, beta=beta, row_height=rh, block_size=bs,
        valid_shape=tuple(prog.output_meta.valid_shape),
        alu_ops=tuple(prog.alu_ops),
        fused=fused is not None,
        relu=fused[0] if fused else False,
        shift=fused[1] if fused else 0,
        wgt=_span("wgt", prog.regions["wgt"].nbytes),
        acc=(_span("acc", alpha * beta * rh * bs * 4)
             if "acc" in prog.regions else None),
        band=cp.band)


@dataclasses.dataclass(frozen=True)
class KernelCall:
    """One served layer's kernel call over a batch: the activation stack
    it sends (and the skip's, at a join), how the device stages A from it
    (``ops.stage_rows``), the padded GEMM ``(m, k) @ (k, n)``, the
    TensorAlu epilogue after it where the kernel does not fuse the
    layer's, and the rows and columns of each image's result that come
    back."""

    x: Tuple[int, ...]            # (B, C, H, W) conv input, (B, D) fc
    conv: Optional[Tuple[int, int, int, int]]   # (kh, kw, stride, pad)
    gather: int                   # band rows (max3x3s2), 0 for none
    rows: int                     # A's rows per image, padded
    k: int
    n: int
    bias: bool
    relu: bool
    shift: int
    out_dtype: object             # the kernel's: int32 under an epilogue
    valid: Tuple[int, int]        # each image's result (M, N)
    keep: int = 0                 # rows a pooled layer keeps, 0 for all
    res: Optional[Tuple[int, ...]] = None   # the skip activations' shape
    preload: bool = False         # an X preload (rows, n) int32 operand
    epilogue: Optional[Tuple] = None        # ``ops.AluStep``s, or fused
    tables: Tuple[int, ...] = ()  # the epilogue's index tables' lengths
    depthwise: bool = False       # ``vta_dwconv`` on (k, n) taps

    @property
    def m(self) -> int:
        return self.x[0] * self.rows

    def statics(self) -> dict:
        """The static kwargs of ``ops.staged_vta_matmul`` for this call
        (serving's truncating commit, the default blocks)."""
        return dict(conv=self.conv, rows=self.rows, cols=self.k,
                    relu=self.relu, shift=self.shift, saturate=False,
                    out_dtype=self.out_dtype, valid=self.valid,
                    epilogue=self.epilogue, depthwise=self.depthwise)

    def matmul_args(self, sharding=None):
        """``(operand shapes, static kwargs)`` of the one jitted call
        serving makes (``ops._staged_vta_gemm``: staging, pads,
        ``vta_gemm``, the epilogue, the valid rows; ``interpret`` left to
        the caller) — what its ``lower`` takes."""
        shapes = [(self.x, jnp.int8),
                  ((self.gather,), jnp.int32) if self.gather else None,
                  ((self.k, self.n), jnp.int8),
                  ((self.n,), jnp.int32) if self.bias else None,
                  ((self.rows, self.n), jnp.int32) if self.preload else None,
                  (self.res, jnp.int8) if self.res else None,
                  ((self.keep,), jnp.int32) if self.keep else None,
                  *(((t,), jnp.int32) for t in self.tables)]
        return _shape_structs(shapes, sharding), dict(
            self.statics(), block_m=256, block_n=256, block_k=256)

    def vta_gemm_args(self, sharding=None):
        """``(operand shapes, static kwargs)`` of the padded ``vta_gemm``
        call inside :meth:`matmul_args`'s program — what ``vta_gemm.lower``
        takes.  A depthwise call has no ``vta_gemm``."""
        from repro.kernels.ops import gemm_blocks
        if self.depthwise:
            raise CompileError("a depthwise layer runs vta_dwconv, not "
                               "vta_gemm", constraint="kernel-depthwise")
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
    fuses, else the int32 GEMM and the layer's TensorAlu epilogue after it
    (:func:`device_alu`), and only the rows the host reads back; a
    depthwise layer ``vta_dwconv`` over its ``(kh·kw, C)`` taps, its
    epilogue always after it.  The chip-compile rehearsal compiles exactly
    these calls."""
    p = plan_pallas(layer.program)
    low = serving_lowering(layer)
    geo = layer.geometry
    m, n = p.valid_shape
    shape = dict(
        x=((batch, layer.input_matrix.shape[1]) if geo is None else
           (batch, geo.in_channels, geo.in_h, geo.in_w)),
        conv=None if geo is None else (geo.kh, geo.kw, geo.stride, geo.pad),
        gather=0 if low.gather is None else len(low.gather),
        rows=p.padded_shape[0], k=low.w.shape[0],
        n=p.padded_shape[1], valid=(m, n),
        keep=0 if low.keep is None else len(low.keep))
    if low.fused:
        return KernelCall(**shape, bias=low.bias is not None, relu=p.relu,
                          shift=p.shift, out_dtype=jnp.int8)
    res = None
    if layer.residual_matrix is not None:   # the skip's map, or its matrix
        res = ((batch,) + layer.residual_matrix.shape if geo is None
               else (batch, n, geo.out_h, geo.out_w))
    return KernelCall(**shape, bias=low.bias is not None, relu=False,
                      shift=0, out_dtype=jnp.int32, res=res,
                      preload=low.preload is not None,
                      epilogue=low.epilogue,
                      tables=tuple(len(t) for t in low.tables),
                      depthwise=bool(p.band))


# ---------------------------------------------------------------------------
# The TensorAlu program as the kernel's jitted call runs it
# ---------------------------------------------------------------------------

_ALU_OPS = {isa.AluOp.MIN: "min", isa.AluOp.MAX: "max",
            isa.AluOp.ADD: "add", isa.AluOp.SHR: "shr"}


def _alu_op(op) -> str:
    if op not in _ALU_OPS:
        raise CompileError(f"unsupported ALU op {op!r}",
                           constraint="pallas-alu-op")
    return _ALU_OPS[op]


def _row_major(p: PallasPlan, vec: np.ndarray) -> np.ndarray:
    """Block-major result-vector indices (§3.2) → indices of the same
    vectors in the result's row-major view ``(α·rh·β, bs)``."""
    block_row, rest = np.divmod(np.asarray(vec, np.int64),
                                p.beta * p.row_height)
    col_block, within = np.divmod(rest, p.row_height)
    return (block_row * p.row_height + within) * p.beta + col_block


def _whole_rows(p: PallasPlan, *tables) -> Optional[Tuple[np.ndarray, ...]]:
    """Row-major vector tables of equal length as result rows, where they
    name whole rows: taken in the order of the first, each holds the β
    vectors of its rows in order, so entry ``t`` of one table sits beside
    entry ``t`` of the others.  None where they do not."""
    g = p.beta
    vec = np.stack(tables)
    vec = vec[:, np.argsort(vec[0], kind="stable")]
    if vec.shape[1] % g:
        return None
    groups = vec.reshape(len(tables), -1, g)
    if (groups[..., 0] % g).any() or \
            (groups != groups[..., :1] + np.arange(g)).any():
        return None
    return tuple((groups[..., 0] // g).astype(np.int32))


def _index_tables(p: PallasPlan, groups, rows: bool = True):
    """``(lanes, tables)``: each group of block-major tables as row-major
    index tables — whole rows of ``β·bs`` lanes where every group names
    whole rows (and ``rows`` allows it), else vectors of ``bs``."""
    vec = [tuple(_row_major(p, t) for t in group) for group in groups]
    if rows:
        whole = [_whole_rows(p, *group) for group in vec]
        if all(w is not None for w in whole):
            return p.beta * p.block_size, [t for w in whole for t in w]
    return p.block_size, [t.astype(np.int32) for group in vec for t in group]


def device_alu(p: PallasPlan) -> Tuple[Tuple, Tuple[np.ndarray, ...]]:
    """``p``'s TensorAlu program as ``ops.alu_epilogue`` runs it: one
    ``ops.AluStep`` per op (the jitted call's static description) and the
    int32 index tables of the indexed and pair ops (its operands), made
    once per program.  A lattice whose pairs are independent runs as its
    passes; one that must run in pair order (``PairLattice.sequential``)
    runs pair by pair."""
    steps, tables = [], []
    for spec in p.alu_ops:
        op = _alu_op(spec.op)
        if isinstance(spec, AluImmOp):
            steps.append(AluStep("imm", op, spec.imm))
        elif isinstance(spec, AluResidualOp):
            steps.append(AluStep("residual", op, spec.pre_shift))
        elif isinstance(spec, AluIndexedImmOp):
            lanes, idx = _index_tables(p, [(np.unique(spec.indices),)])
            steps.append(AluStep("indexed", op, spec.imm, lanes))
            tables += idx
        elif isinstance(spec, AluPairOp):
            lat = pair_lattice(spec.op, spec.pairs)
            if lat.sequential:
                lanes, pairs = _index_tables(p, [(lat.dst, lat.src)],
                                             rows=False)
                steps.append(AluStep("pairs_in_order", op, 0, lanes))
            else:
                lanes, pairs = _index_tables(p, lat.passes)
                steps.append(AluStep("pairs", op, len(lat.passes), lanes))
            tables += pairs
        else:
            raise CompileError(f"unknown ALU spec {type(spec).__name__}",
                               constraint="pallas-alu-op")
    return tuple(steps), tuple(tables)


@dataclasses.dataclass(frozen=True)
class ServingLowering:
    """What every ``serve(backend="pallas")`` of one compiled layer uses
    besides its activations, made once from the program's own WGT and ACC
    segments and ALU spec: ``w`` ``(λ·bs, β·bs)`` int8 (a depthwise
    layer's ``(kh·kw, C)`` taps, from its weight matrix), the fused
    row-broadcast ``bias`` ``(β·bs,)`` int32 or None and the band
    ``gather``; where the layer does not run whole in the kernel, the int32
    X ``preload`` ``(α·rh, β·bs)`` or None, the ``epilogue``
    (:func:`device_alu`'s steps) and its index ``tables``; and ``keep``,
    the result rows a pooled layer keeps."""

    w: np.ndarray
    bias: Optional[np.ndarray]
    gather: Optional[np.ndarray]
    preload: Optional[np.ndarray]
    epilogue: Optional[Tuple]
    tables: Tuple[np.ndarray, ...]
    keep: Optional[np.ndarray]

    @property
    def fused(self) -> bool:
        return self.epilogue is None


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
    preload of any other form is the epilogue's.  ``vta_dwconv`` adds
    such a bias itself and never fuses the epilogue."""
    p = plan_pallas(layer.program)

    def make():
        row = layer.program.dram_image()[None]
        x = _decode_acc32(row, p)[0] if p.acc else None
        bias, fused = None, p.fused and not p.band
        if x is not None and (fused or p.band):
            m = p.valid_shape[0]
            if (x[:m] == x[:1]).all() and (x[m:] == 0).all():
                bias, x = x[0], None
            else:
                fused = False
        epilogue, tables = (None, ()) if fused else device_alu(p)
        gather = _layer_gather(layer)
        return ServingLowering(
            w=_depthwise_taps(layer) if p.band else _decode_wgt(row, p)[0],
            bias=bias,
            gather=None if gather is None else gather.astype(np.int32),
            preload=x, epilogue=epilogue, tables=tables,
            keep=(None if layer.keep_rows is None
                  else np.asarray(layer.keep_rows, np.int32)))
    return _held(p, "lowering", make)


def serving_constants(layer) -> Tuple:
    """``(w, bias, gather, preload, keep, *tables)`` of
    :func:`serving_lowering` on the device: put once per program, inside a
    ``vta.const.put`` span."""
    low = serving_lowering(layer)

    def make():
        consts = (low.w, low.bias, low.gather, low.preload, low.keep,
                  *low.tables)
        sent = sum(c.nbytes for c in consts if c is not None)
        with span("vta.const.put", bytes=sent):
            return tuple(jax.device_put(consts))
    return _held(plan_pallas(layer.program), "device", make)


# ---------------------------------------------------------------------------
# §3.2 layout: the program's WGT and ACC segments, read once per program
# ---------------------------------------------------------------------------

def _decode_wgt(stack: np.ndarray, p: PallasPlan) -> np.ndarray:
    """WGT bytes (blocks stored transposed, §3.2) → (B, λ·bs, β·bs) int8."""
    start, size = p.wgt
    raw = stack[:, start:start + size].view(np.int8)
    b, bs = stack.shape[0], p.block_size
    blocks = raw.reshape(b, p.lam, p.beta, bs, bs)   # each block is Bᵀ
    return blocks.transpose(0, 1, 4, 2, 3).reshape(
        b, p.lam * bs, p.beta * bs)


def _depthwise_taps(layer) -> np.ndarray:
    """A depthwise layer's ``(kh·kw, C)`` int8 taps, read off its band
    weight matrix (``conv_lowering.depthwise_ker2col``): tap ``t`` of
    channel ``c`` is row ``c·kh·kw + t`` of column ``c``."""
    w = layer.weight_matrix
    c = w.shape[1]
    taps = w.shape[0] // c
    rows = np.arange(c)[None, :] * taps + np.arange(taps)[:, None]
    return np.ascontiguousarray(w[rows, np.arange(c)[None, :]])


def _decode_acc32(stack: np.ndarray, p: PallasPlan) -> np.ndarray:
    """ACC bytes → (B, α·rh, β·bs) int32 (the X preload)."""
    start, size = p.acc
    raw = stack[:, start:start + size].view("<i4")
    b = stack.shape[0]
    blocks = raw.reshape(b, p.alpha, p.beta, p.row_height, p.block_size)
    return blocks.transpose(0, 1, 3, 2, 4).reshape(
        b, p.alpha * p.row_height, p.beta * p.block_size)


# ---------------------------------------------------------------------------
# One served layer
# ---------------------------------------------------------------------------

def _report(prog, b: int) -> SimReport:
    report = SimReport()
    report.gemm_loops = b * prog.gemm_loops()
    report.alu_loops = b * prog.alu_loops()
    return report


def serve_layer(layer, x: np.ndarray, res: Optional[np.ndarray]
                ) -> Tuple[np.ndarray, SimReport]:
    """One layer of ``serve(backend="pallas")``, no DRAM image: ``x`` is
    the stacked int8 activations (``(B, C, H, W)``, or ``(B, D)`` for an fc
    layer), ``res`` the stacked int8 skip activations of a join
    (``(B, C, H, W)``, or ``(B, M, N)``) or None; both go up in one put.
    The device stages A from ``x``, runs the kernel and the layer's
    epilogue inside the kernel's jitted call; W, the bias, the gather and
    the epilogue's tables are already there (:func:`serving_constants`).
    Returns the committed int8 result ``(B, M, N)`` at its valid shape,
    ``M`` the kept rows of a pooled layer."""
    _require_pallas()
    from repro.kernels import ops as kernel_ops
    b = x.shape[0]
    call = kernel_call(layer, b)
    if x.shape != call.x:
        raise ValueError(
            f"layer {layer.spec.name!r}: activations of shape {x.shape[1:]}, "
            f"compiled for {call.x[1:]} — request shape does not match the "
            f"compiled geometry")
    if (None if res is None else res.shape) != call.res:
        raise CompileError(
            f"skip activations of shape "
            f"{None if res is None else res.shape[1:]} for a layer whose "
            f"join takes {None if call.res is None else call.res[1:]}",
            layer=layer.spec.name, constraint="residual-shape")
    w, bias, gather, preload, keep, *tables = serving_constants(layer)
    sent = (x,) if res is None else (x, res)
    with span("vta.kernel"):
        with span("vta.kernel.put", bytes=sum(a.nbytes for a in sent)):
            sent = jax.device_put(sent)
        out = kernel_ops.staged_vta_matmul(
            sent[0], gather, w, bias, preload=preload,
            res=sent[1] if res is not None else None, keep=keep,
            tables=tables, **call.statics())
        with span("vta.kernel.fetch", bytes=out.size * out.dtype.itemsize):
            out = np.asarray(out)       # read-only, as jax buffers are
    return out.reshape(b, -1, out.shape[1]), _report(layer.program, b)
