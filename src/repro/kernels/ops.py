"""Jitted public wrappers around the Pallas kernels.

Every ``vta_gemm`` call goes through one jitted program,
:func:`_staged_vta_gemm`: the staging of a served layer's GEMM input from
its activations (:func:`stage_rows`; the identity for a plain matrix),
shape padding to block multiples (:func:`gemm_blocks`), the kernel and
the slice back.  Also here: the one decision of whether a kernel runs
compiled or interpreted (:func:`pallas_interpret`: compiled on the TPU,
interpreted only on the CPU the tests run on), and the ``xla`` legs that
call the pure-JAX references in ``kernels/ref.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax._src import config as _jax_config

from repro.core.errors import CompileError
from repro.trace import span

from . import ref as _ref
from .flash_attention import flash_attention as _flash
from .vta_gemm import vta_gemm as _vta_gemm

_BACKENDS = ("auto", "pallas", "xla")


def _check_backend(backend: str) -> None:
    if backend not in _BACKENDS:
        raise ValueError(
            f"kernel backend must be one of {_BACKENDS}, got {backend!r}")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode in this process.

    Only on the ``cpu`` platform, which is how the tests run.  On ``tpu``
    they run compiled.  Any other platform, or a TPU process that forced
    interpret mode (``pltpu.force_tpu_interpret_mode``), raises: a run
    that lost its chip must fail, not serve correct bytes from the host."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform != "tpu":
        raise CompileError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"this process's platform is {platform!r}",
            constraint="pallas-platform")
    if _jax_config.pallas_tpu_interpret_mode_context_manager.value is not None:
        raise CompileError(
            "TPU interpret mode is forced in a process that holds a TPU; "
            "the kernels must run compiled there",
            constraint="pallas-interpret-on-tpu")
    return False


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class GemmBlocks(NamedTuple):
    """Kernel block sizes and the padded operand dims of one GEMM."""

    block_m: int
    block_k: int
    block_n: int
    m: int
    k: int
    n: int


def gemm_blocks(m: int, k: int, n: int, *, block_m: int = 256,
                block_n: int = 256, block_k: int = 256) -> GemmBlocks:
    """Blocks and padding for an int8 ``(m, k) @ (k, n)`` ``vta_gemm`` call.

    M rounds up to int8's 32-row sublane tile, K and N to the 128-wide
    lanes; each block is capped at the requested size and each dim padded
    to a whole number of blocks.  A block is then either the requested
    size or the whole padded dim."""
    bm = min(block_m, _round_up(m, 32))
    bk = min(block_k, _round_up(k, 128))
    bn = min(block_n, _round_up(n, 128))
    return GemmBlocks(bm, bk, bn,
                      _round_up(m, bm), _round_up(k, bk), _round_up(n, bn))


def vta_matmul(a: jax.Array, b: jax.Array,
               bias: Optional[jax.Array] = None, *,
               relu: bool = False, shift: int = 0, saturate: bool = True,
               out_dtype=jnp.int8,
               block_m: int = 256, block_n: int = 256, block_k: int = 256,
               backend: str = "auto") -> jax.Array:
    """Fused W8A8 GEMM (the paper's datapath as a TPU feature).

    backend: "pallas" | "xla" | "auto".  "pallas" runs the kernel, compiled
    on the TPU and interpreted on the CPU (:func:`pallas_interpret`);
    "auto" off the TPU uses the XLA reference, which is semantically
    identical.
    """
    _check_backend(backend)
    k = a.shape[1]
    if b.shape[0] != k:
        raise CompileError(
            f"incompatible GEMM operand shapes {tuple(a.shape)} @ "
            f"{tuple(b.shape)}", constraint="kernel-gemm-shape")
    if backend == "xla" or (backend == "auto" and not _on_tpu()):
        return _ref.vta_gemm_ref(a, b, bias, relu=relu, shift=shift,
                                 saturate=saturate, out_dtype=out_dtype)
    # a plain matrix is a batch of m one-row "activations": no staging
    return staged_vta_matmul(a, None, b, bias, conv=None, rows=1, cols=k,
                             relu=relu, shift=shift, saturate=saturate,
                             out_dtype=out_dtype, block_m=block_m,
                             block_n=block_n, block_k=block_k)


def stage_rows(x, gather, *, conv, rows, cols):
    """A layer's padded GEMM input built from its activations, on the
    device: ``x`` is ``(B, C, H, W)`` int8 for a conv, ``(B, D)`` for an fc
    layer; the result is ``(B·rows, cols)``.

    ``conv`` is ``(kh, kw, stride, pad)``: zero padding, the map split
    into its ``stride × stride`` phases (so that every kernel tap is a
    unit-stride slice of one phase), one slice per tap, patches flattened
    channel-major (``c·kh·kw + i·kw + j``, ``conv_lowering.im2row_batch``'s
    order) — data movement only.  ``gather`` (int32, or None) lays the
    rows out in the bands of a padded 3×3/s2 max pool, -1 giving a zero
    row (``conv_lowering.gather_rows``).  Rows pad with zeros to ``rows``
    per image, columns to ``cols``."""
    b = x.shape[0]
    if conv is None:
        a = x.reshape(b, 1, -1)
    else:
        kh, kw, s, pad = conv
        _, c, h, w = x.shape
        oh = (h + 2 * pad - kh) // s + 1
        ow = (w + 2 * pad - kw) // s + 1
        xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad + (-(h + 2 * pad)) % s),
                         (pad, pad + (-(w + 2 * pad)) % s)))
        hs, ws = xp.shape[2] // s, xp.shape[3] // s
        phases = xp.reshape(b, c, hs, s, ws, s).transpose(0, 3, 5, 1, 2, 4)
        taps = [phases[:, i % s, j % s, :, i // s:i // s + oh,
                       j // s:j // s + ow]
                for i in range(kh) for j in range(kw)]
        a = jnp.stack(taps, axis=2).reshape(b, c * kh * kw, oh * ow)
        a = a.transpose(0, 2, 1)
    if gather is not None:
        m = a.shape[1]
        a = jnp.pad(a, ((0, 0), (0, 1), (0, 0)))       # row m is zeros
        a = jnp.take(a, jnp.where(gather < 0, m, gather), axis=1,
                     mode="clip")
    a = jnp.pad(a, ((0, 0), (0, rows - a.shape[1]), (0, cols - a.shape[2])))
    return a.reshape(b * rows, cols)


def staged_vta_matmul(x: jax.Array, gather: Optional[jax.Array],
                      b: jax.Array, bias: Optional[jax.Array] = None, *,
                      conv, rows: int, cols: int,
                      relu: bool = False, shift: int = 0,
                      saturate: bool = True, out_dtype=jnp.int8,
                      block_m: int = 256, block_n: int = 256,
                      block_k: int = 256) -> jax.Array:
    """The fused GEMM of one served layer, its input staged on the device
    (:func:`stage_rows`) in the kernel's own jitted call: ``x``'s
    activations in, ``(B·rows, n)`` out.  The pallas leg only, compiled on
    the TPU and interpreted on the CPU (:func:`pallas_interpret`)."""
    m, n = x.shape[0] * rows, b.shape[1]
    if b.shape[0] != cols:
        raise CompileError(
            f"staged GEMM input of {cols} columns @ {tuple(b.shape)}",
            constraint="kernel-gemm-shape")
    g = gemm_blocks(m, cols, n, block_m=block_m, block_n=block_n,
                    block_k=block_k)
    with span("vta.kernel.dispatch", issued_macs=g.m * g.k * g.n,
              a_bytes=m * cols):
        return _staged_vta_gemm(x, gather, b, bias, conv=conv, rows=rows,
                                cols=cols, relu=relu, shift=shift,
                                saturate=saturate, out_dtype=out_dtype,
                                block_m=block_m, block_n=block_n,
                                block_k=block_k,
                                interpret=pallas_interpret())


@functools.partial(
    jax.jit,
    static_argnames=("conv", "rows", "cols", "relu", "shift", "saturate",
                     "out_dtype", "block_m", "block_n", "block_k",
                     "interpret"))
def _staged_vta_gemm(x, gather, b, bias, *, conv, rows, cols, relu, shift,
                     saturate, out_dtype, block_m, block_n, block_k,
                     interpret):
    """:func:`staged_vta_matmul` as one program: stage A, pad it to
    :func:`gemm_blocks`, run ``vta_gemm``, slice.  One trip into the
    runtime per call: each op run on its own would be another.  Its module
    (``jit__staged_vta_gemm``) names ``vta_gemm``, which is how a device
    trace finds the kernel's time (the staging included)."""
    a = stage_rows(x, gather, conv=conv, rows=rows, cols=cols)
    m, n = a.shape[0], b.shape[1]
    g = gemm_blocks(m, cols, n, block_m=block_m, block_n=block_n,
                    block_k=block_k)
    a_p = jnp.pad(a, ((0, g.m - m), (0, g.k - cols)))
    b_p = jnp.pad(b, ((0, g.k - cols), (0, g.n - n)))
    bias_p = jnp.pad(bias, (0, g.n - n)) if bias is not None else None
    out = _vta_gemm(a_p, b_p, bias_p, relu=relu, shift=shift,
                    saturate=saturate, out_dtype=out_dtype,
                    block_m=g.block_m, block_n=g.block_n, block_k=g.block_k,
                    interpret=interpret)
    return out[:m, :n]


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, sm_scale: Optional[float] = None,
              window: Optional[int] = None, q_offset: int = 0,
              block_q: int = 128, block_k: int = 128,
              backend: str = "auto") -> jax.Array:
    """Flash attention with GQA; pads sequence dims to block multiples."""
    _check_backend(backend)
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if backend == "xla" or (backend == "auto" and not _on_tpu()):
        return _ref.attention_ref(q, k, v, causal=causal, sm_scale=sm_scale,
                                  window=window, q_offset=q_offset)
    interpret = pallas_interpret()
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k, _round_up(skv, 8))
    sq_p, skv_p = _round_up(sq, bq), _round_up(skv, bk)
    q_p = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    out = _flash(q_p, k_p, v_p, causal=causal, sm_scale=sm_scale,
                 window=window, q_offset=q_offset,
                 block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :, :sq, :]


def vta_matmul_pallas(a, b, bias=None, **kw):
    """Force the Pallas path (interpreted on the CPU) — used by kernel tests."""
    return vta_matmul(a, b, bias, backend="pallas", **kw)


def attention_pallas(q, k, v, **kw):
    """Force the Pallas path (interpreted on the CPU) — used by kernel tests."""
    return attention(q, k, v, backend="pallas", **kw)
