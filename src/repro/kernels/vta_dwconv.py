"""Pallas TPU kernel: a depthwise convolution on the VPU (DESIGN.md §Graph).

A depthwise conv is the grouped GEMM the compiler lowers it to: each
channel multiplies only its own ``kh·kw`` taps.  On the MXU that GEMM is
a block-diagonal matrix, almost all zeros; ``vta_dwconv`` issues none of
them.  Channels lie on the 128 lanes, output columns on the sublanes, and
the kernel runs the ``kh·kw`` shifted int8 × int8 → int32 multiply-adds
of each tap over the padded map, plus the bias (the paper's ACC preload),
element-wise on the VPU.

The map comes in NHWC, zero-padded, and split into its ``stride ×
stride`` phases (as ``ops.stage_rows`` splits it), so that every tap of a
strided conv is a unit-stride window of one phase.  The grid is (image,
block of 128 channels, block of output rows); an image's phases for one
channel block stay in VMEM across its row blocks.  The result is int32:
the layer's TensorAlu program (ReLU6, requant) runs after it in the same
jitted call (``ops._staged_vta_gemm``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
OUT_BLOCK_BYTES = 1 << 19      # an output block's VMEM, int32


class DwBlocks(NamedTuple):
    """The kernel's geometry for one call: output ``oh × ow``, each phase
    ``hs × ws``, channels padded to ``cp`` lanes, ``block_rows`` output
    rows per grid step; and what it issues: multiply-adds (padding
    included) and bytes moved (the phases read, the int32 result
    written)."""

    oh: int
    ow: int
    hs: int
    ws: int
    cp: int
    block_rows: int
    issued_macs: int
    bytes: int


def dw_blocks(b: int, c: int, h: int, w: int, kh: int, kw: int,
              stride: int, pad: int) -> DwBlocks:
    """:class:`DwBlocks` of a ``(b, c, h, w)`` map under a ``kh × kw``
    depthwise kernel with ``stride`` and zero ``pad``."""
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (w + 2 * pad - kw) // stride + 1
    hs = -(-(h + 2 * pad) // stride)
    ws = -(-(w + 2 * pad) // stride)
    cp = -(-c // LANES) * LANES
    per_row = ow * LANES * 4
    rows = max(r for r in range(1, oh + 1)
               if oh % r == 0 and (r == 1 or r * per_row <= OUT_BLOCK_BYTES))
    return DwBlocks(oh, ow, hs, ws, cp, rows,
                    issued_macs=b * oh * ow * kh * kw * cp,
                    bytes=b * stride * stride * hs * ws * cp
                    + 4 * b * oh * ow * cp)


def _dw_kernel(x_ref, w_ref, bias_ref, out_ref, *, kh, kw, stride, rows,
               ow):
    """One (image, channel block, row block) step: ``out = bias + Σ_taps
    phase window × tap``, int32."""
    r0 = pl.program_id(2) * rows
    acc = jnp.broadcast_to(bias_ref[...], (rows, ow, LANES))
    for i in range(kh):
        for j in range(kw):
            phase = (i % stride) * stride + j % stride
            win = x_ref[0, phase, pl.ds(r0 + i // stride, rows),
                        pl.ds(j // stride, ow), :]
            acc = acc + win.astype(jnp.int32) * w_ref[i * kw + j, :]
    out_ref[0] = acc


def vta_dwconv(x: jax.Array, taps: jax.Array, bias, *, conv,
               interpret: bool = False) -> jax.Array:
    """Depthwise conv of ``x`` ``(B, C, H, W)`` int8 with ``taps``
    ``(kh·kw, C)`` int8 (tap ``i·kw + j`` of channel ``c`` in row
    ``i·kw + j``) plus ``bias`` ``(C,)`` int32 or None; ``conv`` is
    ``(kh, kw, stride, pad)``.  Returns ``(B, OH·OW, C)`` int32, rows in
    ``im2row``'s order.  Traced inside the caller's jitted program."""
    kh, kw, s, pad = conv
    b, c, h, w = x.shape
    g = dw_blocks(b, c, h, w, kh, kw, s, pad)
    xp = jnp.pad(x.transpose(0, 2, 3, 1),
                 ((0, 0), (pad, g.hs * s - h - pad),
                  (pad, g.ws * s - w - pad), (0, g.cp - c)))
    phases = xp.reshape(b, g.hs, s, g.ws, s, g.cp).transpose(
        0, 2, 4, 1, 3, 5).reshape(b, s * s, g.hs, g.ws, g.cp)
    w32 = jnp.pad(taps.astype(jnp.int32), ((0, 0), (0, g.cp - c)))
    b32 = (jnp.zeros((1, g.cp), jnp.int32) if bias is None else
           jnp.pad(bias.astype(jnp.int32), (0, g.cp - c)).reshape(1, g.cp))
    kernel = functools.partial(_dw_kernel, kh=kh, kw=kw, stride=s,
                               rows=g.block_rows, ow=g.ow)
    out = pl.pallas_call(
        kernel,
        grid=(b, g.cp // LANES, g.oh // g.block_rows),
        in_specs=[
            pl.BlockSpec((1, s * s, g.hs, g.ws, LANES),
                         lambda n, cb, r: (n, 0, 0, 0, cb)),
            pl.BlockSpec((kh * kw, LANES), lambda n, cb, r: (0, cb)),
            pl.BlockSpec((1, LANES), lambda n, cb, r: (0, cb)),
        ],
        out_specs=pl.BlockSpec((1, g.block_rows, g.ow, LANES),
                               lambda n, cb, r: (n, r, 0, cb)),
        out_shape=jax.ShapeDtypeStruct((b, g.oh, g.ow, g.cp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="vta_dwconv",        # the kernel's op family in a device trace
    )(phases, w32, b32)
    return out.reshape(b, g.oh * g.ow, g.cp)[:, :, :c]
