"""Pallas TPU kernel: the VTA datapath as one fused kernel (DESIGN.md §2).

``vta_gemm`` is the TPU-native re-expression of the paper's execution model:

* TensorGemm — int8 × int8 → int32 blocked matmul on the MXU
  (``preferred_element_type=int32``; the FPGA's 16×16 MAC array becomes the
  128×128 systolic array);
* ACC preload — the optional bias is the paper's ``C = A·B + X`` form;
* TensorAlu — the element-wise epilogue (ReLU, arithmetic-shift-right
  requant, int8 saturation) fused into the same kernel, replacing the VTA's
  separate ALU instruction stream;
* LOAD/STORE overlap — the ``(i, j, k)`` grid with an ``arbitrary`` K axis
  gives Pallas's automatic HBM→VMEM double buffering, playing the role of
  the VTA's dependency-flag-driven module overlap.

Block shapes are the kernel's VMEM claim: with the default 256×256×256
int8/int32 tiles the working set is A(64 KiB) + B(64 KiB) + acc(256 KiB) +
out(64 KiB) ≈ 0.45 MiB — comfortably double-bufferable in 16 MiB VMEM, and
every matmul dimension is a multiple of the 128-wide MXU.

One deliberate semantic upgrade over the FPGA: the epilogue *saturates* to
int8 instead of truncating (the paper's OUT path truncates ACC).  Truncation
is reproduced bit-exactly by the core/ simulator; saturation is what a
quantised LM inference path needs.  ``ops.vta_matmul(..., saturate=False)``
selects faithful truncation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.errors import CompileError


def _gemm_kernel(a_ref, b_ref, bias_ref, out_ref, acc_ref, *,
                 n_k: int, relu: bool, shift: int, saturate: bool,
                 out_dtype):
    """Grid = (M/bm, N/bn, K/bk); K is the innermost (arbitrary) axis so
    ``acc_ref`` persists across K steps for a fixed (i, j) tile."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU: int8 × int8 → int32 (the TensorGemm step).
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(jnp.int32)   # ACC preload (X)
        if relu:
            acc = jnp.maximum(acc, 0)                     # TensorAlu MAX
        if shift:
            acc = jax.lax.shift_right_arithmetic(         # TensorAlu SHR
                acc, jnp.int32(shift))
        if out_dtype == jnp.int8:
            if saturate:
                acc = jnp.clip(acc, -128, 127)
            else:
                # faithful VTA truncation: low 8 bits, two's complement
                acc = jax.lax.shift_right_arithmetic(
                    jax.lax.shift_left(acc, 24), jnp.int32(24))
        out_ref[...] = acc.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("relu", "shift", "saturate", "out_dtype",
                     "block_m", "block_n", "block_k", "interpret"))
def vta_gemm(a: jax.Array, b: jax.Array,
             bias: Optional[jax.Array] = None, *,
             relu: bool = False, shift: int = 0, saturate: bool = True,
             out_dtype=jnp.int8,
             block_m: int = 256, block_n: int = 256, block_k: int = 256,
             interpret: bool = False) -> jax.Array:
    """Fused quantised GEMM: ``epilogue(A @ B + bias)``.

    ``a`` int8 (M, K), ``b`` int8 (K, N), ``bias`` int32 (N,) or None.
    M/N/K must be multiples of the block sizes (``ops.vta_matmul`` pads).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise CompileError(
            f"incompatible GEMM operand shapes {tuple(a.shape)} @ "
            f"{tuple(b.shape)}", constraint="kernel-gemm-shape")
    if m % block_m or n % block_n or k % block_k:
        raise CompileError(
            f"GEMM shape {(m, k, n)} not a multiple of the kernel blocks "
            f"{(block_m, block_k, block_n)}; call through ops.vta_matmul, "
            f"which pads", constraint="kernel-block-divisibility")
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
    ]
    args = [a, b]
    if bias is not None:
        # bias broadcasts over rows: keep a (1, block_n) VMEM tile
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        args.append(bias.reshape(1, n).astype(jnp.int32))
        kernel = functools.partial(_gemm_kernel, n_k=n_k, relu=relu,
                                   shift=shift, saturate=saturate,
                                   out_dtype=out_dtype)
    else:
        def kernel(a_ref, b_ref, out_ref, acc_ref):
            _gemm_kernel(a_ref, b_ref, None, out_ref, acc_ref, n_k=n_k,
                         relu=relu, shift=shift, saturate=saturate,
                         out_dtype=out_dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="vta_gemm",          # the kernel's name in traces and HLO
    )(*args)
