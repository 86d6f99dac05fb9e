"""Pallas TPU kernel: causal flash attention with native GQA (bf16/f32).

The LM-side hot path of the framework (DESIGN.md §4).  Online-softmax over
KV blocks with running (m, l, o) carried in VMEM scratch; GQA is handled in
the BlockSpec index maps (query head h reads KV head ``h // group``), so
K/V are never materialised per-query-head.

Grid = (batch, q_heads, Sq/bq, Skv/bk); the KV axis is ``arbitrary`` (the
scratch carries across it), everything else parallel.  Causal masking is
applied in-kernel from absolute positions; fully-masked KV blocks are
numerically inert (contribute exp(-inf)=0), and the `block_causal` fast
path skips them via the grid truncation in ops.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.errors import CompileError

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, sm_scale: float, causal: bool,
                  block_q: int, block_k: int, q_offset: int,
                  window: Optional[int]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window is not None:
        # sliding-window attention (Mixtral-style SWA)
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                               # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jax.lax.dot_general(
                        p, v_ref[0, 0].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_cur

    @pl.when(ki == n_kv - 1)
    def _finish():
        # fully-masked rows (l == 0) return 0, not NaN
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "window",
                     "q_offset", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    window: Optional[int] = None,
                    q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """``q`` (B, H, Sq, D); ``k``/``v`` (B, Hkv, Skv, D) with H % Hkv == 0.

    Sq/Skv must be multiples of the block sizes (ops.py pads).  ``q_offset``
    is the absolute position of q[…, 0, :] — used for chunked prefill where
    queries start mid-sequence.
    """
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if h % hkv:
        raise CompileError(
            f"{h} query heads do not group over {hkv} KV heads",
            constraint="kernel-gqa-heads")
    group = h // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5
    if sq % block_q or skv % block_k:
        raise CompileError(
            f"sequence lengths {(sq, skv)} not multiples of the attention "
            f"blocks {(block_q, block_k)}; call through ops.attention, "
            f"which pads", constraint="kernel-block-divisibility")
    n_q = sq // block_q
    n_kv = skv // block_k
    grid = (b, h, n_q, n_kv)

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, sm_scale=float(sm_scale), causal=causal,
        block_q=block_q, block_k=block_k, q_offset=q_offset, window=window)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qi, ki, g=group: (bb, hh // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qi, ki, g=group: (bb, hh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
