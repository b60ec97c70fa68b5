"""Banded (sliding-window) flash-attention Pallas TPU kernel.

TPU-native adaptation of SWAT [6] (the paper's FPGA sliding-window attention
accelerator). SWAT streams the token sequence through a systolic pipeline
that only ever holds the current window; the TPU analogue is a *banded*
flash-attention: the kv-block grid dimension visits only the blocks inside
the window of each query block, so compute and memory are O(S * w) instead
of O(S^2), and the S matrix is never materialized (this fusion is the
beyond-paper optimization vs. the paper's separate SDDMM/softmax/SpMM
stages — see DESIGN.md §7).

Layout: q, k, v are (B, H, S, D) with K/V possibly having fewer (KV) heads
(GQA); the kernel maps query head h to kv head h // (H // KV) in the
BlockSpec index_map — no materialized broadcast.

Tiling: q is tiled (blk, D) and each grid step loads one (blk, D) kv tile
into VMEM; blk defaults to 128 so the MXU matmuls are 128-aligned. The
online-softmax state (m, l, acc) lives in VMEM scratch across the innermost
(kv) grid dimension.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                blk: int, window: int, nkv: int, scale: float):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    kb = iq + jk - (nkv - 1)          # kv block index this step visits

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 inputs contract at full f32 precision, narrower ones in one pass
    prec = jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    @pl.when(kb >= 0)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (blk, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (blk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32)
        row = iq * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        col = kb * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        rel = row - col
        valid = (rel >= 0) & (rel < window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # fully-masked rows (m_new == NEG_INF): keep them inert
        p = jnp.where(valid, p, 0.0)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, precision=prec, preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        m_ref[...] = m_new

    @pl.when(jk == nkv - 1)
    def _fini():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "blk", "interpret"))
def swa_attention_pallas(q, k, v, *, window: int, scale: float,
                         blk: int = 128, interpret: bool = False):
    """Banded flash attention. q: (B, H, S, D); k, v: (B, KV, S, D)."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    assert S % blk == 0, (S, blk)
    assert window % blk == 0, (window, blk)
    nq = S // blk
    nkv = min(window // blk + 1, nq)

    grid = (B, H, nq, nkv)
    q_spec = pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, blk, D),
        lambda b, h, i, j: (b, h // G, jnp.maximum(i + j - (nkv - 1), 0), 0))
    o_spec = pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0))

    kernel = functools.partial(_swa_kernel, blk=blk, window=window,
                               nkv=nkv, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # (m, l, acc) online-softmax carry lives in VMEM scratch across the
        # innermost (kv) grid dimension
        scratch_shapes=[
            pltpu.VMEM((blk,), jnp.float32),
            pltpu.VMEM((blk,), jnp.float32),
            pltpu.VMEM((blk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
