"""Mamba2 SSD (state-space duality) chunk-scan Pallas TPU kernel.

The SSD computation is the hot spot of the mamba2/zamba2 architectures: per
chunk it is two MXU matmuls (the attention-like intra-chunk term) plus a
rank-Q state update, with a sequential state carried across chunks. The
kernel maps that structure directly:

  grid = (batch, heads, n_chunks)  — n_chunks is the sequential ("arbitrary")
  dimension; the (P, N) state lives in VMEM scratch across it, exactly like
  the online-softmax carry of flash attention. Per step:

      W    = (C B^T) ⊙ M ⊙ dt          (Q,Q)  one MXU matmul + mask
      y    = W x + (C S^T) ⊙ e^la      (Q,P)  two MXU matmuls
      S'   = e^{la_Q} S + (x ⊙ w)^T B  (P,N)  one MXU matmul

Q (chunk) and N (state) default to 128/256-aligned so every matmul hits the
MXU; dt/decay streams are kept 2-D (Q, 1) for TPU layout friendliness.
The pure-jnp oracle is ``models/ssm.py:ssd_chunked`` (also the model path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, s_out_ref,
                s_ref, *, nc: int, Q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    # f32 inputs contract at full f32 precision, narrower ones in one pass
    prec = jax.lax.Precision.HIGHEST if x_ref.dtype == jnp.float32 else None
    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q, 1)
    da = da_ref[0, 0, 0].astype(jnp.float32)        # (Q, 1)
    B = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)             # (Q, N)

    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    it = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = iq >= it
    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # ones matmul, at full f32 precision like the cumsum it replaces
    la = jax.lax.dot(causal.astype(jnp.float32), da,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)  # (Q, 1) log decay
    seg = la - la.T                                 # (Q, Q): la_s - la_t
    M = jnp.where(causal, jnp.exp(seg), 0.0)

    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())), precision=prec,
                             preferred_element_type=jnp.float32)   # (Q,Q)
    W = cb * M * dt.T                               # ⊙ dt_t
    y = jax.lax.dot(W, x, precision=prec,
                    preferred_element_type=jnp.float32)            # (Q,P)

    S = s_ref[...]                                  # (P, N)
    y = y + jax.lax.dot_general(C, S, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32
                                ) * jnp.exp(la)
    # state update: S' = e^{la_Q} S + (x ⊙ w)^T B, w = e^{la_Q - la} dt.
    # Static slices and a scalar decay: Mosaic lowers neither a negative
    # index nor a (1, 1) -> (P, N) broadcast.
    la_q = jax.lax.slice(la, (Q - 1, 0), (Q, 1))    # (1, 1)
    w = jnp.exp(la_q - la) * dt                     # (Q, 1)
    s_ref[...] = (S * jnp.exp(jnp.sum(da)) +
                  jax.lax.dot_general(x * w, B, (((0,), (0,)), ((), ())),
                                      precision=prec,
                                      preferred_element_type=jnp.float32))
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fini():
        s_out_ref[0, 0] = s_ref[...].astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_pallas(x, dt, B, C, A_log, D, *, chunk: int = 128,
                       interpret: bool = False):
    """Drop-in for models.ssm.ssd_chunked (zero init state).

    x: (b,L,H,P); dt: (b,L,H) raw (softplus applied here); B/C: (b,L,N).
    Returns (y (b,L,H,P), final_state (b,H,P,N))."""
    b, L, H, Pd = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    a = -jnp.exp(A_log.astype(jnp.float32))                    # (H,)
    dts = jax.nn.softplus(dt.astype(jnp.float32))              # (b,L,H)
    da = dts * a                                               # (b,L,H)

    # chunked, head-major layouts
    xq = x.reshape(b, nc, Q, H, Pd).transpose(0, 3, 1, 2, 4)   # (b,H,nc,Q,P)
    dtq = dts.reshape(b, nc, Q, H).transpose(0, 3, 1, 2)[..., None]
    daq = da.reshape(b, nc, Q, H).transpose(0, 3, 1, 2)[..., None]
    Bq = B.reshape(b, nc, Q, N)
    Cq = C.reshape(b, nc, Q, N)

    grid = (b, H, nc)
    y, s_final = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, Pd), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda i, h, c: (i, h, c, 0, 0)),
            # B/C shared across heads: index_map drops h
            pl.BlockSpec((1, 1, Q, N), lambda i, h, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, c: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, Pd), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Pd, N), lambda i, h, c: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, nc, Q, Pd), x.dtype),
            jax.ShapeDtypeStruct((b, H, Pd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Pd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, dtq, daq, Bq, Cq)

    y = y.transpose(0, 2, 3, 1, 4).reshape(b, L, H, Pd)
    y = y + (D.astype(jnp.float32)[None, None, :, None]
             * x.astype(jnp.float32)).astype(y.dtype)
    return y, s_final
