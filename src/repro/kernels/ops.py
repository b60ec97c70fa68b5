"""Public jit'd wrappers around the Pallas kernels.

These take the model-zoo layouts ((B, S, H, D) activations, dense (M, K)
sparse operands) and handle layout transposition + format conversion, so the
rest of the framework never touches BlockSpecs. They compile for the TPU by
default; ``interpret=True`` runs the kernel bodies in Python instead, which
is how the CPU tests validate them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .spmm import spmm_blocked_ell, to_blocked_ell
from .swa import swa_attention_pallas


@functools.partial(jax.jit,
                   static_argnames=("window", "scale", "blk", "interpret"))
def swa_attention_op(q, k, v, *, window: int, scale: float, blk: int = 128,
                     interpret: bool = False):
    """Sliding-window attention, model layout: q (B,S,H,D), k/v (B,S,KV,D)."""
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = swa_attention_pallas(qt, kt, vt, window=window, scale=scale,
                              blk=blk, interpret=interpret)
    return jnp.transpose(ot, (0, 2, 1, 3))


def spmm_op(a_dense: np.ndarray, x, *, bm: int = 128, bk: int = 128,
            interpret: bool = False):
    """SpMM with host-side blocked-ELL conversion (one-time; the format is
    cached by callers for repeated multiplies, mirroring the paper's
    pre-loaded static graph data)."""
    blocks, idx = to_blocked_ell(np.asarray(a_dense), bm, bk)
    return spmm_blocked_ell(jnp.asarray(blocks), jnp.asarray(idx),
                            jnp.asarray(x), interpret=interpret)
