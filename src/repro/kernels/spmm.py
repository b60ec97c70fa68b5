"""Blocked-ELL SpMM Pallas TPU kernel — the TPU-native re-design of the
paper's customized Sextans [30] FPGA SpMM.

Sextans streams CSR non-zeros through 640 MAC units with the dense matrix
resident in HBM. A TPU gets no value from scalar streaming — the MXU wants
128x128 dense tiles — so the adaptation (DESIGN.md §2) re-blocks the sparse
matrix into a *blocked-ELL* format: each (bm x bk) tile that contains any
non-zero is stored densely, padded to a fixed number of tiles per block-row
(the ELL width). The kernel then:

  * prefetches the column-block index array as a scalar operand, so the
    BlockSpec index_map of the dense operand gathers exactly the needed
    (bk x N) slab of X into VMEM per grid step (data-dependent tiling — the
    TPU analogue of Sextans' HBM channel streaming),
  * runs one (bm x bk) @ (bk x N) MXU matmul per step, accumulating the
    block-row's output tile in place.

Padding tiles point at column-block 0 with all-zero values, so they
contribute nothing (branch-free, like Sextans' zero-padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# format conversion (host-side, numpy)
# ---------------------------------------------------------------------------
def to_blocked_ell(a_dense: np.ndarray, bm: int = 128, bk: int = 128):
    """Dense (M, K) -> (blocks (nbr, ell, bm, bk), idx (nbr, ell) int32).
    ell = max non-empty column-blocks over the block-rows."""
    M, K = a_dense.shape
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    nbr, nbc = M // bm, K // bk
    tiles = a_dense.reshape(nbr, bm, nbc, bk).transpose(0, 2, 1, 3)
    nonzero = np.abs(tiles).sum(axis=(2, 3)) > 0          # (nbr, nbc)
    ell = max(int(nonzero.sum(axis=1).max()), 1)
    blocks = np.zeros((nbr, ell, bm, bk), a_dense.dtype)
    idx = np.zeros((nbr, ell), np.int32)
    for r in range(nbr):
        cols = np.nonzero(nonzero[r])[0]
        for e, c in enumerate(cols):
            blocks[r, e] = tiles[r, c]
            idx[r, e] = c
    return blocks, idx


def _spmm_kernel(idx_ref, a_ref, x_ref, o_ref):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[0, 0]                                   # (bm, bk)
    x = x_ref[...]                                    # (bk, N)
    # f32 operands contract at full f32 precision, narrower ones in one pass
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    o_ref[...] += jax.lax.dot(a.astype(jnp.float32),
                              x.astype(jnp.float32), precision=prec,
                              preferred_element_type=jnp.float32
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmm_blocked_ell(blocks, idx, x, *, interpret: bool = False):
    """(nbr, ell, bm, bk) blocked-ELL  @  (K, N) -> (M, N)."""
    nbr, ell, bm, bk = blocks.shape
    K, N = x.shape
    grid = (nbr, ell)
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk), lambda r, e, idx: (r, e, 0, 0)),
                # data-dependent gather of the X slab this tile needs
                pl.BlockSpec((bk, N), lambda r, e, idx: (idx[r, e], 0)),
            ],
            out_specs=pl.BlockSpec((bm, N), lambda r, e, idx: (r, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nbr * bm, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx, blocks, x)
