"""Plain reference of the served device work, and its lower-precision control.

``stage_chain`` is a copy of ``numpy_stage_chain`` from the repo's
``chip_smoke.py``: the pallas backend's proxy stage chain in NumPy. Stage s
multiplies by W_s = (0.8 + 0.02 s) I + 0.01 P^(s+1) (P the cyclic shift of
the features) for each of its kernels, adds half the activation rolled by
one row (spmm) or one feature (win_attn), and applies tanh. Its input is
the backend's seedless microbatch, ``linspace(-1, 1)`` over (m, 8, 16).

``operands`` names the precision in which the matmuls see their operands:
``"float32"`` is exact float32 arithmetic; ``"bfloat16"`` rounds both
operands to bfloat16 and sums the products in float32, which is what a TPU
does for a float32 matmul at the default precision (one bfloat16 pass).
Everything else is float32 either way.

``control_chain`` is the same chain computed on the device in bfloat16
throughout, the nearest precision below the configuration's: activations,
products and sums are all rounded to bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def microbatch(m: int, act_batch: int = 8, act_dim: int = 16) -> np.ndarray:
    return np.linspace(-1.0, 1.0, m * act_batch * act_dim,
                       dtype=np.float32).reshape(m, act_batch, act_dim)


def stage_weight(s: int, F: int) -> np.ndarray:
    eye = np.eye(F, dtype=np.float32)
    return ((0.8 + 0.02 * s) * eye
            + 0.01 * np.roll(eye, s + 1, axis=1)).astype(np.float32)


def _operand(a: np.ndarray, operands: str) -> np.ndarray:
    if operands == "float32":
        return a
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def stage_chain(stage_kinds, micro: np.ndarray, *,
                operands: str = "float32") -> np.ndarray:
    F = micro.shape[-1]
    x = micro.astype(np.float32)
    for s, kinds in enumerate(stage_kinds):
        w = _operand(stage_weight(s, F), operands)
        for kind in kinds:
            y = _operand(x, operands) @ w
            if kind == "spmm":
                y = y + 0.5 * np.roll(x, 1, axis=1)     # rows of a microbatch
            elif kind == "win_attn":
                y = y + 0.5 * np.roll(x, 1, axis=2)     # features
            x = np.tanh(y).astype(np.float32)
    return x


def control_chain(stage_kinds, micro: np.ndarray) -> np.ndarray:
    """The chain in bfloat16 on JAX's default device; returns float32."""
    import jax.numpy as jnp

    bf = jnp.bfloat16
    F = micro.shape[-1]
    x = jnp.asarray(micro, bf)
    for s, kinds in enumerate(stage_kinds):
        w = jnp.asarray(stage_weight(s, F), bf)
        for kind in kinds:
            y = x @ w
            if kind == "spmm":
                y = y + bf(0.5) * jnp.roll(x, 1, axis=1)
            elif kind == "win_attn":
                y = y + bf(0.5) * jnp.roll(x, 1, axis=2)
            x = jnp.tanh(y)
    return np.asarray(x.astype(jnp.float32))
