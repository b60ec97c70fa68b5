"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (ref, spmm_blocked_ell, swa_attention_op,
                           swa_attention_pallas, to_blocked_ell)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# sliding-window attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,window,blk", [(256, 128, 128), (512, 256, 128),
                                          (512, 128, 128), (384, 128, 128)])
@pytest.mark.parametrize("D", [64, 128])
def test_swa_shapes(S, window, blk, D):
    B, H, KV = 1, 2, 1
    ks = jax.random.split(jax.random.PRNGKey(S + D), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    out = swa_attention_pallas(q, k, v, window=window,
                               scale=D ** -0.5, blk=blk, interpret=True)
    exp = ref.swa_attention_ref(q, k, v, window=window, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_dtypes(dtype):
    B, H, KV, S, D, W = 2, 4, 2, 256, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, S, D)).astype(dtype)
    out = swa_attention_pallas(q, k, v, window=W, scale=0.125,
                               interpret=True)
    exp = ref.swa_attention_ref(q, k, v, window=W, scale=0.125)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_swa_gqa_groups():
    """H=8 query heads sharing KV=2 heads via index arithmetic."""
    B, H, KV, S, D, W = 1, 8, 2, 256, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    out = swa_attention_pallas(q, k, v, window=W, scale=0.125,
                               interpret=True)
    exp = ref.swa_attention_ref(q, k, v, window=W, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_swa_matches_model_zoo_semantics():
    """The kernel agrees with the model zoo's chunk+halo swa_attention."""
    from repro.models.attention import swa_attention
    B, S, H, KV, D, W = 1, 512, 4, 2, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    out = swa_attention_op(q, k, v, window=W, scale=0.125, interpret=True)
    exp = swa_attention(q, k, v, window=W, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=3e-5, rtol=3e-5)


def test_swa_window_larger_than_kvblocks_clamps():
    """window//blk + 1 >= nq: every causal block is visited (full causal)."""
    B, H, KV, S, D = 1, 1, 1, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    out = swa_attention_pallas(q, k, v, window=256, scale=0.125,
                               interpret=True)
    exp = ref.swa_attention_ref(q, k, v, window=256, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


# ---------------------------------------------------------------------------
# blocked-ELL SpMM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N,density", [
    (256, 256, 128, 0.02), (512, 768, 256, 0.05),
    (256, 512, 128, 0.30), (384, 384, 128, 0.001),
])
def test_spmm_shapes(M, K, N, density):
    rng = np.random.default_rng(M + N)
    a = rng.normal(size=(M, K)).astype(np.float32)
    a[rng.random((M, K)) > density] = 0.0
    blocks, idx = to_blocked_ell(a, 128, 128)
    x = rng.normal(size=(K, N)).astype(np.float32)
    out = np.asarray(spmm_blocked_ell(jnp.asarray(blocks), jnp.asarray(idx),
                                      jnp.asarray(x), interpret=True))
    exp = a.astype(np.float64) @ x.astype(np.float64)
    np.testing.assert_allclose(out, exp, atol=1e-3, rtol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32])
def test_spmm_blocked_ell_roundtrip(dtype):
    rng = np.random.default_rng(7)
    a = rng.normal(size=(256, 384)).astype(dtype)
    a[rng.random(a.shape) > 0.08] = 0.0
    blocks, idx = to_blocked_ell(a, 128, 128)
    # reconstruct dense from the format
    recon = np.zeros_like(a)
    nbr, ell, bm, bk = blocks.shape
    for r in range(nbr):
        for e in range(ell):
            c = idx[r, e]
            recon[r*bm:(r+1)*bm, c*bk:(c+1)*bk] += blocks[r, e]
    np.testing.assert_allclose(recon, a)


def test_spmm_empty_rows():
    """Block-rows with no nonzeros produce zero output."""
    a = np.zeros((256, 256), np.float32)
    a[200, 5] = 3.0      # only the second block-row has data
    blocks, idx = to_blocked_ell(a, 128, 128)
    x = np.ones((256, 64), np.float32)
    out = np.asarray(spmm_blocked_ell(jnp.asarray(blocks), jnp.asarray(idx),
                                      jnp.asarray(x), interpret=True))
    assert np.all(out[:128] == 0)
    np.testing.assert_allclose(out[200], 3.0)


def test_spmm_matches_csr_substrate():
    from repro.sparse import csr_to_dense, random_graph_csr, spmm_csr
    g = random_graph_csr(256, 1500, seed=3)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 64))
                    .astype(np.float32))
    dense = csr_to_dense(g)
    blocks, idx = to_blocked_ell(dense, 128, 128)
    out_k = np.asarray(spmm_blocked_ell(jnp.asarray(blocks),
                                        jnp.asarray(idx), x,
                                        interpret=True))
    out_c = np.asarray(spmm_csr(g, x))
    np.testing.assert_allclose(out_k, out_c, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunk scan
# ---------------------------------------------------------------------------
def _ssd_inputs(key, b, L, H, P, N):
    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (b, L, H, P), jnp.float32),
            jax.random.normal(ks[1], (b, L, H), jnp.float32) * 0.5,
            jax.random.normal(ks[2], (b, L, N), jnp.float32) * (N ** -0.5),
            jax.random.normal(ks[3], (b, L, N), jnp.float32) * (N ** -0.5),
            jax.random.normal(ks[4], (H,)) * 0.3,
            jax.random.normal(ks[5], (H,)) * 0.1)


@pytest.mark.parametrize("L,Q", [(256, 128), (512, 128), (512, 256),
                                 (128, 128)])
@pytest.mark.parametrize("P,N", [(64, 128), (128, 128)])
def test_ssd_shapes(L, Q, P, N):
    from repro.kernels.ssd import ssd_chunked_pallas
    from repro.models.ssm import ssd_chunked
    x, dt, B, C, A_log, D = _ssd_inputs(jax.random.PRNGKey(L + P), 2, L, 2,
                                        P, N)
    y1, s1 = ssd_chunked_pallas(x, dt, B, C, A_log, D, chunk=Q,
                                interpret=True)
    y2, s2 = ssd_chunked(x, dt, B, C, A_log, D, chunk=Q)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=2e-5, rtol=2e-5)


def test_ssd_state_feeds_decode():
    """Kernel final state continues exactly into the recurrent decode path."""
    from repro.kernels.ssd import ssd_chunked_pallas
    from repro.models.ssm import ssd_chunked
    x, dt, B, C, A_log, D = _ssd_inputs(jax.random.PRNGKey(9), 1, 256, 2,
                                        64, 128)
    _, s_k = ssd_chunked_pallas(x, dt, B, C, A_log, D, chunk=128,
                                interpret=True)
    _, s_r = ssd_chunked(x, dt, B, C, A_log, D, chunk=64)  # different chunking
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# contraction precision: each kernel chooses its own, whatever the caller's
# default (on a TPU the default contracts f32 operands in one bf16 pass)
# ---------------------------------------------------------------------------
def _kernel_jaxpr(name, dtype):
    from repro.kernels.ssd import ssd_chunked_pallas
    z = lambda *shape, dt=dtype: jnp.zeros(shape, dt)  # noqa: E731
    if name == "swa":
        fn = lambda q: swa_attention_pallas(  # noqa: E731
            q, q, q, window=128, scale=0.125, interpret=True)
        args = (z(1, 1, 256, 64),)
    elif name == "spmm":
        fn = lambda b, i, x: spmm_blocked_ell(b, i, x,  # noqa: E731
                                              interpret=True)
        args = (z(2, 2, 128, 128), z(2, 2, dt=jnp.int32), z(256, 128))
    else:
        fn = lambda *a: ssd_chunked_pallas(*a, chunk=128,  # noqa: E731
                                           interpret=True)
        args = (z(1, 256, 2, 64), z(1, 256, 2), z(1, 256, 128),
                z(1, 256, 128), z(2, dt=jnp.float32), z(2, dt=jnp.float32))
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("name", ["swa", "spmm", "ssd"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_contraction_precision(name, dtype):
    """f32 inputs contract at HIGHEST in every dot; narrower inputs take the
    default, except SSD's prefix-sum matmul, which stands for a cumsum."""
    text = _kernel_jaxpr(name, dtype)
    dots = text.count("dot_general[")
    highest = text.count("precision=(Precision.HIGHEST")
    assert dots > 0
    if dtype == jnp.float32:
        assert highest == dots
    else:
        assert highest == (1 if name == "ssd" else 0)
