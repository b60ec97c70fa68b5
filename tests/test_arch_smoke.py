"""Per-architecture smoke tests (deliverable f): every assigned arch
instantiates a REDUCED config of the same family and runs one forward/loss
and one decode step on CPU, asserting shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, LONG_SKIP, get_config, get_smoke
from repro.launch.mesh import make_mesh
from repro.models import (axis_env_for_mesh, decode_step, init_cache,
                          init_params, lm_loss, model_decls, param_count)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _batch(cfg, key, B=2, S=128):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jnp.ones(
            (B, cfg.prefix_tokens, cfg.frontend_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["src_frames"] = jnp.ones((B, S, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_and_decode(arch, mesh):
    cfg = get_smoke(arch)
    ax = axis_env_for_mesh(mesh)
    key = jax.random.PRNGKey(0)
    decls = model_decls(cfg, ax)
    params = init_params(decls, key, cfg.pdtype)
    B, S = 2, 128
    batch = _batch(cfg, key, B, S)

    loss = lm_loss(params, batch, cfg, ax, mesh)
    assert np.isfinite(float(loss)), (arch, float(loss))
    assert float(loss) > 0

    cache = init_cache(cfg, B, 64)
    if cfg.family == "encdec":
        cache["enc_out"] = jnp.ones((B, 64, cfg.d_model), cfg.cdtype)
    logits, cache2 = decode_step(params, batch["tokens"][:, :1],
                                 jnp.int32(3), cache, cfg, ax, mesh)
    assert logits.shape[0] == B and logits.shape[-1] == cfg.vocab_size
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """Full configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expect = {
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "mamba2-780m": (48, 1536, None, None, 0, 50280),
        "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
    }[arch]
    L, d, h, kv, ff, vocab = expect
    assert cfg.n_layers == L
    assert cfg.d_model == d
    if h is not None:
        assert cfg.n_heads == h
        assert cfg.n_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == vocab


def test_cells_cover_40():
    from repro.configs import cells
    cs = cells()
    assert len(cs) == 40
    skipped = [c for c in cs if c[2]]
    assert {c[0] for c in skipped} == LONG_SKIP
    assert all(c[1] == "long_500k" for c in skipped)


def test_smoke_param_counts_small():
    """Smoke configs stay CPU-sized (<60M params)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    ax = axis_env_for_mesh(mesh)
    for arch in ARCHS:
        decls = model_decls(get_smoke(arch), ax)
        assert param_count(decls) < 6e7, arch
