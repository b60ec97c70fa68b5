"""Tests for the beyond-paper §Perf features: vocab-parallel cross-entropy,
int8 serving quantization, MoE capacity rightsizing, HLO analysis parsers."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# int8 serving quantization
# ---------------------------------------------------------------------------
def test_quantized_array_roundtrip():
    from repro.models.quant import QuantizedArray, quantize
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    q = quantize(w)
    assert q.dtype == jnp.int8 and q.shape == w.shape
    deq = q.astype(jnp.float32)
    rel = float(jnp.linalg.norm(deq - w) / jnp.linalg.norm(w))
    assert rel < 0.02                      # absmax int8: ~1% rms error


def test_quantized_array_scan_sliceable():
    from repro.models.quant import quantize
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 256, 512))
    q = quantize(w)

    def body(c, layer):
        return c + layer.astype(jnp.float32).sum(), None

    out, _ = jax.lax.scan(body, jnp.float32(0), q)
    expect = sum(float(quantize(w[i]).astype(jnp.float32).sum())
                 for i in range(4))
    assert float(out) == pytest.approx(expect, rel=1e-4)


def test_quantize_params_skips_small_and_vectors():
    from repro.models.quant import QuantizedArray, quantize_params
    params = {"norm": jnp.ones((4, 4096)),          # stacked vectors: skip
              "small": jnp.ones((64, 64)),          # too small: skip
              "embedding": jnp.ones((512, 256)),    # excluded by name
              "wi": jnp.ones((512, 512))}           # quantized
    q = quantize_params(params)
    assert isinstance(q["wi"], QuantizedArray)
    for k in ("norm", "small", "embedding"):
        assert not isinstance(q[k], QuantizedArray), k


def test_quantized_decode_matches_fp():
    from repro.configs import get_smoke
    from repro.models import (axis_env_for_mesh, decode_step, init_cache,
                              init_params, model_decls)
    from repro.models.quant import QuantizedArray, quantize_params
    cfg = get_smoke("mistral-large-123b").replace(
        d_model=256, d_ff=512, n_heads=4, n_kv_heads=2, head_dim=64)
    mesh = make_mesh((1, 1), ("data", "model"))
    ax = axis_env_for_mesh(mesh)
    params = init_params(model_decls(cfg, ax), jax.random.PRNGKey(0),
                         cfg.pdtype)
    qparams = quantize_params(params)
    nq = sum(isinstance(l, QuantizedArray)
             for l in jax.tree.leaves(
                 qparams, is_leaf=lambda x: isinstance(x, QuantizedArray)))
    assert nq >= 4
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0,
                             cfg.vocab_size)
    l1, _ = decode_step(params, tok, jnp.int32(3), init_cache(cfg, 2, 64),
                        cfg, ax, mesh)
    l2, _ = decode_step(qparams, tok, jnp.int32(3), init_cache(cfg, 2, 64),
                        cfg, ax, mesh)
    a, b = np.asarray(l1, np.float32), np.asarray(l2, np.float32)
    assert np.linalg.norm(a - b) / np.linalg.norm(a) < 0.1


# ---------------------------------------------------------------------------
# vocab-parallel cross-entropy (needs a sharded mesh -> subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_vocab_parallel_loss_matches_baseline():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, r"%s")
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.models import (axis_env_for_mesh, init_params,
                                  model_decls, lm_loss)
        from repro.launch.mesh import make_mesh
        cfg = get_smoke("gemma-2b").replace(vocab_size=512)
        mesh = make_mesh((2, 2), ("data", "model"))
        ax = axis_env_for_mesh(mesh)
        params = init_params(model_decls(cfg, ax), jax.random.PRNGKey(0),
                             cfg.pdtype)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0,
                                    cfg.vocab_size)
        batch = {"tokens": tokens, "labels": tokens}
        cfg2 = cfg.replace(vp_loss=False)
        l1 = float(jax.jit(lambda p: lm_loss(p, batch, cfg, ax, mesh))(params))
        l2 = float(jax.jit(lambda p: lm_loss(p, batch, cfg2, ax, mesh))(params))
        assert abs(l1 - l2) / abs(l2) < 1e-3, (l1, l2)
        g1 = jax.jit(jax.grad(lambda p: lm_loss(p, batch, cfg, ax, mesh)))(params)
        g2 = jax.jit(jax.grad(lambda p: lm_loss(p, batch, cfg2, ax, mesh)))(params)
        num = den = 0.0
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            a = np.asarray(a, np.float32); b = np.asarray(b, np.float32)
            num += float(((a - b) ** 2).sum()); den += float((b ** 2).sum())
        assert (num / den) ** 0.5 < 5e-2
        print("OK")
    """ % (REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# MoE capacity rightsizing
# ---------------------------------------------------------------------------
def test_moe_decode_small_capacity_still_correct():
    from repro.configs import get_smoke
    from repro.models import (axis_env_for_mesh, decode_step, init_cache,
                              init_params, model_decls)
    cfg = get_smoke("deepseek-v3-671b")
    mesh = make_mesh((1, 1), ("data", "model"))
    ax = axis_env_for_mesh(mesh)
    params = init_params(model_decls(cfg, ax), jax.random.PRNGKey(0),
                         cfg.pdtype)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0,
                             cfg.vocab_size)
    logits, _ = decode_step(params, tok, jnp.int32(3), init_cache(cfg, 2, 32),
                            cfg, ax, mesh)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# ---------------------------------------------------------------------------
# HLO analysis parsers (the roofline substrate)
# ---------------------------------------------------------------------------
HLO = """
HloModule test

%inner (p0: f32[8,16]) -> f32[8,32] {
  %p0 = f32[8,16] parameter(0)
  %w = f32[16,32] constant(0)
  ROOT %dot.1 = f32[8,32]{1,0} dot(%p0, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%cond (c: (s32[], f32[8,32])) -> pred[] {
  %c = (s32[], f32[8,32]) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %k = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (c: (s32[], f32[8,32])) -> (s32[], f32[8,32]) {
  %c = (s32[], f32[8,32]) parameter(0)
  %x = f32[8,16]{1,0} constant(0)
  %y = f32[8,32]{1,0} fusion(%x), kind=kLoop, calls=%inner
  %ar = f32[8,32]{1,0} all-reduce(%y), replica_groups={{0,1}}, to_apply=%add
  %i = s32[] get-tuple-element(%c), index=0
  ROOT %t = (s32[], f32[8,32]) tuple(%i, %ar)
}

ENTRY %main () -> (s32[], f32[8,32]) {
  %init = (s32[], f32[8,32]) tuple()
  ROOT %w1 = (s32[], f32[8,32]) while(%init), condition=%cond, body=%body
}
"""


def test_parse_dot_flops_trip_corrected():
    from repro.launch.dryrun import parse_dot_flops
    # dot: 2 * (8*32) * 16 = 8192 flops, x5 while trips
    assert parse_dot_flops(HLO) == pytest.approx(8192 * 5)


def test_parse_collectives_trip_corrected():
    from repro.launch.dryrun import parse_collectives
    out = parse_collectives(HLO)
    # all-reduce of f32[8,32] = 1024 B, x5 trips
    assert out["all-reduce"]["bytes"] == 1024 * 5
    assert out["all-reduce"]["count"] == 5
