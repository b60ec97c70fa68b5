"""chip_smoke.py on the CPU: its phases at tiny sizes (Pallas kernels in
interpret mode), its refusal to run without a TPU, and the compile-cache
helper the entry points call."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    rc = smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""                          # no result line at all
    assert "'cpu'" in err and "TPU" in err


def test_serve_phase_tiny(smoke):
    rows = smoke.serve_phase("cpu", streams=(
        ("tiny-diurnal", ["--duration", "4", "--day", "4"]),
        ("tiny-tenants", ["--duration", "4", "--day", "4",
                          "--tenants", smoke.TENANTS]),
    ))
    assert [r["stream"] for r in rows] == ["tiny-diurnal", "tiny-tenants"]
    for r in rows:
        assert r["arrivals"] > 0
        assert r["completed"] + r["dropped"] == r["arrivals"]
        assert r["batches"] > 0
        assert set(r["modes"]) == {"chain"}   # one device: the chain
        assert r["chain_err"] < 1e-5          # f32 on the CPU


def test_kernel_phase_tiny(smoke):
    errs = smoke.kernel_phase(
        "cpu", interpret=True,
        swa=dict(B=1, H=2, S=256, D=64, window=128),
        spmm=dict(vertices=512, n=128, band=16, far=0.0),
        ssd=dict(b=1, L=256, H=2, P=64, N=128, chunk=128))
    assert set(errs) == {"swa_float32", "swa_bfloat16", "spmm_float32",
                         "ssd_y_float32", "ssd_state_float32"}
    assert errs["swa_float32"] < 2e-5 and errs["spmm_float32"] < 1e-4
    assert errs["ssd_y_float32"] < 2e-5 and errs["ssd_state_float32"] < 2e-5


def _run(code: str, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path on 4 virtual CPU devices: a mesh handle over
    the DP's stage groups, matching the one-device chain."""
    r = _run("""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", r"%s")
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        res = smoke.four_chip_phase("cpu")
        assert res["n_mesh"] == sum(res["groups"]) > 1, res
        assert res["err"] < 1e-5 and res["ref_err"] < 1e-5, res
        print("OK", res)
    """ % (REPO / "chip_smoke.py"),
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_compile_cache_follows_env_else_checkout(tmp_path):
    env_dir = tmp_path / "cache"
    r = _run("""
        import os, jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable
        assert enable() == os.environ["JAX_COMPILATION_CACHE_DIR"]
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
        print("OK")
    """, {"JAX_COMPILATION_CACHE_DIR": str(env_dir),
          "PYTHONPATH": str(REPO / "src")})
    assert r.returncode == 0, r.stderr[-2000:]
    assert any(env_dir.iterdir())             # the entry landed there
    r = _run("""
        import jax
        from repro.launch.compile_cache import CHECKOUT_CACHE, enable
        assert enable() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        print(CHECKOUT_CACHE)
    """, {"PYTHONPATH": str(REPO / "src"), "JAX_COMPILATION_CACHE_DIR": ""})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(REPO / ".jax_cache")
