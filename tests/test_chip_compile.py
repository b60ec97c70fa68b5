"""Compile the main path's device programs for a described TPU v5e, with no
chip attached: the Pallas kernels at the widths chip_smoke.py runs
(``interpret=False``), one backend stage chain on one chip, and the grouped
pipeline executor on a 2x2 mesh. What the chip's compiler refuses fails
here, at no chip time. Nothing runs, so nothing here is a result or a
time.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and the test workers
all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *shapes, precision="default"):
    with jax.default_matmul_precision(precision):
        return jax.jit(fn).lower(*shapes).compile().as_text()


# f32 kernels pin full f32 contraction and bf16 ones take the default, under
# either ambient matmul precision: Mosaic must accept both contractions
@pytest.mark.parametrize("dtype,precision", [(jnp.bfloat16, "default"),
                                             (jnp.float32, "highest")])
def test_swa_compiles_at_swa_t_width(one_chip, dtype, precision):
    from repro.kernels import swa_attention_pallas
    s = _on(one_chip, (1, 8, 4096, 64), dtype)
    hlo = _kernel_hlo(
        lambda q, k, v: swa_attention_pallas(q, k, v, window=512,
                                             scale=0.125), s, s, s,
        precision=precision)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_spmm_compiles_n128(one_chip, precision):
    from repro.kernels import spmm_blocked_ell
    hlo = _kernel_hlo(spmm_blocked_ell,
                      _on(one_chip, (128, 128, 128, 128)),
                      _on(one_chip, (128, 128), jnp.int32),
                      _on(one_chip, (16384, 128)), precision=precision)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_ssd_compiles_at_mamba2_width(one_chip, precision):
    from repro.kernels.ssd import ssd_chunked_pallas
    b, L, H, Pd, N = 1, 4096, 48, 64, 128
    hlo = _kernel_hlo(
        lambda *a: ssd_chunked_pallas(*a, chunk=256),
        _on(one_chip, (b, L, H, Pd)), _on(one_chip, (b, L, H)),
        _on(one_chip, (b, L, N)), _on(one_chip, (b, L, N)),
        _on(one_chip, (H,)), _on(one_chip, (H,)), precision=precision)
    assert "tpu_custom_call" in hlo


def test_backend_stage_chain_compiles_on_one_chip(one_chip):
    """The per-stage jits the pallas backend serves with on one chip."""
    from repro.core import (DATASETS, DynamicScheduler, PerfModel,
                            gcn_workload, paper_system)
    from repro.runtime import PallasPipelineBackend
    wl = gcn_workload(DATASETS["OA"])
    res = DynamicScheduler(paper_system("pcie4"), PerfModel()).submit(wl)
    be = PallasPipelineBackend(mode="chain")
    h = be.prepare(res, wl)
    stage_jits, _ = h.payload
    assert len(stage_jits) == len(res.pipeline.stages)
    F, B = be.act_dim, be.act_batch
    for sj in stage_jits:
        compiled = sj.lower(_on(one_chip, (F, F)),
                            _on(one_chip, (be.max_micro, B, F))).compile()
        assert compiled.as_text()


def test_grouped_executor_compiles_on_2x2_mesh(topo):
    """Stage groups (2, 1, 1) over the four described chips: the stage
    handoffs lower to collective-permutes over ICI."""
    from repro.runtime import GroupedPipelineExecutor
    mesh = Mesh(np.asarray(topo.devices[:4]), ("stage",))
    ws = np.zeros((3, 16, 16), np.float32)
    ex = GroupedPipelineExecutor(mesh, "stage",
                                 [lambda p, x: x @ p["w"] + 1.0] * 3,
                                 {"w": ws}, (8, 16), group_sizes=(2, 1, 1))
    rep = NamedSharding(mesh, P())
    hlo = ex._step.lower({"w": _on(rep, ws.shape)},
                         _on(rep, (5, 8, 16))).compile().as_text()
    assert "collective-permute" in hlo
