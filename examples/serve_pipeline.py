"""End-to-end driver: serve GCN inference through the inter-operator
pipeline runtime with a DYPE-chosen schedule.

This is the paper's system running for real (CPU-scale): a stream of
batched requests flows through pipeline stages placed on mesh device
groups (shard_map + collective_permute — the ICI analogue of the paper's
P2P transfers). Mid-stream, the input graph's sparsity changes; the
DynamicScheduler re-partitions the pipeline and serving continues.

Run:  PYTHONPATH=src python examples/serve_pipeline.py
"""
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (DynamicScheduler, PerfModel, Workload, KernelSpec,
                        paper_system)
from repro.launch.mesh import make_mesh
from repro.models.gnn import gcn_forward, init_gcn_params
from repro.runtime import PipelineExecutor
from repro.sparse import random_graph_csr, spmm_csr


def tiny_gcn_workload(v, e, feat, hidden=128, layers=2) -> Workload:
    ks = []
    f = feat
    for layer in range(1, layers + 1):
        ks.append(KernelSpec(f"SpMM{layer}", "spmm", M=v, K=v, N=f, nnz=e + v))
        ks.append(KernelSpec(f"GeMM{layer}", "gemm", M=v, K=f, N=hidden))
        f = hidden
    return Workload(f"tiny-gcn-v{v}-e{e}", tuple(ks))


def main():
    V, F, HID = 1024, 128, 128
    mesh = make_mesh((4,), ("stage",))

    # 1) DYPE decides the stage partition from the data characteristics
    system = paper_system("pcie4")
    dyn = DynamicScheduler(system, PerfModel(), mode="perf")
    wl = tiny_gcn_workload(V, 16 * V, F)
    schedule = dyn.submit(wl)
    print(f"[dype] schedule for {wl.name}: {schedule.mnemonic} "
          f"({len(schedule.pipeline.stages)} stages)")

    # 2) deploy: 2-layer GCN as a 4-stage pipeline over the mesh
    #    (SpMM1 | GeMM1 | SpMM2 | GeMM2), one mesh group per stage
    graph = random_graph_csr(V, 16 * V, seed=0)
    key = jax.random.PRNGKey(0)
    params = init_gcn_params(key, F, HID)
    w1, w2 = params[0]["theta"], params[1]["theta"]
    # stage s holds only its own weights (sharded over the stage axis)
    stacked = {"w": jnp.stack([w1, w1, w2, w2])}   # spmm stages ignore theirs

    def spmm_stage(p, x):
        return spmm_csr(graph, x)

    def gemm_relu_stage(p, x):
        return jax.nn.relu(x @ p["w"])

    def gemm_stage(p, x):
        return x @ p["w"]

    fns = [spmm_stage, gemm_relu_stage, spmm_stage, gemm_stage]
    ex = PipelineExecutor(mesh, "stage", fns, stacked, (V, F))

    # 3) serve a stream of batched requests
    rng = np.random.default_rng(0)
    n_micro = 8
    micro = jnp.asarray(rng.normal(size=(n_micro, V, F)).astype(np.float32))
    t0 = time.time()
    out = ex(micro)
    out.block_until_ready()
    dt = time.time() - t0
    # reference
    exp = jnp.stack([gcn_forward(params, graph, micro[i])
                     for i in range(n_micro)])
    err = float(jnp.abs(out - exp).max())
    print(f"[serve] {n_micro} microbatches in {dt*1e3:.1f} ms "
          f"({n_micro/dt:.1f} inf/s), pipeline vs reference max err {err:.2e}")
    assert err < 1e-3

    # 4) the data drifts (graph becomes denser) -> DYPE reschedules
    wl2 = tiny_gcn_workload(V, 128 * V, F)
    s2 = dyn.submit(wl2)
    print(f"[dype] drift: {wl.name} -> {wl2.name}: "
          f"{schedule.mnemonic} -> {s2.mnemonic}")
    print("[done]")


if __name__ == "__main__":
    main()
