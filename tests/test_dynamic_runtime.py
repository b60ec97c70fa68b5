"""Dynamic rescheduler, straggler monitor, elastic runtime, and the
shard_map pipeline executor (subprocess: needs >1 host device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import (DATASETS, DynamicScheduler, GraphDataset, PerfModel,
                        gcn_workload, paper_system, signature)
from repro.runtime import ElasticRuntime, StragglerMonitor

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def dyn():
    return DynamicScheduler(paper_system("pcie4"), PerfModel(), mode="perf")


def test_signature_quantization():
    wl1 = gcn_workload(DATASETS["OA"])
    wl2 = gcn_workload(DATASETS["OA"])
    assert signature(wl1) == signature(wl2)
    dense = GraphDataset("x", DATASETS["OA"].vertices,
                         DATASETS["OA"].edges * 100, 128)
    assert signature(gcn_workload(dense)) != signature(wl1)


def test_dynamic_caches_and_reschedules(dyn):
    wl = gcn_workload(DATASETS["OP"])
    r1 = dyn.submit(wl)
    r2 = dyn.submit(wl)                       # same signature -> cached
    assert r1 is r2
    n_events = len(dyn.events)
    dyn.submit(gcn_workload(DATASETS["S1"]))  # drift
    assert len(dyn.events) == n_events + 1
    assert dyn.events[-1].reason == "drift"


def test_resize_forces_reschedule():
    dyn = DynamicScheduler(paper_system("pcie4"), PerfModel(), mode="perf")
    wl = gcn_workload(DATASETS["OP"])
    r1 = dyn.submit(wl)
    dyn.resize(0, 2)
    r2 = dyn.submit(wl)
    assert all(s.dev.name == "GPU" for s in r2.pipeline.stages)


def test_straggler_monitor_flags_persistent_only():
    m = StragglerMonitor(2, baselines=[1.0, 1.0], patience=3)
    # transient spike: no flag
    assert not m.observe(0, 2.0)
    assert not m.observe(0, 1.0)
    assert not m.observe(0, 2.0)
    # persistent drift on stage 1
    flagged = [m.observe(1, 2.5) for _ in range(6)]
    assert any(flagged)
    assert 1 in m.flagged()


def test_elastic_runtime_story():
    dyn = DynamicScheduler(paper_system("pcie4"), PerfModel(), mode="perf")
    rt = ElasticRuntime(dyn, gcn_workload(DATASETS["OP"]))
    first = rt.schedule.mnemonic
    assert "F" in first                     # heterogeneous at full pool
    r = rt.on_failure("FPGA", 3)
    assert "F" not in r.mnemonic            # all FPGAs gone
    r = rt.on_join("FPGA", 3)
    assert r.mnemonic == first              # recovered
    assert len(rt.log) >= 4


def test_pipeline_executor_multi_device():
    """Run the shard_map pipeline on 4 host devices in a subprocess."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, r"%s")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.runtime import PipelineExecutor
        mesh = make_mesh((4,), ("stage",))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(size=(4, 16, 16)).astype(np.float32) * 0.1)
        ex = PipelineExecutor(mesh, "stage",
                              [lambda p, x: x @ p["w"] + 1.0] * 4,
                              {"w": Ws}, (8, 16))
        micro = jnp.asarray(rng.normal(size=(5, 8, 16)).astype(np.float32))
        out = ex(micro)
        exp = micro
        for s in range(4):
            exp = jnp.einsum("mbf,fg->mbg", exp, Ws[s]) + 1.0
        err = float(jnp.abs(out - exp).max())
        assert err < 1e-5, err
        print("OK", err)
    """ % (REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_grouped_pipeline_executor_multi_device():
    """DP-sized stage groups (2,1,1) on 4 host devices: group heads chain
    the stage fns exactly like a sequential reference."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, r"%s")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.runtime import GroupedPipelineExecutor
        mesh = make_mesh((4,), ("stage",))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(size=(3, 16, 16)).astype(np.float32) * 0.1)
        ex = GroupedPipelineExecutor(
            mesh, "stage", [lambda p, x: x @ p["w"] + 1.0] * 3,
            {"w": Ws}, (8, 16), group_sizes=(2, 1, 1))
        micro = jnp.asarray(rng.normal(size=(5, 8, 16)).astype(np.float32))
        out = ex(micro)
        exp = micro
        for s in range(3):
            exp = jnp.einsum("mbf,fg->mbg", exp, Ws[s]) + 1.0
        err = float(jnp.abs(out - exp).max())
        assert err < 1e-5, err
        print("OK", err)
    """ % (REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_pallas_backend_mesh_mode_multi_device():
    """PallasPipelineBackend lowers a DP schedule onto the grouped executor
    with mesh slices sized by Stage.n; completion times stay parity with
    the analytic model."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, r"%s")
        from repro.core import (DATASETS, DynamicScheduler, PerfModel,
                                gcn_workload, paper_system)
        from repro.runtime import AnalyticBackend, PallasPipelineBackend
        wl = gcn_workload(DATASETS["OA"])
        dyn = DynamicScheduler(paper_system("pcie4"), PerfModel())
        res = dyn.submit(wl)
        be = PallasPipelineBackend(mode="mesh", act_dim=4, act_batch=2)
        h = be.prepare(res, wl, epoch=dyn.epoch)
        assert h.mode == "mesh", h.mode
        runner = h.payload
        assert runner.group_sizes == tuple(
            s.n for s in res.pipeline.stages), runner.group_sizes
        rep = be.execute(h, 3, 0.0)
        ana = AnalyticBackend()
        rep2 = ana.execute(ana.prepare(res, wl), 3, 0.0)
        assert rep.finishes == rep2.finishes
        assert rep.wall > 0.0
        print("OK", runner.group_sizes)
    """ % (REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
