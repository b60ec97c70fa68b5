"""Builds the system under test from a configuration file.

The wiring mirrors ``repro.launch.serve.run_stream`` for ``--stream
--backend pallas`` (no cluster, no fleet, no governor, no tracer), because
the program has no factory that takes a configuration: DynamicScheduler over
``paper_system`` with ``PerfModel()``, the configuration's batcher (or
tenancy layer), ``LoadWatermarkPolicy``, the pallas backend and
``Router(async_mode=True)``.

The backend is ``PallasPipelineBackend`` with two additions that leave the
served arithmetic alone: every stage structure is warmed for each
microbatch count it can be given as soon as it is first prepared, so that
nothing compiles once the measured window has begun; and the output of
every dispatched batch is kept, on the device, for the check after the
window.
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.core import DynamicScheduler, PerfModel, paper_system
from repro.core.workload import (GraphDataset, gcn_workload,
                                 swa_transformer_workload)
from repro.runtime import PallasPipelineBackend
from repro.serving import LoadWatermarkPolicy, Router, SignatureBatcher
from repro.tenancy import build_tenancy, parse_tenants

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def workload(name: str, cfg: dict):
    """The configuration's workload ``name`` as a ``repro`` Workload."""
    w = cfg["workloads"][name]
    if w["model"] == "gcn":
        ds = GraphDataset(w["graph"], w["vertices"], w["edges"],
                          w["features"])
        return gcn_workload(ds, hidden=w["hidden"], layers=w["layers"])
    if w["model"] == "swa_t":
        return swa_transformer_workload(
            w["seq_len"], w["window"], layers=cfg["swa_layers"], d=w["d"],
            heads=w["heads"], ffn_mult=w["ffn_mult"])
    raise ValueError(f"{name}: unknown model {w['model']!r}")


class RecordingBackend(PallasPipelineBackend):
    """``outputs`` holds ``(stage kinds, microbatch count, output)`` for
    every batch dispatched while ``record`` is set."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.record = False
        self.outputs: list = []
        self.kinds: dict = {}          # id(payload) -> stage kinds
        self.structures_in_window = 0

    def prepare(self, schedule, workload, *, epoch: int = 0):
        import jax
        before = len(self.prepared)
        h = super().prepare(schedule, workload, epoch=epoch)
        if len(self.prepared) > before:
            (kinds, _), _ = next(reversed(self.prepared.items()))
            self.kinds[id(h.payload)] = kinds
            self.structures_in_window += self.record
            for m in range(1, self.max_micro + 1):
                jax.block_until_ready(
                    super().dispatch(h, self.microbatches(m)))
        return h

    def dispatch(self, handle, micro):
        outs = super().dispatch(handle, micro)
        if self.record:
            self.outputs.append((self.kinds[id(handle.payload)],
                                 micro.shape[0], outs[-1]))
        return outs


def build(cfg: dict, provisioned_rate: float, backend=None):
    """(router, backend) for configuration ``cfg``; ``backend`` replaces
    the recording pallas backend (``knee.py`` passes the analytic one)."""
    system = paper_system(cfg["interconnect"])
    pool = {dev.name: n for dev, n in system.pools}
    if pool != cfg["pool"]:
        raise ValueError(f"paper_system gives {pool}, the configuration "
                         f"states {cfg['pool']}")
    dyn = DynamicScheduler(system, PerfModel(), mode="perf")
    b = cfg["batcher"]
    manager = None
    if cfg["tenants"]:
        t = cfg["tenants"]
        manager, batcher = build_tenancy(
            parse_tenants(t["spec"]), preempt=t["preempt"],
            starve_after=t["starve_after_s"], max_batch=b["max_batch"],
            max_wait=b["max_wait_s"])
    else:
        batcher = SignatureBatcher(max_batch=b["max_batch"],
                                   max_wait=b["max_wait_s"])
    p = cfg["policy"]
    if backend is None:
        backend = RecordingBackend(**cfg["backend"])
    router = Router(
        dyn, batcher=batcher,
        policy=LoadWatermarkPolicy(low=p["low"], high=p["high"],
                                   window=p["window_s"],
                                   cooldown=p["cooldown_s"]),
        backend=backend, max_cells=cfg["max_cells"],
        async_mode=cfg["async_dispatch"], tenancy=manager)
    router.provisioned_capacity = provisioned_rate
    return router, backend
