"""Plug-in of the toy configuration: a backend whose every stage doubles its
input, one jitted program a stage. It joins the benchmark by new files
alone (``bench/tests/test_plugins.py``)."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import files

reference = files.load("references", "toy_doubler")

LIMITS = {"toy_gap": 0.0}         # doubling in float32 is exact
COUNTERS = ("doublings",)
WIDTH = 4                         # the configuration's backend width


def toy_stage(x):
    return x + x


def workload(name: str, cfg: dict):
    """A GCN or SWA-T workload at the configuration's small sizes, named
    by its configuration entry."""
    from repro.core.workload import (GraphDataset, gcn_workload,
                                     swa_transformer_workload)

    w = cfg["workloads"][name]
    if w["model"] == "gcn":
        wl = gcn_workload(GraphDataset(name, w["vertices"], w["edges"],
                                       w["features"]),
                          hidden=w["hidden"], layers=w["layers"])
    else:
        wl = swa_transformer_workload(w["seq_len"], w["window"],
                                      layers=w["layers"], d=w["d"],
                                      heads=w["heads"], ffn_mult=2)
    return dataclasses.replace(wl, name=name)


def backend(cfg: dict):
    from repro.obs import NULL_TRACER
    from repro.runtime.backend import (AnalyticBackend, BackendFuture,
                                       ExecutionBackend, PipelineHandle,
                                       batch_size)
    import jax
    import jax.numpy as jnp

    class Doubler(ExecutionBackend):
        name = "toy-doubler"

        def __init__(self, width: int, max_inputs: int):
            self.width = width
            self.max_inputs = max_inputs
            self.stage = jax.jit(toy_stage)
            self.prepared: dict = {}       # stage count -> payload
            self.launches = 0
            self.doublings = 0             # rows doubled
            self.tracer = NULL_TRACER
            self.timing = AnalyticBackend()

        def prepare(self, schedule, workload, *, epoch: int = 0):
            n = len(schedule.pipeline.stages)
            payload = self.prepared.setdefault(n, (self.stage,) * n)
            return PipelineHandle(schedule, workload, epoch=epoch,
                                  backend=self.name, payload=payload)

        def inputs(self, n: int):
            m = max(1, min(n, self.max_inputs))
            return jnp.arange(m * self.width, dtype=jnp.float32).reshape(
                m, self.width)

        def dispatch(self, handle, x) -> tuple:
            with self.tracer.span("backend", "toy.dispatch", 0.0):
                outs = []
                for stage in handle.payload:
                    x = stage(x)
                    outs.append(x)
                self.launches += len(handle.payload)
                self.doublings += x.shape[0] * len(handle.payload)
                return tuple(outs)

        def submit(self, handle, batch, t0: float):
            report = self.timing.execute(handle, batch, t0)
            outs = self.dispatch(handle, self.inputs(batch_size(batch)))

            def resolve():
                jax.block_until_ready(outs)
                return report
            return BackendFuture(t0, report.finishes, resolve)

    return Doubler(**cfg["backend"])


def warm(backend, handle) -> None:
    import jax

    for m in range(1, backend.max_inputs + 1):
        jax.block_until_ready(backend.dispatch(handle, backend.inputs(m)))


def keep(output):
    return output


def work(workload: str, kinds: tuple, m: int) -> tuple[float, float]:
    """(flops, HBM bytes) of one stage program on ``m`` inputs: one add a
    value, each value read and written once in float32."""
    n = m * WIDTH
    return float(n), 8.0 * n


def numbers(records, platform: str) -> dict:
    """``toy_gap``: the largest absolute difference between a kept output
    and the reference over its recorded input."""
    import jax

    host = jax.device_get([(r.input, r.kept) for r in records])
    gap = 0.0
    for r, (x, got) in zip(records, host):
        want = reference.chain(np.asarray(x), len(r.kinds))
        gap = max(gap, float(np.abs(np.asarray(got) - want).max()))
    return {"toy_gap": gap}
