"""Builds the system under test from a configuration file.

The wiring mirrors ``repro.launch.serve.run_stream`` for ``--stream
--backend pallas`` (no cluster, no fleet, no governor), because the program
has no factory that takes a configuration: DynamicScheduler over
``paper_system`` with ``PerfModel()``, the configuration's batcher (or
tenancy layer), ``LoadWatermarkPolicy``, the backend and
``Router(async_mode=True)``.

What a configuration serves on the device comes from its plug-in,
``plugins/<plugin>.py`` (the configuration's ``"plugin"`` key, ``"proxy"``
when absent): the ``repro`` workloads, the backend, how a new stage
structure is warmed and what of each output is kept for the check.
``Recorder`` wraps the plug-in's backend in place. It leaves the served
arithmetic alone: it warms every new stage structure as soon as it is
first prepared, so that nothing compiles once the measured window has
begun, and while ``record`` is set it notes every batch dispatched.
"""
from __future__ import annotations

import dataclasses
import json

from repro.core import DynamicScheduler, PerfModel, paper_system
from repro.serving import LoadWatermarkPolicy, Router, SignatureBatcher
from repro.tenancy import build_tenancy, parse_tenants

from . import files


def load_config(name: str) -> dict:
    return json.loads(files.find("configs", name, ".json").read_text())


def plugin(cfg: dict):
    """The plug-in module of configuration ``cfg``."""
    return files.load("plugins", cfg.get("plugin", "proxy"))


def workload(name: str, cfg: dict):
    """The configuration's workload ``name`` as a ``repro`` Workload."""
    return plugin(cfg).workload(name, cfg)


def stage_kinds(handle) -> tuple:
    """The kernel kinds of each stage of a handle's schedule."""
    wl = handle.workload
    return tuple(tuple(wl[i].kind for i in range(s.i0, s.i1))
                 for s in handle.schedule.pipeline.stages)


@dataclasses.dataclass
class Record:
    """One batch dispatched in the window."""
    workload: str          # the repro Workload's name
    kinds: tuple           # kernel kinds of each stage
    m: int                 # inputs in the batch (the input's leading axis)
    input: object          # the batch input, as dispatched
    kept: object           # the plug-in's keep(output), on the device


class Recorder:
    """Wraps a backend in place: its ``prepare`` and ``dispatch``.

    The backend's ``submit`` has to run the device work through
    ``dispatch(handle, x)``, which returns the device arrays of the batch
    with the output last, and its ``prepare`` has to give a handle of a new
    stage structure a new ``payload`` object and a handle of a known one
    the known object. ``prepare`` calls the plug-in's ``warm`` on each new
    payload; ``dispatch`` appends a ``Record`` to ``records`` while
    ``record`` is set. ``counters`` are the backend's integer attributes
    that a traced window reports the change of: ``launches`` and the
    plug-in's ``COUNTERS``."""

    def __init__(self, backend, plug):
        self.backend = backend
        self.plugin = plug
        self.record = False
        self.records: list = []
        self.kinds: dict = {}          # id(payload) -> (payload, kinds)
        self.structures_in_window = 0
        self._warming = False
        self.counters = tuple(
            c for c in ("launches", *getattr(plug, "COUNTERS", ()))
            if hasattr(backend, c))
        prepare, dispatch = backend.prepare, backend.dispatch
        keep = plug.keep

        def recording_prepare(schedule, workload, *, epoch: int = 0):
            h = prepare(schedule, workload, epoch=epoch)
            if id(h.payload) not in self.kinds:
                self.kinds[id(h.payload)] = (h.payload, stage_kinds(h))
                self.structures_in_window += self.record
                self._warming = True
                try:
                    plug.warm(backend, h)
                finally:
                    self._warming = False
            return h

        def recording_dispatch(handle, x):
            outs = dispatch(handle, x)
            if self.record and not self._warming:
                self.records.append(Record(
                    handle.workload.name, self.kinds[id(handle.payload)][1],
                    x.shape[0], x, keep(outs[-1])))
            return outs

        backend.prepare = recording_prepare
        backend.dispatch = recording_dispatch

    @property
    def structures(self) -> int:
        """Stage structures prepared (and warmed) so far."""
        return len(self.kinds)

    def counts(self) -> dict:
        return {c: getattr(self.backend, c) for c in self.counters}


def build(cfg: dict, provisioned_rate: float, backend=None, *,
          tracer=None):
    """(router, served) for configuration ``cfg``. Without ``backend`` the
    plug-in's backend serves, wrapped by a ``Recorder``, which is
    ``served``; a given ``backend`` (``knee.py`` passes the analytic one)
    serves as it is and is ``served``. ``tracer`` goes into the Router,
    which hands it on to the Engine, the DP and the backend."""
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
    served = backend
    if backend is None:
        plug = plugin(cfg)
        backend = plug.backend(cfg)
        served = Recorder(backend, plug)
    router = Router(
        dyn, batcher=batcher,
        policy=LoadWatermarkPolicy(low=p["low"], high=p["high"],
                                   window=p["window_s"],
                                   cooldown=p["cooldown_s"]),
        backend=backend, max_cells=cfg["max_cells"],
        async_mode=cfg["async_dispatch"], tenancy=manager, tracer=tracer)
    router.provisioned_capacity = provisioned_rate
    return router, served
