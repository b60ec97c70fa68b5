"""ExecutionBackend — the seam between the DYPE schedule and what actually
runs it.

The DP scheduler produces a ``ScheduleResult``; *executing* it is a separate
concern with several legitimate substrates (HTS's point: the scheduler/
executor split must be a first-class interface so substrates plug in behind
one dispatch API). Every substrate implements three calls:

    prepare(schedule, workload) -> PipelineHandle
        Deploy the schedule: build whatever resident state execution needs
        (compiled pipeline, trace cursor, nothing at all) and stamp the
        scheduler epoch so stale handles are detectable.

    submit(handle, batch, t0) -> BackendFuture
        Non-blocking dispatch of a batch of ``len(batch)`` requests starting
        at simulated time ``t0``. The future's *simulated* completion times
        are available immediately (they come from the schedule model or a
        trace, never from the device), so callers can advance busy clocks
        and keep admitting/batching while the substrate executes;
        ``result()`` blocks until real work finishes and yields the full
        ``CompletionReport`` including measured wall/stage seconds.

    execute(handle, batch, t0) -> CompletionReport
        Blocking convenience: ``submit(...).result()``. The base class
        provides the inverse default (``submit`` wrapping a synchronous
        ``execute``), so a backend implements whichever is natural.

Three implementations ship:

  * ``AnalyticBackend`` — the GPipe fill+period arithmetic the Router used
    to inline: request i of a batch finishes at t0 + fill + i*period.
  * ``PallasPipelineBackend`` — lowers the schedule's stages onto the real
    shard_map pipeline (``GroupedPipelineExecutor``: collective_permute
    over a jax mesh whose stage slices are sized by the DP's per-stage
    device counts) and actually runs the microbatches; completion *times*
    still come from the schedule model so
    the simulated clock stays consistent, which is also what makes analytic
    vs pallas completion ordering bit-identical (the parity tests). Where
    the host exposes fewer devices than the DP's stage groups need (one
    chip, or the CPU tests) it runs the same stage chain sequentially on
    one device instead; the handle's ``mode`` says which it got.
  * ``ReplayBackend`` — deterministic timings from recorded traces
    (``TraceRecorder`` wraps any backend and captures them), for replaying
    production behavior in tests and what-if studies.
  * ``ClusterBackend`` — routes every handle to its owning worker peer in
    a ``repro.cluster`` control plane, so the Router/Engine serve across
    hosts with zero changes to scheduling code. A worker lost mid-batch
    surfaces as ``WorkerLost`` at reap; the Router re-queues that batch.

All simulated times are seconds; ``CompletionReport.wall`` carries real
elapsed wall-clock for backends that execute actual compute.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import time

from ..core.scheduler import ScheduleResult
from ..core.workload import Workload
from ..obs.trace import NULL_TRACER


class WorkerLost(Exception):
    """The peer executing a batch died before delivering its report. The
    Engine's reap converts this into a lost-batch delivery (report None)
    so the Router can re-queue the batch's requests — at-least-once
    semantics instead of stranded work."""


def pipeline_fill(res: ScheduleResult) -> float:
    """Latency of the first request through the pipeline (sum of stage
    in+exec+out times); subsequent requests stream at the period."""
    return sum(s.total for s in res.pipeline.stages)


def batch_size(batch) -> int:
    """Backends accept either a sized batch object or a bare int."""
    return batch if isinstance(batch, int) else len(batch)


@dataclasses.dataclass
class PipelineHandle:
    """A deployed schedule: everything a backend needs to run batches under
    it. ``epoch`` is the DynamicScheduler epoch at prepare time — a resize
    or objective flip bumps the scheduler's epoch, invalidating the handle
    (holders compare and re-prepare). ``mode`` is how the backend chose to
    run it, for backends with more than one way ("mesh" or "chain" on
    pallas; empty elsewhere)."""
    schedule: ScheduleResult
    workload: Workload
    epoch: int = 0
    backend: str = ""
    payload: object = None         # backend-specific resident state
    mode: str = ""

    def stale(self, current_epoch: int) -> bool:
        return self.epoch != current_epoch


@dataclasses.dataclass
class CompletionReport:
    """Per-batch execution outcome. All times are seconds.

    ``finishes[i]`` is the *simulated-clock* completion time of the batch's
    i-th request (batch order); ``stage_times`` are the schedule model's
    per-stage estimates for this batch. ``measured_stage_times`` are the
    per-stage seconds the substrate actually observed — this is what feeds
    the straggler monitors (ISSUE 3: measurements, not DP estimates).
    Backends without real compute synthesize them (analytic: the estimates
    themselves; replay: the recorded trace), so the feedback path is
    uniform across substrates. ``wall`` is real elapsed wall-clock.

    ``worker`` is the id of the host that *executed* the batch — stamped
    by ``WorkerCore`` on cluster runs ("" on single-host backends). With
    work stealing a batch may run on a different host than its cell's
    owner, so measured-time consumers (``WallClockCalibrator``) key on
    the executing worker, not the placement.

    ``stage_expected`` is the control plane's *belief* about this batch:
    per-stage ``(device name, exec seconds, transfer seconds)`` from the
    schedule the controller deployed to the executing worker (stamped by
    ``WorkerCore``; empty on single-host backends). Measured-vs-expected
    per stage is the signal ``repro.fleet.OnlineHostEstimator`` solves
    host scales from — carried in the report so a *stolen* batch's
    expectation is the thief's deployed schedule, not the owner's."""
    t0: float
    finishes: tuple
    energy_per_req: float
    stage_times: tuple             # schedule-model per-stage seconds
    wall: float = 0.0              # real wall-clock spent executing (s)
    measured_stage_times: tuple = ()   # observed per-stage seconds
    worker: str = ""               # executing host id (cluster runs)
    stage_expected: tuple = ()     # belief (dev, exec_s, xfer_s) per stage

    @property
    def finish(self) -> float:
        return max(self.finishes) if self.finishes else self.t0

    @property
    def measured(self) -> tuple:
        """Backend-measured per-stage seconds, falling back to the schedule
        estimates for reports that predate the measurement path."""
        return self.measured_stage_times or self.stage_times


class BackendFuture:
    """Handle to one in-flight batch dispatched via ``submit``.

    Two-phase by design: the *simulated* completion times (``t0``,
    ``finishes``, seconds on the shared simulated clock) are fixed at
    submission — every backend derives them from the schedule model or a
    recorded trace, never from the device — so the Engine can advance busy
    clocks and keep admitting without blocking. ``result()`` blocks until
    the substrate's real work completes and returns the full
    ``CompletionReport`` (measured wall/stage seconds filled in).

    Futures are single-threaded objects: ``result()`` is expected to be
    called from the same control loop that called ``submit`` (reap phase);
    there is no cross-thread signalling."""

    def __init__(self, t0: float, finishes: tuple, resolve):
        self.t0 = t0
        self.finishes = finishes
        self._resolve = resolve            # () -> CompletionReport
        self._report: CompletionReport | None = None

    @property
    def finish(self) -> float:
        """Simulated completion time of the batch's last request."""
        return max(self.finishes) if self.finishes else self.t0

    def done(self) -> bool:
        """True once ``result()`` has materialized the report."""
        return self._report is not None

    def ready(self) -> bool:
        """True when ``result()`` can deliver without waiting on an
        unresponsive peer. Local substrates are always ready (a pallas
        ``result()`` blocks, but only on finite device work); the cluster
        future reports False until its worker answers or is declared
        lost — the Engine's reap defers not-ready batches to a later
        cycle instead of hanging the control loop on a dead host."""
        return True

    def result(self) -> CompletionReport:
        """Block until execution finishes; idempotent."""
        if self._report is None:
            self._report = self._resolve()
        return self._report

    @classmethod
    def resolved(cls, report: CompletionReport) -> "BackendFuture":
        """An already-completed future (the sync-execute adapter)."""
        fut = cls(report.t0, report.finishes, lambda: report)
        fut._report = report
        return fut


class ExecutionBackend:
    """Protocol base. Subclasses override ``prepare`` plus either
    ``execute`` (synchronous substrates — ``submit`` wraps it in a resolved
    future) or both ``submit``/``execute`` (substrates with genuinely
    asynchronous dispatch, e.g. the Pallas backend's device-async path).

    Threading model: backends are driven by one host control loop;
    ``submit`` and ``result`` are never called concurrently from different
    threads. All simulated times are seconds.

    ``measured_sim_clock`` declares which clock the backend's
    ``measured_stage_times`` live on. True (analytic, replay): simulated
    seconds, directly comparable to the schedule's stage estimates — safe
    to judge against a StragglerMonitor baselined on them. False (pallas):
    real wall seconds, on a different scale from the model baselines *and*
    — on the async submit path — contaminated by whatever host work ran
    between submit and reap; consumers must not feed them RAW to model-
    baselined monitors (they remain useful as telemetry, and a
    ``WallClockCalibrator`` makes them monitor-grade)."""
    name = "abstract"
    measured_sim_clock = True

    def prepare(self, schedule: ScheduleResult, workload: Workload, *,
                epoch: int = 0) -> PipelineHandle:
        raise NotImplementedError

    def execute(self, handle: PipelineHandle, batch,
                t0: float) -> CompletionReport:
        raise NotImplementedError

    def submit(self, handle: PipelineHandle, batch,
               t0: float) -> BackendFuture:
        """Non-blocking dispatch; default adapter runs the synchronous
        ``execute`` eagerly and returns an already-resolved future."""
        return BackendFuture.resolved(self.execute(handle, batch, t0))


def _analytic_report(schedule: ScheduleResult, n: int, t0: float,
                     *, wall: float = 0.0) -> CompletionReport:
    stages = schedule.pipeline.stages
    fill = pipeline_fill(schedule)
    period = schedule.pipeline.period
    finishes = tuple(t0 + fill + i * period for i in range(n))
    est = tuple(s.total for s in stages)
    return CompletionReport(t0, finishes, schedule.energy, est, wall=wall,
                            measured_stage_times=est)


class AnalyticBackend(ExecutionBackend):
    """Closed-form pipeline model: no resident state, instant 'execution'.
    Measured stage times are synthesized as the schedule estimates (a
    healthy pipeline by construction — the straggler monitors see exactly
    their baselines)."""
    name = "analytic"

    def prepare(self, schedule, workload, *, epoch: int = 0) -> PipelineHandle:
        return PipelineHandle(schedule, workload, epoch=epoch,
                              backend=self.name)

    def execute(self, handle, batch, t0: float) -> CompletionReport:
        return _analytic_report(handle.schedule, batch_size(batch), t0)


# ---------------------------------------------------------------------------
# real execution: the shard_map pipeline
# ---------------------------------------------------------------------------
#: device programs that indexing an array sharded over a mesh by one
#: integer enqueues outside a jit (jax 0.9; tests/test_obs_spans.py)
SHARDED_INDEX_PROGRAMS = 4

class PallasPipelineBackend(ExecutionBackend):
    """Runs batches through the shard_map pipeline executors in
    ``runtime.pipeline_exec``.

    Each schedule stage becomes one pipeline stage function applying a proxy
    of its kernel group (spmm -> neighbor-aggregate + matmul, gemm ->
    matmul, win_attn -> windowed mix + matmul) on a shape-homogeneous
    (act_batch, act_dim) activation — the executor requires one static
    activation shape across stage boundaries. On a mesh the schedule lowers
    to ``GroupedPipelineExecutor``: one mesh axis of sum(Stage.n) devices,
    each stage owning a contiguous slice sized by the DP's per-stage device
    count, activations handed over at group boundaries.

    ``mode``:
      * "mesh"  — a (sum of DP stage counts,) jax mesh; raises when fewer
                  devices are visible
      * "chain" — the same stage chain as per-stage jits, sequential on
                  the default device, each stage's weight sliced once at
                  ``prepare``: one device program a stage
      * "auto"  — mesh when enough devices are visible, else chain

    ``output_platforms`` counts executed batches by the platform of the
    device their output landed on ("tpu", "cpu", ...). ``launches`` counts
    the device programs ``dispatch`` enqueues; ``tracer`` (handed on by
    the Engine) times the microbatch build, ``dispatch`` and the resolve.

    Measured stage times are real wall seconds (``measured_sim_clock`` is
    False): they are NOT comparable to the schedule's simulated-seconds
    baselines, and on the async path stage 0 additionally absorbs any host
    work (DP solves, other cells' jit compiles) that ran between submit
    and reap — so raw they feed ServingMetrics telemetry only. With a
    ``WallClockCalibrator`` (``Router(calibrator=...)``) the Router
    rescales them per (cell, stage) onto the simulated clock and they
    drive straggler demotion too (docs/heterogeneity.md).
    """
    name = "pallas"
    measured_sim_clock = False

    def __init__(self, *, act_batch: int = 8, act_dim: int = 16,
                 max_micro: int = 8, mode: str = "auto"):
        assert mode in ("auto", "mesh", "chain"), mode
        self.act_batch = act_batch
        self.act_dim = act_dim
        self.max_micro = max_micro
        self.mode = mode
        self.output_platforms: collections.Counter = collections.Counter()
        # (mode, payload) by (stage kinds, group sizes): a pure function of
        # the stage structure, so cell evictions/readmissions don't pay
        # the jit cost twice
        self.prepared: dict = {}
        self.launches = 0
        self.tracer = NULL_TRACER

    # -- stage lowering ------------------------------------------------------
    @staticmethod
    def stage_name(kinds) -> str:
        """Name of a chain-mode stage program, from its kernel kinds with
        runs counted: ("spmm", "gemm") -> "stage_spmm_gemm", ("gemm",
        "gemm") -> "stage_gemm2"."""
        parts: list[list] = []
        for kind in kinds:
            if parts and parts[-1][0] == kind:
                parts[-1][1] += 1
            else:
                parts.append([kind, 1])
        return "stage_" + "_".join(k if n == 1 else f"{k}{n}"
                                   for k, n in parts)

    def _stage_fn(self, kinds):
        import jax
        import jax.numpy as jnp

        def fn(p, x):
            for kind in kinds:
                if kind == "spmm":
                    # neighbor aggregation proxy: row shift + feature mix
                    x = x @ p["w"] + 0.5 * jnp.roll(x, 1, axis=0)
                elif kind == "win_attn":
                    # windowed mixing proxy along the feature axis
                    x = x @ p["w"] + 0.5 * jnp.roll(x, 1, axis=1)
                else:                      # gemm
                    x = x @ p["w"]
                x = jax.nn.tanh(x)         # bounded through deep chains
            return x
        return fn

    def stage_weights(self, n_stages: int):
        """The stacked (n_stages, act_dim, act_dim) f32 stage weights: a
        scaled identity + deterministic off-diagonal per stage, so stage
        order matters (parity/permutations are observable)."""
        import jax.numpy as jnp

        eye = jnp.eye(self.act_dim, dtype=jnp.float32)
        return jnp.stack([
            (0.8 + 0.02 * s) * eye
            + 0.01 * jnp.roll(eye, s + 1, axis=1)
            for s in range(n_stages)])

    def prepare(self, schedule, workload, *, epoch: int = 0) -> PipelineHandle:
        import jax
        import numpy as np

        stages = schedule.pipeline.stages
        n_stages = len(stages)
        group_sizes = tuple(s.n for s in stages)   # the DP's device counts
        F = self.act_dim
        stage_kinds = tuple(tuple(workload[i].kind
                                  for i in range(s.i0, s.i1))
                            for s in stages)
        cache_key = (stage_kinds, group_sizes)
        cached = self.prepared.get(cache_key)
        if cached is not None:
            mode, payload = cached
            return PipelineHandle(schedule, workload, epoch=epoch,
                                  backend=self.name, payload=payload,
                                  mode=mode)
        fns = [self._stage_fn(kinds) for kinds in stage_kinds]
        ws = self.stage_weights(n_stages)
        params = {"w": ws}

        n_dev = sum(group_sizes)
        devices = jax.devices()
        if self.mode == "mesh" and len(devices) < n_dev:
            raise RuntimeError(
                f"mode='mesh' needs {n_dev} devices for stage groups "
                f"{group_sizes}, but only {len(devices)} are visible")
        if self.mode == "mesh" or (self.mode == "auto" and n_stages > 1
                                   and len(devices) >= n_dev):
            from .pipeline_exec import GroupedPipelineExecutor
            # a plain Mesh has Auto axes: the executor's host-side gather
            # of the last head's slice is refused under Explicit ones
            mesh = jax.sharding.Mesh(np.asarray(devices[:n_dev]),
                                     ("stage",))
            mode = "mesh"
            payload = GroupedPipelineExecutor(mesh, "stage", fns, params,
                                              (self.act_batch, F),
                                              group_sizes)
        else:
            # the same stage chain, sequential on one device — identical
            # math to the executor's per-microbatch path, but jitted per
            # stage so the stage loop can be timed stage by stage (the
            # measured times the straggler monitors consume)
            def stage_apply(fn, kinds):
                def apply(w, micro):
                    return jax.vmap(lambda x: fn({"w": w}, x))(micro)
                apply.__name__ = self.stage_name(kinds)
                return jax.jit(apply)

            mode = "chain"
            # one (F, F) weight a stage, sliced here once: indexing the
            # stacked array in dispatch would enqueue two programs a stage
            # on every batch
            payload = (tuple(stage_apply(f, k)
                             for f, k in zip(fns, stage_kinds)),
                       {"w": tuple(ws)})
        self.prepared[cache_key] = (mode, payload)
        return PipelineHandle(schedule, workload, epoch=epoch,
                              backend=self.name, payload=payload, mode=mode)

    def microbatches(self, n_micro: int):
        """Deterministic microbatch content (replayable, seedless)."""
        import jax.numpy as jnp
        import numpy as np

        m = max(1, min(n_micro, self.max_micro))
        return jnp.asarray(
            np.linspace(-1.0, 1.0,
                        m * self.act_batch * self.act_dim,
                        dtype=np.float32)
            .reshape(m, self.act_batch, self.act_dim))

    def dispatch(self, handle, micro) -> tuple:
        """Enqueue ``micro`` (m, act_batch, act_dim) through the handle's
        stages without blocking. Returns the device arrays to wait on, in
        completion order; the last is the pipeline output (m, B, F)."""
        with self.tracer.span("backend", "backend.dispatch", 0.0):
            if handle.mode == "mesh":
                # the pipeline program, then the head's slice of its output
                self.launches += 1 + SHARDED_INDEX_PROGRAMS
                return (handle.payload(micro),)
            stage_jits, params = handle.payload
            outs = []
            x = micro
            for sj, w in zip(stage_jits, params["w"]):
                x = sj(w, x)
                outs.append(x)
            # one program a stage: its jit, on the weight sliced at prepare
            self.launches += len(stage_jits)
            return tuple(outs)

    def submit(self, handle, batch, t0: float) -> BackendFuture:
        """Dispatch the batch to the device WITHOUT blocking (jax dispatch
        is asynchronous) and return a future. Completion *times* still come
        from the schedule model — the simulated clock is shared with every
        other backend (and with admission control), which is exactly what
        makes analytic/pallas ordering parity hold — so they are available
        immediately; ``result()`` blocks on the device and fills in the
        measured wall/stage seconds.

        Measured per-stage times: in chain mode each stage is a separate
        jit call, so blocking on the successive stage outputs in order
        timestamps each stage's real completion (the device executes them
        in dispatch order). In mesh mode the whole pipeline is one
        shard_map program, so the measured wall is apportioned over stages
        by the schedule's stage weights — total is measured, the split is
        modeled."""
        n = batch_size(batch)
        base = _analytic_report(handle.schedule, n, t0)
        tracer = self.tracer
        # host-side input build and copy: not in the measured stage times
        with tracer.span("backend", "backend.microbatches", t0):
            micro = self.microbatches(n)
        w0 = time.perf_counter()
        outs = self.dispatch(handle, micro)

        def resolve():
            with tracer.span("backend", "backend.resolve", t0):
                meas, prev = [], w0
                for o in outs:             # device runs stages in order
                    o.block_until_ready()
                    now = time.perf_counter()
                    meas.append(now - prev)
                    prev = now
                self.output_platforms.update(
                    {d.platform for d in outs[-1].devices()})
            wall = prev - w0
            if handle.mode == "mesh":
                est = base.stage_times
                tot = sum(est) or 1.0
                meas = [wall * e / tot for e in est]
            return dataclasses.replace(
                base, wall=wall, measured_stage_times=tuple(meas))
        return BackendFuture(t0, base.finishes, resolve)

    def execute(self, handle, batch, t0: float) -> CompletionReport:
        return self.submit(handle, batch, t0).result()


# ---------------------------------------------------------------------------
# trace capture + replay
# ---------------------------------------------------------------------------
def _trace_key(schedule: ScheduleResult) -> str:
    """Identity of a schedule for trace purposes. The mnemonic alone is NOT
    enough — two schedules can share one (e.g. "1G1G") with very different
    stage baselines — so the key also pins the kernel spans and the period."""
    spans = ",".join(f"{s.i0}-{s.i1}x{s.n}{s.dev.name[0]}"
                     for s in schedule.pipeline.stages)
    return (f"{schedule.mnemonic}|{schedule.mode}|{spans}"
            f"|{schedule.pipeline.period:.9e}")


class TraceRecorder(ExecutionBackend):
    """Wraps any backend; records per-schedule timing traces suitable for
    ``ReplayBackend``. One trace per distinct (mnemonic, mode, n_stages).
    ``stage_times`` in the trace are the inner backend's *measured*
    per-stage seconds (``CompletionReport.measured``) when those live on
    the simulated clock, so replaying reproduces the observed stage
    behavior — including any straggling stage — not the DP estimates.
    For a wall-clock inner backend (pallas) the schedule-model stage times
    are recorded instead: its measurements are on the wrong scale for a
    trace whose fill/period are simulated seconds, and the first report
    per schedule is jit-compile-dominated — replaying either would inject
    phantom stragglers."""

    def __init__(self, inner: ExecutionBackend):
        self.inner = inner
        self.name = f"record({inner.name})"
        self.traces: dict[str, dict] = {}

    @property
    def measured_sim_clock(self) -> bool:
        return self.inner.measured_sim_clock

    def prepare(self, schedule, workload, *, epoch: int = 0) -> PipelineHandle:
        return self.inner.prepare(schedule, workload, epoch=epoch)

    def _record(self, handle, rep: CompletionReport) -> CompletionReport:
        key = _trace_key(handle.schedule)
        if key not in self.traces:
            period = (rep.finishes[1] - rep.finishes[0]
                      if len(rep.finishes) > 1
                      else handle.schedule.pipeline.period)
            self.traces[key] = {
                "fill": rep.finishes[0] - rep.t0 if rep.finishes else 0.0,
                "period": period,
                "energy": rep.energy_per_req,
                "stage_times": list(rep.measured if self.measured_sim_clock
                                    else rep.stage_times),
            }
        return rep

    def submit(self, handle, batch, t0: float) -> BackendFuture:
        fut = self.inner.submit(handle, batch, t0)
        return BackendFuture(fut.t0, fut.finishes,
                             lambda: self._record(handle, fut.result()))

    def execute(self, handle, batch, t0: float) -> CompletionReport:
        return self.submit(handle, batch, t0).result()

    def to_replay(self) -> "ReplayBackend":
        return ReplayBackend(dict(self.traces))

    def to_jsonl(self, path) -> None:
        with open(path, "w") as f:
            for key, tr in sorted(self.traces.items()):
                f.write(json.dumps({"key": key, **tr}) + "\n")


class ReplayBackend(ExecutionBackend):
    """Deterministic execution timings from recorded traces: each schedule's
    fill/period/energy/stage-times come from the trace instead of the model.
    Trace ``stage_times`` are replayed as the report's *measured* per-stage
    seconds, so a trace recorded on straggling hardware (or edited to
    inject a slow stage) drives the straggler monitors exactly like a live
    measurement. Missing schedules fall back to the analytic model when
    ``strict`` is False (default), else raise KeyError."""
    name = "replay"

    def __init__(self, traces: dict, *, strict: bool = False):
        self.traces = traces
        self.strict = strict

    @classmethod
    def from_jsonl(cls, path, *, strict: bool = False) -> "ReplayBackend":
        traces = {}
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                rec = json.loads(line)
                traces[rec.pop("key")] = rec
        return cls(traces, strict=strict)

    def prepare(self, schedule, workload, *, epoch: int = 0) -> PipelineHandle:
        return PipelineHandle(schedule, workload, epoch=epoch,
                              backend=self.name,
                              payload=self.traces.get(_trace_key(schedule)))

    def execute(self, handle, batch, t0: float) -> CompletionReport:
        n = batch_size(batch)
        tr = handle.payload
        if tr is None:
            if self.strict:
                raise KeyError(f"no trace for {_trace_key(handle.schedule)}")
            return _analytic_report(handle.schedule, n, t0)
        finishes = tuple(t0 + tr["fill"] + i * tr["period"] for i in range(n))
        recorded = tuple(tr["stage_times"])
        return CompletionReport(t0, finishes, tr["energy"], recorded,
                                measured_stage_times=recorded)


# ---------------------------------------------------------------------------
# multi-host execution: route handles to cluster workers
# ---------------------------------------------------------------------------
class _ClusterFuture(BackendFuture):
    """Future for a batch executing on a remote worker. ``ready`` gates
    the Engine's reap: False while the submission is unanswered and its
    worker not yet declared lost — the failure detector (heartbeat
    timeout, or an RPC fallback on the blocking path) decides its fate,
    never a hang in the reap loop."""

    def __init__(self, controller, sid: int, t0: float, finishes: tuple):
        super().__init__(t0, finishes, lambda: controller.resolve(sid))
        self._controller = controller
        self._sid = sid

    def ready(self) -> bool:
        return self.done() or self._controller.ready(self._sid, self.finish)


class ClusterBackend(ExecutionBackend):
    """Executes every batch on a ``repro.cluster`` worker peer.

    ``prepare`` asks the controller to *place* the cell — pick an owning
    worker (sub-pool-fit first, then deterministic round-robin) — and the
    worker prepares its local backend's handle; the returned
    ``PipelineHandle.payload`` is just ``(worker_id, remote_handle_id)``.
    ``submit`` routes the batch to that worker and returns a future whose
    simulated finishes come from the worker's report (the same schedule
    model every backend uses, which is what makes cluster-vs-local
    completion ordering identical). A worker death surfaces as
    ``WorkerLost`` at resolution — see ``cluster/controller.py`` for the
    detection story.

    Not in ``BACKENDS``: it needs a live controller, so entry points build
    it via ``cluster.LocalCluster`` rather than ``make_backend``."""
    name = "cluster"

    def __init__(self, controller):
        self.controller = controller

    @property
    def measured_sim_clock(self) -> bool:
        return self.controller.measured_sim_clock

    def prepare(self, schedule, workload, *, epoch: int = 0) -> PipelineHandle:
        # the controller may deploy a *host-adjusted* schedule (the owning
        # worker's physics, possibly a different stage split) — the handle
        # carries that one, so the Engine's busy clocks and straggler
        # baselines see the same truth the worker will report against
        wid, hid, deployed = self.controller.prepare(schedule, workload,
                                                     epoch)
        return PipelineHandle(deployed, workload, epoch=epoch,
                              backend=self.name, payload=(wid, hid))

    def submit(self, handle, batch, t0: float) -> BackendFuture:
        wid, hid = handle.payload
        sid, finishes = self.controller.submit(wid, hid, handle.schedule,
                                               batch_size(batch), t0)
        fut = _ClusterFuture(self.controller, sid, t0, finishes)
        # the *executing* host — replica routing and stealing both
        # already applied; the Engine advances that replica's clock
        fut.worker = self.controller.worker_of(sid)
        return fut

    @property
    def handles_migration(self) -> bool:
        """True when a learned-profile publication is absorbed by live
        migration (drain-to-replica -> retire) — the Router then skips
        the engine-wide invalidation it would otherwise perform."""
        return bool(getattr(self.controller, "migrate", False))

    def cancel(self, future, now: float) -> bool:
        """Preemption hook (``Engine.preempt``): withdraw an in-flight
        submission from its worker before it reports. Refused (False)
        once the report already arrived or the worker died — the caller
        must then leave the batch alone and reap it normally."""
        if future.done():
            return False
        return self.controller.cancel(future._sid, now)

    def est_wait_bound(self, handle, now: float, est: float) -> float:
        """Steal-aware admission bound (Engine.est_wait hook): the wait
        behind this cell's busy owner collapses to zero when the
        controller would migrate the next pending batch to a dry,
        strictly-faster peer — judged on the *current* (declared or
        learned) host profiles."""
        wid, hid = handle.payload
        return self.controller.steal_wait_bound(wid, hid, now, est)

    def execute(self, handle, batch, t0: float) -> CompletionReport:
        return self.submit(handle, batch, t0).result()


BACKENDS = {
    "analytic": AnalyticBackend,
    "pallas": PallasPipelineBackend,
}


def make_backend(name: str, **kw) -> ExecutionBackend:
    """Factory for CLI entry points (``--backend analytic|pallas``)."""
    try:
        return BACKENDS[name](**kw)
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; choose from {sorted(BACKENDS)}")
