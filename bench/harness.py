"""One run of one benchmark cell: set-up, a measured window on the wall clock,
the check, and the result line.

Set-up (timed as ``setup_s``, from process start) turns on the persistent
compile cache, builds the stack from the cell's configuration and its
plug-in (``stack.py``), and serves the cell's own seeded stream for
``warmup_sim_s`` simulated seconds, which prepares and warms every stage
structure that traffic produces.

The window continues the same stream, tick by tick on the simulated clock,
through ``Router.submit`` and ``Router.step``, as fast as the stack runs,
for ``--seconds`` of wall time. A request's wall latency runs from the
harness's submit to the return of the ``step`` that completed it. Modelled
waits cost no wall time, so this is the delay that the real stack adds; a
generator on a simulated clock cannot run late. After the window the stack
is drained (not timed) and checked (``check.py`` and the plug-in).

A traced run (``--trace 1``) serves with ``repro.obs.Tracer(profile=True)``
in the stack, under the profiler, and hands its readers a ``Window`` that
also carries the program's spans and counters, the device time of every
program and what was dispatched; an untraced run builds no tracer.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

from . import arrivals, check, files, program_spans, stack, xtrace

BENCH = files.BENCH
ROOT = files.ROOT


def process_start_wall() -> float:
    """Wall-clock time (``time.time``) at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return time.time()


class CompileCount:
    """Programs compiled or read from the persistent cache, from JAX's
    monitoring events. Registered once per process."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    _instance = None

    def __init__(self):
        self.n = 0

    @classmethod
    def get(cls) -> "CompileCount":
        if cls._instance is None:
            import jax.monitoring as mon
            cls._instance = cls()
            mon.register_event_duration_secs_listener(cls._instance._on)
        return cls._instance

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


@dataclasses.dataclass
class Window:
    """What the metric readers read (``metrics/<name>.py``). The fields
    after ``reschedules`` are filled in traced runs only, and are None in
    the others."""
    seconds: float                 # wall length of the window
    setup_s: float
    completed: int                 # requests completed in the window
    latencies_s: list              # their wall latencies
    batches: int                   # batches dispatched in the window
    place_s: list                  # placement walls of those batches
    reschedules: int               # DynamicScheduler events in the window
    busy_s: float | None = None    # device busy seconds in the trace
    window_s: float | None = None  # traced window length, seconds
    # program span name -> (seconds, count, self seconds) in the traced
    # window (program_spans.py)
    span_totals: dict | None = None
    # backend counter -> its change over the window (stack.Recorder)
    counters: dict | None = None
    # wall seconds from Router.submit to the first dispatch, per request
    # first dispatched in the window
    queue_wait_s: list | None = None
    # device program -> (device seconds, calls) in the traced window
    device_programs: dict | None = None
    device_kind: str | None = None     # as JAX names it; peaks.py's key
    # (workload, stage kinds, inputs) of each batch dispatched in the window
    dispatched: list | None = None
    # the plug-in's work(workload, stage kinds, m) -> (flops, bytes), or
    # None where it has none
    work: object = None


class Driver:
    """Feeds the stream into the router one tick at a time."""

    def __init__(self, router, stream, cfg, annotate: bool = False):
        self.router = router
        self.stream = stream
        self.cfg = cfg
        self.annotate = annotate
        self.wls: dict = {}
        self.rid = 0
        self.submitted_at: dict = {}   # rid -> perf_counter at submit
        self.completed: list = []      # rids, in completion order

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(xtrace.HOST_PREFIX + name)

    def _wl(self, name: str):
        wl = self.wls.get(name)
        if wl is None:
            wl = self.wls[name] = stack.workload(name, self.cfg)
        return wl

    def tick(self) -> tuple[list, float]:
        """Submit one tick's arrivals, step to its end; returns the wall
        latencies of the requests completed and the wall time after."""
        from repro.serving import Request

        with self._span("submit"):
            now = time.perf_counter()
            for a in self.stream.next_tick():
                req = Request(self.rid, self._wl(a.name), a.t,
                              deadline=a.deadline, kind=a.kind,
                              tenant=a.tenant)
                self.submitted_at[self.rid] = now
                self.rid += 1
                self.router.submit(req, a.t)
        with self._span("step"):
            done = self.router.step(self.stream.t)
        w = time.perf_counter()
        lat = []
        for r in done:
            self.completed.append(r.rid)
            lat.append(w - self.submitted_at.pop(r.rid))
        return lat, w

    def serve_until(self, sim_t: float) -> None:
        while self.stream.t < sim_t:
            self.tick()

    def drain(self) -> None:
        for r in self.router.drain(self.stream.t):
            self.completed.append(r.rid)
            self.submitted_at.pop(r.rid, None)


def _annotate_layers(router, backend, span) -> None:
    """Traced runs: spans around the calls into the Engine and the backend,
    so that the trace can say what the host did in each device idle gap."""
    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            with span(name):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    wrap(router.engine, "submit", "engine.submit")
    wrap(router.engine, "reap", "engine.reap")
    wrap(backend, "dispatch", "backend.dispatch")
    submit = backend.submit

    def backend_submit(*a, **kw):
        fut = submit(*a, **kw)
        resolve = fut._resolve

        def annotated():
            with span("backend.resolve"):
                return resolve()
        fut._resolve = annotated
        return fut
    backend.submit = backend_submit


def load_benchmark() -> dict:
    return json.loads(files.BENCHMARK.read_text())


def _reader(name: str):
    return files.load("metrics", name).read


def metrics_for(bench: dict, cell: str, trace: bool, w: Window) -> dict:
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced), each from its reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if cell not in m.get("workloads", [cell]):
            continue
        v = _reader(m["name"])(w)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Served:
    """A cell's stack after its measured window and the drain."""
    cfg: dict
    router: object
    recorder: stack.Recorder
    driver: Driver
    window: Window
    attempted: int                 # requests submitted in the window
    failed: int                    # of those, not completed
    trace_dir: str | None


def serve_window(workload: str, seed: int, seconds: float, *,
                 trace: bool = False, profile: bool | None = None,
                 t_proc: float | None = None, log=print) -> Served:
    """Set-up, the measured window and the drain of one run of cell
    ``workload``: builds the stack, serves the warm-up, serves the window
    for ``seconds`` of wall (under the profiler with ``trace``), drains.
    ``profile`` (default ``trace``) puts ``Tracer(profile=True)`` in the
    stack and notes the window's counters, queue waits and dispatches.
    ``setup_s`` runs from ``t_proc`` (``time.time``), default now."""
    import jax

    from repro.obs import Tracer

    t_proc = time.time() if t_proc is None else t_proc
    profile = trace if profile is None else profile
    cell = next(c for c in load_benchmark()["workloads"]
                if c["name"] == workload)
    compiles = CompileCount.get()
    cfg = stack.load_config(cell["config"])
    spec = arrivals.load_traffic(cell["traffic"])
    t_begin = time.time() - t_proc
    router, rec = stack.build(cfg, spec["provisioned_rate"],
                              tracer=Tracer(profile=True) if profile
                              else None)
    t_built = time.time() - t_proc
    stream = arrivals.Stream(spec, seed)
    driver = Driver(router, stream, cfg)
    driver.serve_until(spec["warmup_sim_s"])
    log(f"set-up: devices and imports by {t_begin:.3f} s, stack built at "
        f"{t_built:.3f} s, warm-up served by {time.time() - t_proc:.3f} s; "
        f"{rec.structures} stage structures prepared, "
        f"{compiles.n} programs built")

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        driver.annotate = True
        _annotate_layers(router, rec.backend, driver._span)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the harness's spans, not every call
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n_disp0 = len(router.dispatches)
    n_place0 = len(router.metrics.place_s)
    n_ev0 = len(router.dyn.events)
    n_pre0 = router.metrics.preemptions
    n_wait0 = len(router.metrics.queue_wait_s)
    counts0 = rec.counts()
    n_comp0 = compiles.n
    rid0 = driver.rid
    rec.record = True
    w0 = time.perf_counter()
    setup_s = time.time() - t_proc
    end = w0 + seconds
    lat: list = []
    w = w0
    while w < end:
        got, w = driver.tick()
        lat.extend(got)
    rec.record = False
    window = Window(
        seconds=w - w0, setup_s=setup_s, completed=len(lat),
        latencies_s=lat, batches=len(router.dispatches) - n_disp0,
        place_s=router.metrics.place_s[n_place0:],
        reschedules=len(router.dyn.events) - n_ev0)
    if profile:
        window.counters = {k: v - counts0[k]
                           for k, v in rec.counts().items()}
        window.queue_wait_s = router.metrics.queue_wait_s[n_wait0:]
        window.dispatched = [(r.workload, r.kinds, r.m)
                             for r in rec.records]
    n_compiles = compiles.n - n_comp0
    rid1 = driver.rid
    if trace:
        jax.profiler.stop_trace()
    place = sum(window.place_s) / max(1, len(window.place_s)) * 1e3
    log(f"window: {window.seconds:.3f} s wall, sim {stream.t:.2f} s, "
        f"{window.completed} completed, {window.batches} batches, "
        f"{rid1 - rid0} submitted, "
        f"{router.metrics.preemptions - n_pre0} preemptions, "
        f"{window.reschedules} reschedules, {n_compiles} programs built, "
        f"{rec.structures_in_window} new stage structures, "
        f"mean placement {place!r} ms"
        f"{' (under the profiler)' if trace else ''}")

    driver.drain()
    done = set(driver.completed)
    failed = (rid1 - rid0) - sum(1 for r in range(rid0, rid1) if r in done)
    return Served(cfg, router, rec, driver, window, rid1 - rid0, failed,
                  trace_dir)


def read_trace(served: Served, device_kind: str, log=print):
    """Reads a traced run's trace into its ``Window`` (device busy and
    window seconds, program span totals, device time by program, the
    device kind, the plug-in's ``work``), deletes the trace, and returns it
    as an ``xtrace.Trace``."""
    path = xtrace.find(served.trace_dir)
    tr = xtrace.read(path)
    log(f"trace: {os.path.getsize(path)} bytes, "
        f"{sum(len(v) for v in tr.ops.values())} device ops, "
        f"{len(tr.spans)} harness spans, {len(tr.program)} program spans")
    shutil.rmtree(served.trace_dir, ignore_errors=True)
    w = served.window
    t0, t1 = tr.window
    w.window_s = (t1 - t0) * 1e-9
    w.busy_s = xtrace.busy_ns(tr) * 1e-9
    totals = program_spans.program_span_totals(tr.program, tr.window)
    own = program_spans.span_self_seconds(tr.program, tr.window)
    w.span_totals = {n: (sec, cnt, own.get(n, 0.0))
                     for n, (cnt, sec) in totals.items()}
    w.device_programs = xtrace.program_times(tr)
    w.device_kind = device_kind
    w.work = getattr(served.recorder.plugin, "work", None)
    return tr


def numbers(served: Served, platform: str) -> dict:
    """The numbers ``check.py`` and the plug-in compare, for a drained run,
    and those printed only (names that start with ``_``)."""
    router, rec = served.router, served.recorder
    left = len(router.queue) + len(router.engine.inflight)
    out = check.accounting(served.driver.rid, served.driver.completed,
                           router.metrics.dropped, left)
    out.update(check.recorded(rec.records, platform, served.window.batches))
    out.update(rec.plugin.numbers(rec.records, platform))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             log=print) -> dict:
    """One run of cell ``workload``; returns the result object."""
    import jax

    from repro.launch.compile_cache import enable as enable_compile_cache

    t_proc = process_start_wall()
    devices = jax.devices()
    platform = devices[0].platform
    log(f"compile cache {enable_compile_cache()}")
    served = serve_window(workload, seed, seconds, trace=trace,
                          t_proc=t_proc, log=log)
    window = served.window
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    nums = numbers(served, platform)
    log(f"checked {len(served.recorder.records)} batch outputs by "
        f"(stages, m): {nums.pop('_by_shape')}; "
        + "; ".join(f"{k[1:]} {nums.pop(k)!r}"
                    for k in [k for k in nums if k.startswith("_")]))
    correct, checks = check.verdict(nums, served.recorder.plugin.LIMITS)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        tr = read_trace(served, devices[0].device_kind, log=log)
        device.update(busy_s=window.busy_s, window_s=window.window_s)
        breakdown = {"device_ops": xtrace.top_programs(tr),
                     "idle_gaps": xtrace.idle_gaps(tr)}
    out = {"correct": correct, "attempted": served.attempted,
           "failed": served.failed,
           "metrics": metrics_for(load_benchmark(), workload, trace, window),
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return out
