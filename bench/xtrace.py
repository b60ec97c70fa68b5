"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
the device operations that took most time, and the idle gaps on the device
named by what the host was doing in them.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, and the programs they belong to those of its
``XLA Modules`` line. Host spans are the harness's own
``jax.profiler.TraceAnnotation`` events on the ``/host:CPU`` plane, whose
names start with ``HOST_PREFIX``, and the program's own duration spans,
whose names start with ``PROGRAM_PREFIX`` (``Tracer(profile=True)``,
read by ``program_spans.py``). All are read on the profiler's one clock, in
nanoseconds. The traced window runs from the first harness span's start
to the last one's end.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

HOST_PREFIX = "bench:"
PROGRAM_PREFIX = "dype:"        # repro.obs.PROFILE_PREFIX
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: dict            # device plane -> [(name, start_ns, end_ns)]
    modules: dict        # device plane -> [(name, start_ns, end_ns)]
    spans: list          # [(name, start_ns, end_ns)] harness spans
    # [(name without the prefix, start_ns, end_ns)] program spans
    program: list = dataclasses.field(default_factory=list)

    @property
    def window(self) -> tuple[float, float]:
        return (min(s for _, s, _ in self.spans),
                max(e for _, _, e in self.spans))


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb under {trace_dir}")
    return paths[0]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans, program = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            n = len(PROGRAM_PREFIX)
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append((e.name[n:], e.start_ns,
                                        e.start_ns + e.duration_ns))
    return Trace(ops, modules, spans, program)


def merged(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of (start, end) intervals clipped to [t0, t1], in order."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace) -> float:
    """Busy time of the device, averaged over the device planes."""
    t0, t1 = trace.window
    if not trace.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in merged([(s, e) for _, s, e in evs],
                                           t0, t1))
              for evs in trace.ops.values())
    return tot / len(trace.ops)


def program_times(trace: Trace) -> dict:
    """{name: (seconds, calls)} of every device program (a jitted
    computation, named with its fingerprint) run in the window: its device
    time summed over its calls, both averaged over the devices."""
    t0, t1 = trace.window
    acc, calls = collections.Counter(), collections.Counter()
    for evs in trace.modules.values():
        for name, s, e in evs:
            s, e = max(s, t0), min(e, t1)
            if e > s:
                acc[name] += (e - s) * 1e-9 / len(trace.modules)
                calls[name] += 1
    return {n: (v, calls[n] / len(trace.modules))
            for n, v in acc.most_common()}


def top_programs(trace: Trace, k: int = 10) -> list:
    """[name, seconds] of the k device programs with most device time in
    the window (``program_times``)."""
    return [[n, v] for n, (v, _) in list(program_times(trace).items())[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """Idle time of the first device in the window, summed by the innermost
    harness span open at each gap's midpoint: [[span (gaps), seconds]] for
    the k spans with most idle time."""
    t0, t1 = trace.window
    evs = next(iter(trace.ops.values()), [])
    busy = merged([(s, e) for _, s, e in evs], t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    spans = sorted(trace.spans, key=lambda x: x[1])
    acc, cnt = collections.Counter(), collections.Counter()
    active, i = [], 0                  # a sweep over the sorted midpoints
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        t = 0.5 * (s + e)
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] > t]
        name = (min(active, key=lambda sp: sp[2] - sp[1])[0]
                [len(HOST_PREFIX):] if active else "none")
        acc[name] += (e - s) * 1e-9
        cnt[name] += 1
    return [[f"{n} ({cnt[n]} gaps)", v] for n, v in acc.most_common(k)]
