#!/usr/bin/env python3
"""The program's own duration spans in a profiler trace, and the per-layer
numbers they give.

``repro.obs.Tracer(profile=True)`` opens a ``jax.profiler.TraceAnnotation``
named ``dype:<span>`` around each duration span of Router, Engine, the DP
and the pallas backend, so the spans sit on the profiler's host plane on
the same clock as the device ops. This module reads them (``read``; a
traced run's ``xtrace.read`` reads them too), sums them over the traced
window (``program_span_totals``, ``span_self_seconds``), names each device
idle gap by the innermost one open over it (``idle_by_program_span``, the
sweep of ``xtrace.idle_gaps`` over these spans) and turns the totals into
per-layer numbers (``layer_metrics``; ``window_metric`` for a reader of a
traced ``harness.Window``).

    python3 bench/program_spans.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

runs one window of the cell as ``bench/run.py`` does, with a profile tracer
in the stack; with ``--trace 1`` under the profiler, as a traced run. The
last line of standard output is one JSON object: ``correct``, the cell's
metrics as its readers give them, and with ``--trace 1`` the numbers of
``layer_metrics``, ``idle_by_program_span`` and the span totals. Exits with
2 unless JAX's devices are TPUs, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import sys
from pathlib import Path


def read(path: str) -> list:
    """[(span name without the prefix, start_ns, end_ns)] of the program's
    spans on the ``/host`` planes of the trace at ``path``."""
    from bench import xtrace

    return xtrace.read(path).program


def program_span_totals(spans: list, window) -> dict:
    """{name: (count, seconds)} of the spans that overlap ``window``
    (t0_ns, t1_ns), their time clipped to it."""
    t0, t1 = window
    acc: dict = {}
    for name, s, e in spans:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            n, sec = acc.get(name, (0, 0.0))
            acc[name] = (n + 1, sec + (e - s) * 1e-9)
    return acc


def span_self_seconds(spans: list, window) -> dict:
    """{name: seconds} of the spans' time inside ``window`` (t0_ns, t1_ns)
    less that of the spans directly inside them, summed by name. The spans
    nest: one host thread opens them as context managers."""
    t0, t1 = window
    acc: dict = collections.Counter()
    open_: list = []                   # (end_ns, name), innermost last
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while open_ and open_[-1][0] <= s:
            open_.pop()
        d = max(0, min(e, t1) - max(s, t0))
        acc[name] += d
        if open_:
            acc[open_[-1][1]] -= d
        open_.append((e, name))
    return {n: v * 1e-9 for n, v in acc.items()}


def idle_by_program_span(trace, spans: list, k: int = 20) -> list:
    """Idle time of the first device in the window of ``trace`` (an
    ``xtrace.Trace``), summed by the innermost program span open at each
    gap's midpoint: [[span (gaps), seconds]] for the k spans with most
    idle time; "none" where no program span is open."""
    from bench import xtrace

    t0, t1 = trace.window
    evs = next(iter(trace.ops.values()), [])
    busy = xtrace.merged([(s, e) for _, s, e in evs], t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    spans = sorted(spans, key=lambda x: x[1])
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
                if active else "none")
        acc[name] += (e - s) * 1e-9
        cnt[name] += 1
    return [[f"{n} ({cnt[n]} gaps)", v] for n, v in acc.most_common(k)]


def _p95(xs) -> float | None:
    if not xs:
        return None
    s = sorted(xs)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def layer_metrics(totals: dict, batches: int, launches: int,
                  queue_wait_s: list) -> dict:
    """Per-layer numbers of one traced window, from the span totals, the
    batches dispatched, the backend's launches and the wall queue waits
    recorded in it; a number with nothing to read is left out:

    - ``batch_form_ms``: ``batcher.next_batch`` seconds per batch;
    - ``preempt_pass_ms``: ``router.preempt_pass`` seconds per
      ``router.step``;
    - ``admit_ms``: mean ``engine.admit`` (DP lookup or solve, evictions,
      ``backend.prepare``);
    - ``micro_build_ms``: ``backend.microbatches`` seconds per batch;
    - ``launch_us``: ``backend.dispatch`` seconds per device program
      launched;
    - ``launches_per_batch``: device programs launched per batch;
    - ``queue_wait_wall_p95_ms``: nearest-rank p95 of the wall wait from
      ``Router.submit`` to the first dispatch."""
    def sec(name):
        return totals.get(name, (0, 0.0))

    out = {}
    if batches:
        if "batcher.next_batch" in totals:
            out["batch_form_ms"] = sec("batcher.next_batch")[1] / batches * 1e3
        if "backend.microbatches" in totals:
            out["micro_build_ms"] = (sec("backend.microbatches")[1]
                                     / batches * 1e3)
        if launches:
            out["launches_per_batch"] = launches / batches
    steps = sec("router.step")[0]
    if "router.preempt_pass" in totals and steps:
        out["preempt_pass_ms"] = sec("router.preempt_pass")[1] / steps * 1e3
    n, s = sec("engine.admit")
    if n:
        out["admit_ms"] = s / n * 1e3
    if launches and "backend.dispatch" in totals:
        out["launch_us"] = sec("backend.dispatch")[1] / launches * 1e6
    p95 = _p95(queue_wait_s)
    if p95 is not None:
        out["queue_wait_wall_p95_ms"] = p95 * 1e3
    return out


def window_layer_metrics(w) -> dict:
    """``layer_metrics`` of a traced ``harness.Window``; {} untraced."""
    if w.span_totals is None:
        return {}
    totals = {n: (cnt, sec) for n, (sec, cnt, _) in w.span_totals.items()}
    return layer_metrics(totals, w.batches, w.counters.get("launches", 0),
                         w.queue_wait_s)


def window_metric(w, name: str):
    """``layer_metrics``' number ``name`` for a ``harness.Window``, or
    None where the window has nothing to read for it."""
    return window_layer_metrics(w).get(name)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        log=print) -> dict:
    """One window of cell ``workload`` with a profile tracer in the stack;
    the result object (see the module's doc)."""
    import jax

    from bench import check, harness, xtrace
    from repro.launch.compile_cache import enable as enable_compile_cache

    t_proc = harness.process_start_wall()
    devices = jax.devices()
    log(f"compile cache {enable_compile_cache()}")
    served = harness.serve_window(workload, seed, seconds, trace=trace,
                                  profile=True, t_proc=t_proc, log=log)
    window = served.window
    correct, checks = check.verdict(
        {k: v for k, v in harness.numbers(served, devices[0].platform).items()
         if not k.startswith("_")}, served.recorder.plugin.LIMITS)
    out = {"correct": correct, "attempted": served.attempted,
           "failed": served.failed}
    if trace:
        tr = harness.read_trace(served, devices[0].device_kind, log=log)
        out["program"] = window_layer_metrics(window)
        out["launches"] = window.counters.get("launches", 0)
        out["batches"] = window.batches
        out["idle_by_program_span"] = idle_by_program_span(tr, tr.program)
        out["idle_gaps"] = xtrace.idle_gaps(tr)
        out["span_totals"] = {n: list(v)
                              for n, v in sorted(window.span_totals.items())}
        out["window_s"] = window.window_s
        out["busy_s"] = window.busy_s
        log(f"idle by program span: {out['idle_by_program_span']}")
    out["metrics"] = {k: v["value"] for k, v in harness.metrics_for(
        harness.load_benchmark(), workload, trace, window).items()}
    out["place_ms"] = (sum(window.place_s) / len(window.place_s) * 1e3
                       if window.place_s else None)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    cells = {c["name"]: c for c in harness.load_benchmark()["workloads"]}
    devices = jax.devices()
    if (args.workload not in cells or devices[0].platform != "tpu"
            or len(devices) < cells[args.workload]["chips"]):
        print(f"needs a known cell on TPU chips; JAX sees "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    log = lambda msg: print(f"[spans] {msg}", file=sys.stderr, flush=True)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace),
              log=log)
    out.update(workload=args.workload, seed=args.seed, trace=args.trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
