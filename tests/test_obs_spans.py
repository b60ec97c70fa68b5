"""Duration spans of ``repro.obs.Tracer`` and the profiler mode.

The contracts under test:
  * a disabled tracer's span site returns the shared ``NULL_SPAN``, opens
    no profiler annotation and allocates nothing;
  * with a sink, duration spans nest per trace, have ``w1 >= w0`` and
    validate; a profile-only tracer runs no instants or roots;
  * a profiled CPU rehearsal of each benchmark cell puts every program
    span on the profiler's host plane, and the backend's launch counter
    matches the device programs the profiler sees executed;
  * the benchmark's trace reduction (``bench/xtrace.py``) reads the
    recorded chip trace as before, and ``bench/program_spans.py`` reduces
    the program's spans and turns them into per-layer numbers.
"""
import sys
import tracemalloc
from pathlib import Path

import pytest

from repro.obs import (MemorySink, NULL_SPAN, NULL_TRACER, PROFILE_PREFIX,
                       Tracer, validate)
from repro.obs import trace as trace_mod

from test_obs import diurnal_sim, local_router

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

SMALL = REPO / "bench" / "tests" / "data" / "small.xplane.pb"

#: program spans every cell opens, and the one only the tenancy cell does
SPANS = {"router.step", "router.reap", "router.policy", "router.submit",
         "batcher.next_batch", "router.dispatch", "engine.submit",
         "engine.admit", "dp.solve", "backend.prepare",
         "backend.microbatches", "backend.dispatch", "backend.resolve"}
CELL_SPANS = {"paper-mix-diurnal": SPANS,
              "llm-tenants-bursty": SPANS | {"router.preempt_pass"}}


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tracer", [NULL_TRACER,
                                    Tracer(MemorySink(), enabled=False)],
                         ids=["null", "disabled"])
def test_disabled_span_is_the_shared_null_context(tracer, monkeypatch):
    import jax

    def refuse(name):
        raise AssertionError(f"annotation {name} opened")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not tracer.timing
    assert tracer.span("router", "router.step", 1.0) is NULL_SPAN
    with tracer.span("router", "router.step", 1.0):
        pass
    assert all(s.records == [] for s in tracer.sinks)


def test_disabled_span_site_allocates_nothing():
    tracemalloc.start()
    try:
        for _ in range(3):
            with NULL_TRACER.span("router", "router.step", 0.0):
                pass
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with NULL_TRACER.span("router", "router.step", 0.0):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [d for d in after.compare_to(before, "filename")
             if d.traceback[0].filename == trace_mod.__file__
             and d.size_diff > 0]
    assert grown == []


def test_spans_nest_per_trace_and_validate():
    sink = MemorySink()
    tr = Tracer(sink)
    with tr.span("router", "router.step", 2.0):
        with tr.span("router", "router.reap", 2.0):
            pass
        with tr.span("engine", "engine.submit", 2.0, {"n": 3}):
            with tr.span("engine", "engine.admit", 2.0):
                pass
    tr.flush()
    by = {r["name"]: r for r in sink.records}
    assert set(by) == {"router.step", "router.reap", "engine.submit",
                       "engine.admit"}
    assert by["router.reap"]["parent"] == by["router.step"]["span"]
    assert by["engine.admit"]["parent"] == by["engine.submit"]["span"]
    # another trace's open span is no parent: parents stay in their trace
    assert by["engine.submit"]["parent"] is None
    assert by["router.step"]["parent"] is None
    assert by["engine.submit"]["n"] == 3
    for r in sink.records:
        assert r["w1"] >= r["w0"] and r["t0"] == r["t1"] == 2.0
    outer, inner = by["router.step"], by["router.reap"]
    assert outer["w0"] <= inner["w0"] <= inner["w1"] <= outer["w1"]
    errors, stats = validate(sink.records)
    assert errors == [] and stats["spans"] == 4


def test_span_parents_to_the_open_root_and_closes_on_error():
    sink = MemorySink()
    tr = Tracer(sink)
    root = tr.open_root("router", "cycle", 0.0)
    with pytest.raises(ValueError):
        with tr.span("router", "router.step", 0.0):
            raise ValueError
    with tr.span("router", "router.submit", 0.0):
        pass
    tr.close_root("router", 0.0)
    steps = [r for r in sink.records if r["name"] != "cycle"]
    assert [r["parent"] for r in steps] == [root, root]
    assert tr._stack == []
    assert validate(sink.records)[0] == []


def test_traced_run_spans_validate_and_solve_drops_place_attrs():
    sink = MemorySink()
    router = local_router(tracer=Tracer(sink))
    diurnal_sim(duration=10.0).run(router)
    router.tracer.flush(router.metrics.t_last)
    errors, stats = validate(sink.records)
    assert errors == [] and stats["coverage"] >= 0.99
    for name in ("router.step", "router.submit", "engine.submit",
                 "batcher.next_batch", "dp.solve"):
        assert stats["names"].get(name, 0) > 0, name
    solves = [r for r in sink.records if r["name"] == "solve"]
    places = [r for r in sink.records if r["name"] == "place"]
    assert solves and places
    assert not any("cache_hit" in r or "wall_ms" in r for r in solves)
    assert all("cache_hit" in r and "wall_ms" in r for r in places)


def test_profile_only_tracer_times_spans_and_runs_no_instants():
    router = local_router(tracer=Tracer(profile=True))
    tr = router.tracer
    assert tr.profile and tr.timing and not tr.enabled
    assert router.engine.tracer is tr and router.dyn.tracer is tr
    snap = diurnal_sim(duration=10.0).run(router)
    assert tr._open == {} and tr._next_span == 0     # no roots, no ids
    waits = router.metrics.queue_wait_s
    assert len(waits) == snap.completed and min(waits) >= 0
    # derived, never inputs: the same simulated outcome as untraced
    plain = local_router()
    assert diurnal_sim(duration=10.0).run(plain) == snap
    assert plain.metrics.queue_wait_s == []


def test_profile_span_is_a_profiler_annotation(monkeypatch):
    import jax

    opened = []

    class Ann:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    sink = MemorySink()
    for tr in (Tracer(profile=True), Tracer(sink, profile=True)):
        with tr.span("engine", "engine.admit", 0.0):
            pass
    assert opened == [PROFILE_PREFIX + "engine.admit"] * 2
    assert [r["name"] for r in sink.records] == ["engine.admit"]


# ---------------------------------------------------------------------------
# the program under the profiler (CPU)
# ---------------------------------------------------------------------------
def _profile(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; the host-plane events."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.rglob("*.xplane.pb")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


@pytest.mark.parametrize("cell", sorted(CELL_SPANS))
def test_cell_rehearsal_puts_every_program_span_on_the_host_plane(
        cell, tmp_path):
    from bench import arrivals, harness, stack
    from repro.runtime import PallasPipelineBackend

    spec = next(c for c in harness.load_benchmark()["workloads"]
                if c["name"] == cell)
    cfg = stack.load_config(spec["config"])
    traffic = arrivals.load_traffic(spec["traffic"])
    router, backend = stack.build(cfg, traffic["provisioned_rate"],
                                  PallasPipelineBackend(**cfg["backend"]))
    tracer = Tracer(profile=True)
    router.tracer = tracer
    router.engine.tracer = tracer
    assert backend.tracer is tracer and router.dyn.tracer is tracer
    driver = harness.Driver(router, arrivals.Stream(traffic, 2**31 + 7), cfg)
    events = _profile(tmp_path, lambda: driver.serve_until(1.5))
    names = {n[len(PROFILE_PREFIX):] for n, _, _ in events
             if n.startswith(PROFILE_PREFIX)}
    assert CELL_SPANS[cell] <= names
    assert not any(n.startswith("bench:") for n, _, _ in events)
    assert router.metrics.queue_wait_s and backend.launches > 0


LAUNCHES = """
import glob, sys
import jax
from jax.profiler import ProfileData
from repro.core import DynamicScheduler, PerfModel
from repro.core.device import tpu_system
from repro.core.workload import gcn_workload, DATASETS
from repro.runtime import PallasPipelineBackend

wl = gcn_workload(DATASETS["OA"], hidden=128, layers=2)
res = DynamicScheduler(tpu_system(2, 2), PerfModel()).submit(wl)
be = PallasPipelineBackend(mode=sys.argv[1])
h = be.prepare(res, wl)
micro = be.microbatches(2)
jax.block_until_ready(be.dispatch(h, micro))      # compiles outside
n0 = be.launches
jax.profiler.start_trace(sys.argv[2])
for _ in range(3):
    jax.block_until_ready(be.dispatch(h, micro))
jax.profiler.stop_trace()
path, = glob.glob(sys.argv[2] + "/**/*.xplane.pb", recursive=True)
executed = sum(1 for p in ProfileData.from_file(path).planes
               if p.name.startswith("/host:") for line in p.lines
               for e in line.events if e.name == "PjRtCpuExecutable::Execute")
print(h.mode, len(res.pipeline.stages), be.launches - n0, executed)
"""


@pytest.mark.parametrize("mode", ["chain", "mesh"])
def test_launch_counter_matches_executed_programs(mode, tmp_path):
    """Four host devices (a subprocess), so the DP's 2x2 pool fits a
    mesh; the profiler counts the programs each ``dispatch`` executed."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, "-c", LAUNCHES, mode,
                        str(tmp_path)], capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    got_mode, stages, launches, executed = p.stdout.split()
    assert got_mode == mode and int(stages) > 1
    assert int(launches) == int(executed) > 0
    if mode == "chain":        # three dispatches, one program a stage
        assert int(launches) == 3 * int(stages)


def test_chain_stage_programs_are_named_by_their_kinds():
    from repro.core import DynamicScheduler, PerfModel, paper_system
    from repro.core.workload import gcn_workload, DATASETS
    from repro.runtime import PallasPipelineBackend

    name = PallasPipelineBackend.stage_name
    assert name(("spmm", "gemm")) == "stage_spmm_gemm"
    assert name(("gemm", "gemm", "win_attn", "gemm")) == \
        "stage_gemm2_win_attn_gemm"
    wl = gcn_workload(DATASETS["OA"], hidden=128, layers=2)
    res = DynamicScheduler(paper_system("pcie4"), PerfModel()).submit(wl)
    be = PallasPipelineBackend(mode="chain")
    h = be.prepare(res, wl)
    jits, params = h.payload
    micro = be.microbatches(1)
    for s, (stage, sj) in enumerate(zip(res.pipeline.stages, jits)):
        kinds = tuple(wl[i].kind for i in range(stage.i0, stage.i1))
        text = sj.lower(params["w"][s], micro).as_text()
        assert f"jit_{name(kinds)}" in text


# ---------------------------------------------------------------------------
# the benchmark's readings of a trace
# ---------------------------------------------------------------------------
def test_small_trace_reads_as_before():
    from bench import xtrace

    tr = xtrace.read(str(SMALL))
    assert xtrace.busy_ns(tr) == 7554.0
    assert xtrace.idle_gaps(tr) == [
        ["backend.dispatch (29 gaps)", 0.003252820999999999],
        ["backend.resolve (1 gaps)", 0.0018760250000000001],
        ["engine.submit (1 gaps)", 0.0017066300000000002]]
    assert xtrace.top_programs(tr) == [
        ["jit_dynamic_slice(16523057608155512145)", 2.465e-06],
        ["jit_apply(3307493910501777987)", 2.302e-06],
        ["jit_dynamic_slice(4470350432912717391)", 1.4450000000000001e-06],
        ["jit_apply(11086404572152769489)", 1.187e-06],
        ["jit_apply(2897787034631173694)", 1.126e-06]]


def test_small_trace_has_no_program_spans():
    from bench import program_spans, xtrace

    tr = xtrace.read(str(SMALL))
    spans = program_spans.read(str(SMALL))
    assert spans == [] and program_spans.program_span_totals(
        spans, tr.window) == {}
    idle = program_spans.idle_by_program_span(tr, spans)
    assert [n.split(" (")[0] for n, _ in idle] == ["none"]
    assert idle[0][1] * 1e9 + xtrace.busy_ns(tr) == \
        pytest.approx(tr.window[1] - tr.window[0])


def test_idle_by_program_span_names_the_innermost_span():
    from bench import program_spans, xtrace

    # device busy [10, 20) and [40, 50) in a window [0, 100) (ns)
    tr = xtrace.Trace({"/device:TPU:0": [("op", 10, 20), ("op", 40, 50)]},
                      {}, [("bench:step", 0, 100)])
    spans = [("router.step", 0, 90), ("engine.submit", 25, 38),
             ("engine.admit", 28, 36), ("router.reap", 55, 95)]
    got = {n.split(" (")[0]: v
           for n, v in program_spans.idle_by_program_span(tr, spans)}
    # gaps: [0,10) mid 5 -> step; [20,40) mid 30 -> admit; [50,100) mid 75
    # -> reap
    assert got == pytest.approx({"router.step": 10e-9,
                                 "engine.admit": 20e-9,
                                 "router.reap": 50e-9})
    totals = program_spans.program_span_totals(spans, (30, 100))
    assert totals["router.step"] == (1, pytest.approx(60e-9))
    assert totals["engine.admit"] == (1, pytest.approx(6e-9))
    assert "engine.submit" in totals and len(totals) == 4


def test_layer_metrics_from_hand_built_totals():
    from bench import program_spans

    totals = {"router.step": (200, 1.0), "batcher.next_batch": (300, 0.2),
              "router.preempt_pass": (200, 0.05),
              "engine.admit": (40, 0.08), "backend.microbatches": (100, 0.03),
              "backend.dispatch": (100, 0.06)}
    waits = [i * 1e-3 for i in range(1, 101)]      # 1..100 ms
    got = program_spans.layer_metrics(totals, 100, 1200, waits)
    assert got == pytest.approx({
        "batch_form_ms": 2.0, "preempt_pass_ms": 0.25, "admit_ms": 2.0,
        "micro_build_ms": 0.3, "launch_us": 50.0, "launches_per_batch": 12.0,
        "queue_wait_wall_p95_ms": 95.0})
    # nothing to read: left out, never raised on
    assert program_spans.layer_metrics({}, 0, 0, []) == {}
    sparse = program_spans.layer_metrics({"router.step": (5, 0.1)}, 10, 0,
                                         [])
    assert sparse == {}


def test_profiled_stack_notes_the_window(monkeypatch):
    from bench import harness, program_spans

    monkeypatch.setattr("repro.launch.compile_cache.enable", lambda: "off")
    out = program_spans.run("paper-mix-diurnal", 2**31 + 11, 0.3, True,
                            log=lambda msg: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["batches"] > 0 and out["launches"] >= out["batches"]
    assert {"batch_form_ms", "micro_build_ms", "launch_us",
            "launches_per_batch", "queue_wait_wall_p95_ms"} <= \
        set(out["program"])
    assert "preempt_pass_ms" not in out["program"]
    names = {n.split(" (")[0] for n, _ in out["idle_by_program_span"]}
    assert names and names <= SPANS | {"none"}
    assert {"place_ms", "batch_occupancy"} <= set(out["metrics"])
    assert harness.stack.build.__name__ == "build"     # put back
