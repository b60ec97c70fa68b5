"""Async dispatch + measured-time feedback (ISSUE 3 tentpole).

Covers the `ExecutionBackend.submit` protocol extension (two-phase
BackendFuture), sync/async Router parity (identical per-request completion
ordering), the overlap ratio (> 1.0 with two concurrent cells), and the
closed measurement loop: a replay trace with one injected slow stage must
flip the StragglerMonitor and force a demotion + reschedule through the
async loop — driven by backend-*measured* stage times, not DP estimates."""
import pytest

from repro.core import (DATASETS, DynamicScheduler, PerfModel, gcn_workload,
                        paper_system, swa_transformer_workload)
from repro.runtime import (AnalyticBackend, BackendFuture, ElasticRuntime,
                           PallasPipelineBackend, ProbationTracker,
                           ReplayBackend, TraceRecorder)
from repro.serving import (LoadWatermarkPolicy, Request, Router,
                           SignatureBatcher, TrafficSim)

WL_A = gcn_workload(DATASETS["OA"])
WL_B = gcn_workload(DATASETS["OP"])
WL_L = swa_transformer_workload(1024, 512, layers=2)


def fresh_dyn(mode="perf"):
    return DynamicScheduler(paper_system("pcie4"), PerfModel(), mode=mode)


def fresh_router(*, async_mode=True, backend=None, max_wait=0.0,
                 max_batch=4, max_cells=2, policy_window=10.0,
                 probation=None):
    return Router(fresh_dyn(),
                  batcher=SignatureBatcher(max_batch=max_batch,
                                           max_wait=max_wait),
                  policy=LoadWatermarkPolicy(window=policy_window),
                  backend=backend, max_cells=max_cells,
                  async_mode=async_mode, probation=probation)


# ---------------------------------------------------------------------------
# BackendFuture protocol
# ---------------------------------------------------------------------------
def test_default_submit_wraps_execute():
    """Backends without native async get a resolved future wrapping the
    synchronous execute — identical report, finishes available up front."""
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    be = AnalyticBackend()
    h = be.prepare(res, WL_A, epoch=dyn.epoch)
    fut = be.submit(h, 4, 2.0)
    assert isinstance(fut, BackendFuture) and fut.done()
    rep = be.execute(h, 4, 2.0)
    assert fut.finishes == rep.finishes
    assert fut.finish == rep.finish
    assert fut.result().finishes == rep.finishes


def test_analytic_measured_synthesized_as_estimates():
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    be = AnalyticBackend()
    rep = be.execute(be.prepare(res, WL_A), 2, 0.0)
    assert rep.measured_stage_times == rep.stage_times
    assert rep.measured == tuple(s.total for s in res.pipeline.stages)


def test_pallas_future_is_two_phase():
    """Pallas submit dispatches without blocking: simulated finishes are
    known immediately, measured wall/stage seconds only after result()."""
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    be = PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2)
    h = be.prepare(res, WL_A, epoch=dyn.epoch)
    fut = be.submit(h, 3, 5.0)
    assert not fut.done()
    assert len(fut.finishes) == 3 and fut.finishes[0] >= 5.0
    rep = fut.result()
    assert fut.done()
    assert rep.wall > 0.0
    n_stages = len(res.pipeline.stages)
    assert len(rep.measured) == n_stages
    assert all(t > 0.0 for t in rep.measured)
    # the per-stage timestamps partition the measured wall exactly
    assert sum(rep.measured) == pytest.approx(rep.wall)
    # simulated times still come from the schedule model (parity invariant)
    assert rep.finishes == fut.finishes
    assert fut.result() is rep               # idempotent


def test_wall_clock_measurements_never_feed_monitors():
    """Pallas measured times are wall seconds — incommensurate with the
    model-scale baselines, and async stage-0 absorbs host latency between
    submit and reap. They must land in metrics only: no strikes, no
    demotion, no matter how slow the host was."""
    assert PallasPipelineBackend.measured_sim_clock is False
    assert AnalyticBackend.measured_sim_clock is True
    be = PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2)
    r = fresh_router(backend=be)
    for i in range(4):
        r.submit(Request(i, WL_A, 0.0), 0.0)
    r.step(0.0)
    r.drain(0.0)                 # deliver the deferred completion
    cell = r.engine.last_cell
    assert all(s.n == 0 for s in cell.monitor.stats)   # nothing observed
    assert not any("straggler" in line for line in r.log)
    assert r.metrics.measured_stage_s > 0.0            # telemetry kept


def test_trace_recorder_on_wall_clock_backend_stays_sim_clock():
    """Recording a pallas run must not bake wall-scale (or jit-compile-
    dominated first-batch) stage times into a trace whose fill/period are
    simulated seconds — the model stage times are recorded instead."""
    dyn = fresh_dyn()
    rec = TraceRecorder(
        PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2))
    assert rec.measured_sim_clock is False
    res = dyn.submit(WL_A)
    h = rec.prepare(res, WL_A, epoch=dyn.epoch)
    rec.execute(h, 2, 0.0)
    tr = next(iter(rec.traces.values()))
    assert tr["stage_times"] == [s.total for s in res.pipeline.stages]


def test_trace_recorder_records_via_submit():
    dyn = fresh_dyn()
    rec = TraceRecorder(AnalyticBackend())
    res = dyn.submit(WL_A)
    h = rec.prepare(res, WL_A, epoch=dyn.epoch)
    fut = rec.submit(h, 2, 0.0)
    assert rec.traces == {}                  # not recorded until resolution
    fut.result()
    assert len(rec.traces) == 1
    tr = next(iter(rec.traces.values()))
    assert tr["stage_times"] == [s.total for s in res.pipeline.stages]


# ---------------------------------------------------------------------------
# sync/async parity
# ---------------------------------------------------------------------------
def _drive(async_mode):
    r = fresh_router(async_mode=async_mode)
    reqs = []
    for i in range(4):
        reqs.append(Request(i, WL_A, 0.0))
        reqs.append(Request(10 + i, WL_L, 0.0))
    done = []
    for q in reqs:
        r.submit(q, 0.0)
    done += r.step(0.0)
    done += r.drain(0.1)
    order = sorted(((q.finish, q.rid, q.start) for q in done))
    return r, order


def test_sync_async_identical_completion_ordering():
    ra, oa = _drive(async_mode=True)
    rs, os_ = _drive(async_mode=False)
    assert oa == os_                          # per-request ordering parity
    assert len(oa) == 8
    recs_a = [(d.t0, d.sig, d.cell, d.n, d.finish) for d in ra.dispatches]
    recs_s = [(d.t0, d.sig, d.cell, d.n, d.finish) for d in rs.dispatches]
    assert recs_a == recs_s                   # same dispatch decisions


def test_sync_async_identical_stream_telemetry():
    def run(async_mode):
        r = fresh_router(async_mode=async_mode, max_wait=0.25, max_batch=8)
        sim = TrafficSim(seed=11, duration=20.0, day=20.0, peak_rate=6.0,
                         trough_rate=0.5)
        snap = sim.run(r)
        return snap, sorted(r.metrics.latencies)
    (snap_a, lat_a), (snap_s, lat_s) = run(True), run(False)
    assert lat_a == lat_s
    assert snap_a == snap_s                   # includes overlap + measured


def test_deferred_reap_across_cycles():
    """Satellite (ISSUE 4): a batch whose simulated finish lies beyond the
    cycle stays in flight — reaping is deferred to the start of the first
    later cycle that passes it, *before* that cycle dispatches, so a slow
    batch never delays other cells. Drain always delivers the tail."""
    r = fresh_router(async_mode=True)
    for i in range(4):
        r.submit(Request(i, WL_A, 0.0), 0.0)
    done = r.step(0.0)
    assert done == []                        # finish > 0.0: stays in flight
    assert len(r.engine.inflight) == 1
    done = r.step(100.0)                     # reaped at next cycle START
    assert len(done) == 4
    assert r.engine.inflight == []
    assert r.drain(100.0) == []


def test_deferred_reap_delivers_before_dispatch():
    """The start-of-cycle reap frees a busy cell before the same cycle's
    dispatch phase, so the next batch for that signature goes out in the
    same step instead of waiting one more cycle."""
    r = fresh_router(async_mode=True, max_batch=2)
    for i in range(2):
        r.submit(Request(i, WL_A, 0.0), 0.0)
    r.step(0.0)
    fin = r.engine.inflight[0].finish
    for i in range(2):
        r.submit(Request(10 + i, WL_A, fin + 1.0), fin + 1.0)
    done = r.step(fin + 1.0)
    assert [q.rid for q in done] == [0, 1]   # reaped first ...
    assert len(r.dispatches) == 2            # ... then batch 2 dispatched
    assert r.dispatches[1].t0 == fin + 1.0


def test_deferred_reap_ordering_unchanged():
    """Satellite acceptance: deferred reaping must not change per-request
    completion ordering vs blocking dispatch (same finishes, same order)."""
    def run(async_mode):
        r = fresh_router(async_mode=async_mode, max_wait=0.25, max_batch=8)
        sim = TrafficSim(seed=5, duration=15.0, day=15.0, peak_rate=7.0,
                         trough_rate=0.5)
        sim.run(r)
        return ([(d.t0, d.sig, d.cell, d.n, d.finish) for d in r.dispatches],
                sorted(r.metrics.latencies))
    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# overlap ratio: concurrent cell execution
# ---------------------------------------------------------------------------
def test_overlap_ratio_above_one_with_two_cells():
    r = fresh_router(async_mode=True, max_cells=2)
    for i in range(4):
        r.submit(Request(i, WL_A, 0.0), 0.0)
        r.submit(Request(10 + i, WL_L, 0.0), 0.0)
    r.step(0.0)
    r.drain(0.0)                 # deliver the deferred completions
    assert len({d.cell for d in r.dispatches}) == 2
    assert r.metrics.overlap_ratio > 1.0
    snap = r.metrics.snapshot()
    assert snap.overlap_ratio > 1.0
    assert snap.measured_stage_s > 0.0


def test_overlap_ratio_is_one_when_serialized():
    r = fresh_router(async_mode=True, max_cells=1)
    for i in range(4):
        r.submit(Request(i, WL_A, 0.0), 0.0)
    r.step(0.0)
    r.submit(Request(9, WL_A, 50.0), 50.0)   # disjoint in time
    r.step(50.0)
    assert r.metrics.overlap_ratio == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# the measurement loop: replayed slow stage -> straggler -> reschedule
# ---------------------------------------------------------------------------
def _recorded_traces():
    """Traces for WL_B's engine-cell schedule, recorded on healthy analytic
    execution (measured == estimates)."""
    rec = TraceRecorder(AnalyticBackend())
    r = fresh_router(backend=rec)
    for i in range(2):
        r.submit(Request(i, WL_B, 0.0), 0.0)
    r.step(0.0)
    r.drain(0.0)                 # recording happens at future resolution
    assert rec.traces
    return {k: dict(v) for k, v in rec.traces.items()}


def _run_replay(traces, n_batches=6):
    # a huge policy window pins the objective (a mode flip would invalidate
    # the cell and re-key its schedule away from the recorded trace)
    r = fresh_router(backend=ReplayBackend(traces), max_batch=2,
                     policy_window=1e9)
    t, rid = 0.0, 0
    for _ in range(n_batches):
        for _ in range(2):
            r.submit(Request(rid, WL_B, t), t)
            rid += 1
        t += 30.0                            # past each batch's drain
        r.step(t)
    r.drain(t)
    return r


def test_replay_slow_stage_flips_straggler_and_reschedules():
    """Acceptance: the StragglerMonitor consumes backend-measured per-stage
    times. A trace with stage 0 injected 4x slow — fill/period untouched,
    so DP estimates alone would never notice — must demote the stage's
    device and force a reschedule through the async loop."""
    traces = _recorded_traces()
    for tr in traces.values():
        tr["stage_times"] = ([4.0 * tr["stage_times"][0]]
                             + tr["stage_times"][1:])
    r = _run_replay(traces)
    assert any("straggler flagged" in line for line in r.log)
    assert any(e.reason == "resize" for e in r.dyn.events)
    pool = r.pool
    sys0 = paper_system("pcie4")
    assert pool.n_a + pool.n_b == sys0.n_a + sys0.n_b - 1   # one demoted
    # serving survived the demotion: every admitted request completed
    assert r.metrics.completed == 12
    assert len(r.queue) == 0


def test_replay_healthy_trace_never_flags():
    """Control: the same loop on the unmodified trace (measured == the
    schedule baselines) must not demote anything."""
    r = _run_replay(_recorded_traces())
    assert not any("straggler" in line for line in r.log)
    assert not any(e.reason == "resize" for e in r.dyn.events)
    assert r.metrics.completed == 12


# ---------------------------------------------------------------------------
# speculative re-admission (probation) of demoted devices
# ---------------------------------------------------------------------------
def _slow_traces():
    traces = _recorded_traces()
    for tr in traces.values():
        tr["stage_times"] = ([4.0 * tr["stage_times"][0]]
                             + tr["stage_times"][1:])
    return traces


def _pool_total(r):
    return r.pool.n_a + r.pool.n_b


def _drive_batches(r, n_batches, t0=0.0, rid0=0):
    t, rid = t0, rid0
    for _ in range(n_batches):
        for _ in range(2):
            r.submit(Request(rid, WL_B, t), t)
            rid += 1
        t += 30.0
        r.step(t)
    r.drain(t)
    return t, rid


def test_probation_readmits_transient_straggler():
    """Satellite (ISSUE 4 / ROADMAP): a transiently slow stage must not
    shrink the pool forever. Demotion -> N clean epochs -> re-admission
    at reduced weight; and a *relapse* on probation bans the device so a
    persistently sick host cannot flap demote/re-admit forever.

    The replay trace injects a 4x-slow stage for the full-pool schedule
    only; the shrunken pool's schedule has no trace (analytic fallback =
    healthy), so: demote (pool-1) -> clean epochs -> re-admit (pool back
    to full) -> the slow trace applies again -> relapse -> banned."""
    sys0 = paper_system("pcie4")
    full = sys0.n_a + sys0.n_b
    prob = ProbationTracker(clean_epochs=3, threshold_scale=0.75)
    r = fresh_router(backend=ReplayBackend(_slow_traces()), max_batch=2,
                     policy_window=1e9, probation=prob)
    # phase 1: persistent slow stage -> demotion
    t, rid = _drive_batches(r, 4)
    assert any("straggler flagged" in line for line in r.log)
    assert _pool_total(r) == full - 1
    # phase 2: healthy epochs on the shrunken pool -> re-admission
    t, rid = _drive_batches(r, 4, t0=t, rid0=rid)
    assert any("probation: re-admitting" in line for line in r.log)
    assert prob.on_probation or prob.banned       # it came back ...
    # phase 3: the full-pool schedule replays slow again -> relapse -> ban
    t, rid = _drive_batches(r, 8, t0=t, rid0=rid)
    assert any("relapsed on probation" in line for line in r.log)
    assert prob.banned
    assert _pool_total(r) == full - 1             # shrunk, and stays shrunk
    joins = [line for line in r.log if "probation: re-admitting" in line]
    assert len(joins) == 1                        # no flapping
    # zero lost work throughout
    assert r.metrics.completed == rid
    assert len(r.queue) == 0


def test_probation_regression_pool_recovers():
    """Regression (the ROADMAP item's core claim): with probation enabled
    a *transient* slow stage leaves the pool at full size afterwards —
    trace healed after the demotion, so the device re-admits cleanly."""
    sys0 = paper_system("pcie4")
    full = sys0.n_a + sys0.n_b
    prob = ProbationTracker(clean_epochs=3)
    backend = ReplayBackend(_slow_traces())
    r = fresh_router(backend=backend, max_batch=2, policy_window=1e9,
                     probation=prob)
    t, rid = _drive_batches(r, 4)
    assert _pool_total(r) == full - 1
    backend.traces.clear()          # the transient cause is gone: every
    #                                 schedule now replays healthy (analytic)
    t, rid = _drive_batches(r, 8, t0=t, rid0=rid)
    assert any("probation: re-admitting" in line for line in r.log)
    assert _pool_total(r) == full                 # pool fully recovered
    assert not prob.banned
    # ... and the monitors hold: no relapse on the healthy stream
    assert not any("relapsed" in line for line in r.log)


def test_probation_tracker_readmits_every_demoted_device():
    """Two devices of one pool demoted during the window -> two
    re-admissions after it (per-device accounting, not per-pool)."""
    p = ProbationTracker(clean_epochs=2)
    assert p.on_demotion("FPGA")
    assert p.on_demotion("FPGA")            # second device, same pool
    assert p.on_clean() == []               # window restarted
    assert p.on_clean() == ["FPGA", "FPGA"]  # one on_join per device
    assert "FPGA" in p.on_probation


def test_probation_elastic_runtime():
    """Same policy through ElasticRuntime for a pinned workload."""
    dyn = fresh_dyn()
    rec = TraceRecorder(AnalyticBackend())
    res = dyn.submit(WL_B)
    rec.execute(rec.prepare(res, WL_B, epoch=dyn.epoch), 2, 0.0)
    traces = {k: dict(v) for k, v in rec.traces.items()}
    for tr in traces.values():
        tr["stage_times"] = ([4.0 * tr["stage_times"][0]]
                             + tr["stage_times"][1:])
    backend = ReplayBackend(traces)
    rt = ElasticRuntime(fresh_dyn(), WL_B, backend=backend,
                        probation=ProbationTracker(clean_epochs=3))
    full = rt.pool.n_a + rt.pool.n_b
    while not any("straggler flagged" in line for line in rt.log):
        rt.execute(1, t0=0.0)
    assert rt.pool.n_a + rt.pool.n_b == full - 1  # demoted
    backend.traces.clear()                        # transient cause gone
    for _ in range(6):
        rt.execute(1, t0=0.0)
    assert any("probation: re-admitting" in line for line in rt.log)
    assert rt.pool.n_a + rt.pool.n_b == full      # recovered


def test_elastic_runtime_feeds_measured_times():
    """ElasticRuntime.execute closes the same loop for pinned workloads:
    replayed slow stage -> automatic demotion, no manual observe calls."""
    dyn = fresh_dyn()
    rec = TraceRecorder(AnalyticBackend())
    res = dyn.submit(WL_B)
    rec.execute(rec.prepare(res, WL_B, epoch=dyn.epoch), 2, 0.0)
    traces = {k: dict(v) for k, v in rec.traces.items()}
    for tr in traces.values():
        tr["stage_times"] = ([4.0 * tr["stage_times"][0]]
                             + tr["stage_times"][1:])
    rt = ElasticRuntime(fresh_dyn(), WL_B, backend=ReplayBackend(traces))
    for _ in range(6):
        rt.execute(1, t0=0.0)
    assert any("straggler flagged" in line for line in rt.log)
    assert any(e.reason == "resize" for e in rt.dyn.events)
    # control: healthy trace leaves the pool intact
    rt2 = ElasticRuntime(fresh_dyn(), WL_B,
                         backend=ReplayBackend(
                             {k: dict(v) for k, v in rec.traces.items()}))
    for _ in range(6):
        rt2.execute(1, t0=0.0)
    assert not any("straggler" in line for line in rt2.log)
