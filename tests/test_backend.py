"""ExecutionBackend protocol: analytic/pallas ordering parity, trace
record+replay, handle invalidation, and Engine multi-cell concurrency
(acceptance: two signature cells resident on disjoint device subsets
serving concurrently; Router has no inline execution math)."""
import pytest

from repro.core import (DATASETS, DynamicScheduler, PerfModel, gcn_workload,
                        paper_system, swa_transformer_workload)
from repro.runtime import (AnalyticBackend, ElasticRuntime,
                           PallasPipelineBackend, ReplayBackend,
                           TraceRecorder, make_backend, pipeline_fill)
from repro.serving import (Engine, LoadWatermarkPolicy, Request, Router,
                           SignatureBatcher, TrafficSim)

WL_A = gcn_workload(DATASETS["OA"])
WL_B = gcn_workload(DATASETS["OP"])
WL_L = swa_transformer_workload(1024, 512, layers=2)


def fresh_dyn(mode="perf"):
    return DynamicScheduler(paper_system("pcie4"), PerfModel(), mode=mode)


# ---------------------------------------------------------------------------
# protocol basics
# ---------------------------------------------------------------------------
def test_analytic_report_matches_fill_period():
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    be = AnalyticBackend()
    h = be.prepare(res, WL_A, epoch=dyn.epoch)
    rep = be.execute(h, 4, 10.0)
    fill = pipeline_fill(res)
    per = res.pipeline.period
    assert rep.finishes == tuple(10.0 + fill + i * per for i in range(4))
    assert rep.finish == rep.finishes[-1]
    assert rep.energy_per_req == pytest.approx(res.energy)
    assert rep.stage_times == tuple(s.total for s in res.pipeline.stages)


def test_handle_staleness_tracks_epoch():
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    h = AnalyticBackend().prepare(res, WL_A, epoch=dyn.epoch)
    assert not h.stale(dyn.epoch)
    dyn.set_mode("energy")
    assert h.stale(dyn.epoch)
    e = dyn.epoch
    dyn.resize(2, 2)
    assert dyn.epoch == e + 1


def test_submit_rejects_overlong_pool_vector():
    dyn = fresh_dyn()
    with pytest.raises(ValueError):
        dyn.submit(WL_A, pool=(1, 1, 1))    # 2-pool system, 3 counts


def test_make_backend_factory():
    assert isinstance(make_backend("analytic"), AnalyticBackend)
    assert isinstance(make_backend("pallas"), PallasPipelineBackend)
    with pytest.raises(ValueError):
        make_backend("quantum")


# ---------------------------------------------------------------------------
# acceptance: analytic vs pallas (chain) completion-ordering parity
# ---------------------------------------------------------------------------
def _stream_finishes(backend):
    """Run the same batch stream through ``backend``; returns the stream's
    (request-tag, finish-time) pairs sorted by completion."""
    dyn = fresh_dyn()
    out = []
    t0 = 0.0
    for tag, wl, n in (("a", WL_A, 3), ("l", WL_L, 2), ("b", WL_B, 4),
                       ("a2", WL_A, 1)):
        res = dyn.submit(wl)
        h = backend.prepare(res, wl, epoch=dyn.epoch)
        rep = backend.execute(h, n, t0)
        out.extend(((tag, i), f) for i, f in enumerate(rep.finishes))
        t0 = rep.finish
    order = [key for key, f in sorted(out, key=lambda kv: (kv[1], kv[0]))]
    return order, out


def test_analytic_pallas_ordering_parity():
    order_a, fin_a = _stream_finishes(AnalyticBackend())
    order_p, fin_p = _stream_finishes(
        PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2))
    assert order_a == order_p
    # chain-mode times come from the same schedule model: bit-identical
    assert fin_a == fin_p


def test_pallas_backend_actually_executes():
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    be = PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2)
    h = be.prepare(res, WL_A, epoch=dyn.epoch)
    assert h.mode == "chain"
    rep = be.execute(h, 3, 0.0)
    assert rep.wall > 0.0                    # real compute happened
    assert len(rep.finishes) == 3
    assert be.output_platforms == {"cpu": 1}
    # prepared payloads are cached by stage structure
    h2 = be.prepare(res, WL_A, epoch=dyn.epoch)
    assert h2.payload is h.payload


def test_chain_weights_sliced_at_prepare_give_the_same_outputs():
    """Chain mode keeps one weight a stage, sliced once at prepare: each
    is the stacked weight's row ``s`` bit for bit, in stage order, and
    ``dispatch`` computes what picking ``stacked[s]`` per stage does."""
    import numpy as np

    res = fresh_dyn().submit(WL_A)
    n = len(res.pipeline.stages)
    assert n == 2
    be = PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2)
    h = be.prepare(res, WL_A)
    jits, params = h.payload
    stacked = be.stage_weights(n)
    assert len(params["w"]) == n
    for s, w in enumerate(params["w"]):
        assert w.shape == (4, 4)
        np.testing.assert_array_equal(np.asarray(w), np.asarray(stacked[s]))
    micro = be.microbatches(3)
    want, x = [], micro
    for s, sj in enumerate(jits):
        x = sj(stacked[s], x)
        want.append(x)
    n0 = be.launches
    got = be.dispatch(h, micro)
    assert be.launches - n0 == n
    assert len(got) == n
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_pallas_auto_mode_reports_chain_and_mesh_mode_refuses():
    """On one device ``auto`` records the sequential chain on the handle;
    ``mesh`` with too few devices raises instead of degrading."""
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    auto = PallasPipelineBackend(act_dim=4, act_batch=2)
    assert auto.prepare(res, WL_A).mode == "chain"
    with pytest.raises(RuntimeError, match="needs"):
        PallasPipelineBackend(mode="mesh", act_dim=4,
                              act_batch=2).prepare(res, WL_A)


def test_router_parity_analytic_vs_pallas():
    """Same traffic stream, analytic vs real-pipeline execution: identical
    per-request completion ordering end-to-end through the Router."""
    def run(backend):
        r = Router(fresh_dyn(),
                   batcher=SignatureBatcher(max_batch=8, max_wait=0.25),
                   policy=LoadWatermarkPolicy(window=10.0),
                   backend=backend)
        sim = TrafficSim(seed=5, duration=6.0, day=6.0, peak_rate=4.0,
                         trough_rate=1.0)
        sim.run(r)
        return sorted(r.metrics.latencies), r.metrics.completed
    a = run(AnalyticBackend())
    p = run(PallasPipelineBackend(mode="chain", act_dim=4, act_batch=2))
    assert a == p


# ---------------------------------------------------------------------------
# trace record + replay
# ---------------------------------------------------------------------------
def test_trace_recorder_replay_roundtrip(tmp_path):
    dyn = fresh_dyn()
    rec = TraceRecorder(AnalyticBackend())
    reports = []
    for wl, n in ((WL_A, 3), (WL_B, 2)):
        res = dyn.submit(wl)
        h = rec.prepare(res, wl, epoch=dyn.epoch)
        reports.append((res, n, rec.execute(h, n, 1.0)))
    rep_be = rec.to_replay()
    for res, n, orig in reports:
        h = rep_be.prepare(res, WL_A, epoch=dyn.epoch)
        again = rep_be.execute(h, n, 1.0)
        assert again.finishes == pytest.approx(orig.finishes)
        assert again.energy_per_req == pytest.approx(orig.energy_per_req)
    # jsonl round trip
    path = tmp_path / "exec_traces.jsonl"
    rec.to_jsonl(path)
    loaded = ReplayBackend.from_jsonl(path, strict=True)
    res, n, orig = reports[0]
    h = loaded.prepare(res, WL_A, epoch=0)
    assert loaded.execute(h, n, 1.0).finishes == pytest.approx(orig.finishes)


def test_trace_key_distinguishes_shared_mnemonics():
    """GCN-arxiv and the 1k LLM both lower to '1G1G' with ~9x different
    periods; replay must keep their traces separate (keying by mnemonic
    alone would replay one schedule's timings for the other)."""
    dyn = fresh_dyn()
    ra, rl = dyn.peek(WL_A), dyn.peek(WL_L)
    rec = TraceRecorder(AnalyticBackend())
    for res, wl in ((ra, WL_A), (rl, WL_L)):
        rec.execute(rec.prepare(res, wl, epoch=dyn.epoch), 2, 0.0)
    rep = rec.to_replay()
    fa = rep.execute(rep.prepare(ra, WL_A), 2, 0.0).finishes
    fl = rep.execute(rep.prepare(rl, WL_L), 2, 0.0).finishes
    assert fa == pytest.approx(
        AnalyticBackend().execute(AnalyticBackend().prepare(ra, WL_A), 2, 0.0).finishes)
    assert fl == pytest.approx(
        AnalyticBackend().execute(AnalyticBackend().prepare(rl, WL_L), 2, 0.0).finishes)
    if ra.mnemonic == rl.mnemonic:           # the collision this guards
        assert fa != pytest.approx(fl)


def test_engine_ready_full_pool_fallback():
    """A workload feasible only above the fair-share cap (here: weights
    that need 2 GPUs) must still be dispatchable — ready() mirrors the
    admit path's full-pool fallback instead of spinning forever."""
    from repro.core import KernelSpec, Workload
    big = Workload("big-gemm",
                   (KernelSpec("G", "gemm", M=1000, K=160_000, N=150_000),))
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    assert not dyn.feasible(big, (2, 1)) and dyn.feasible(big)
    assert eng.ready(big, 0.0)
    cell, rep = eng.dispatch(FakeBatch(big, 1), 0.0)
    assert cell.devices == {"GPU": 2} and rep.t0 == 0.0
    # and end-to-end: a router stream with it drains promptly
    r = Router(fresh_dyn(),
               batcher=SignatureBatcher(max_batch=4, max_wait=0.25),
               policy=LoadWatermarkPolicy(window=10.0))
    r.submit(Request(0, big, 0.0), 0.0)
    done = r.drain(0.0)
    assert [q.rid for q in done] == [0]


def test_replay_backend_strict_raises_on_unknown():
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    be = ReplayBackend({}, strict=True)
    h = be.prepare(res, WL_A)
    with pytest.raises(KeyError):
        be.execute(h, 1, 0.0)
    # non-strict falls back to the analytic model
    assert ReplayBackend({}).execute(h, 1, 0.0).finishes[0] > 0.0


# ---------------------------------------------------------------------------
# Engine: residency, concurrency, eviction, invalidation
# ---------------------------------------------------------------------------
class FakeBatch:
    def __init__(self, wl, n):
        self.wl = wl
        self.n = n

    def __len__(self):
        return self.n


def test_engine_two_cells_disjoint_and_concurrent():
    """Two signature cells resident at once, on disjoint device subsets,
    with overlapping execution intervals (the multi-pipeline win)."""
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    ca, rep_a = eng.dispatch(FakeBatch(WL_A, 4), 0.0)
    cb, rep_b = eng.dispatch(FakeBatch(WL_L, 4), 0.0)
    assert ca is not cb and len(eng.cells) == 2
    # disjoint subsets: per-type allocations fit inside the pool
    used = eng.allocated()
    assert used.get("FPGA", 0) <= dyn.system.n_a
    assert used.get("GPU", 0) <= dyn.system.n_b
    # concurrent: both started at t=0 and both run past t=0
    assert rep_a.t0 == 0.0 and rep_b.t0 == 0.0
    assert rep_a.finish > 0.0 and rep_b.finish > 0.0
    assert ca.busy_until > 0.0 and cb.busy_until > 0.0
    # a third signature while both are busy must NOT start at t=0 — it
    # waits for an eviction (no device oversubscription)
    cc, rep_c = eng.dispatch(FakeBatch(WL_B, 1), 0.0)
    assert rep_c.t0 >= min(rep_a.finish, rep_b.finish)


def test_engine_lru_eviction_and_capacity_accounting():
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=1)
    c1, rep1 = eng.dispatch(FakeBatch(WL_A, 1), 0.0)
    t = rep1.finish + 1.0                   # c1 idle now
    c2, _ = eng.dispatch(FakeBatch(WL_L, 1), t)
    assert len(eng.cells) == 1 and eng.evictions == 1
    assert c2.key != c1.key
    # all allocations released on eviction: free + allocated == pool
    fa, fb = eng.free()
    used = eng.allocated()
    assert fa + used.get("FPGA", 0) == dyn.system.n_a
    assert fb + used.get("GPU", 0) == dyn.system.n_b


def test_engine_epoch_invalidation_on_mode_flip_and_resize():
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    c1, _ = eng.dispatch(FakeBatch(WL_A, 1), 0.0)
    dyn.set_mode("energy")
    c2, _ = eng.dispatch(FakeBatch(WL_A, 1), 100.0)
    assert c2 is not c1 and c2.epoch == dyn.epoch
    assert c2.schedule.mode == "energy"
    dyn.resize(2, 2)
    c3, _ = eng.dispatch(FakeBatch(WL_A, 1), 200.0)
    assert c3 is not c2 and c3.epoch == dyn.epoch
    used = c3.schedule.pipeline.devices_used()
    assert used.get("FPGA", 0) <= 2 and used.get("GPU", 0) <= 2


def test_engine_fair_share_cap():
    """With max_cells=2 a single cell may not claim the whole pool."""
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    cell, _ = eng.dispatch(FakeBatch(WL_A, 1), 0.0)
    used = cell.schedule.pipeline.devices_used()
    import math
    assert used.get("FPGA", 0) <= math.ceil(dyn.system.n_a / 2)
    assert used.get("GPU", 0) <= math.ceil(dyn.system.n_b / 2)
    fa, fb = eng.free()
    assert fa > 0 or fb > 0                 # room left for a second cell


def test_router_serves_two_cells_concurrently():
    """End-to-end: two signature groups dispatch in overlapping windows on
    different engine cells."""
    r = Router(fresh_dyn(),
               batcher=SignatureBatcher(max_batch=4, max_wait=0.0),
               policy=LoadWatermarkPolicy(window=10.0))
    for i in range(4):
        r.submit(Request(i, WL_A, 0.0), 0.0)
        r.submit(Request(10 + i, WL_L, 0.0), 0.0)
    done = r.step(0.0) + r.drain(0.0)   # completions deliver via deferred reap
    assert len(done) == 8
    cells = {d.cell for d in r.dispatches}
    assert len(cells) == 2
    t0s = [d.t0 for d in r.dispatches]
    assert t0s[0] == t0s[1] == 0.0          # both started immediately


# ---------------------------------------------------------------------------
# ElasticRuntime on the backend
# ---------------------------------------------------------------------------
def test_elastic_runtime_executes_through_backend():
    dyn = fresh_dyn()
    rt = ElasticRuntime(dyn, WL_B)
    rep = rt.execute(3, t0=1.0)
    assert len(rep.finishes) == 3
    assert rep.finishes[0] == pytest.approx(
        1.0 + pipeline_fill(rt.schedule))
    # a failure redeploys: fresh handle, schedule fits the shrunken pool
    rt.on_failure("FPGA", 1)
    rep2 = rt.execute(1, t0=2.0)
    assert rt.handle.epoch == dyn.epoch
    assert rep2.finishes[0] > 2.0


def test_elastic_runtime_execute_reschedules_after_external_flip():
    """An objective flip outside the on_failure/on_join hooks stales the
    handle; execute() must REschedule under the new mode, not re-prepare
    the outdated schedule."""
    dyn = fresh_dyn()
    rt = ElasticRuntime(dyn, WL_B)
    assert rt.schedule.mode == "perf"
    dyn.set_mode("energy")
    rt.execute(1)
    assert rt.schedule.mode == "energy"
    assert rt.handle.schedule.mode == "energy"
    assert not rt.handle.stale(dyn.epoch)


def test_engine_does_not_oversubscribe_extra_pools():
    """Three-pool system: concurrent cells must stay disjoint on the extra
    pool too (capacity accounting covers every pool, not just a/b)."""
    from repro.core import TPU_DENSE
    system = paper_system("pcie4").with_extra((TPU_DENSE, 2))
    dyn = DynamicScheduler(system, PerfModel(), mode="perf")
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    _, rep_a = eng.dispatch(FakeBatch(WL_A, 2), 0.0)
    _, rep_b = eng.dispatch(FakeBatch(WL_L, 2), 0.0)
    used = eng.allocated()
    for dev, cnt in system.pools:
        assert used.get(dev.name, 0) <= cnt, (dev.name, used)


def test_engine_busy_floor_survives_invalidation():
    """A resize/mode-flip mid-batch drops the cell, but its devices stay
    physically busy until the batch drains — the next admission must not
    start on them before that (no capacity double-counting)."""
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    cell, rep = eng.dispatch(FakeBatch(WL_A, 8), 0.0)
    drain = rep.finish
    assert drain > 0.0
    dyn.resize(2, 2)                        # epoch bump mid-batch
    assert not eng.ready(WL_A, drain / 2)   # still draining
    cell2, rep2 = eng.dispatch(FakeBatch(WL_A, 1), drain / 2)
    assert rep2.t0 >= drain                 # waited for the old pipeline


def test_engine_admission_pool_keys_are_stable():
    """Admissions schedule on the fair-share cap, not the churning free
    vector, so the DP cache stays hot across evict/readmit cycles."""
    dyn = fresh_dyn()
    eng = Engine(dyn, AnalyticBackend(), max_cells=2)
    t = 0.0
    for _ in range(6):                      # force eviction churn
        for wl in (WL_A, WL_B, WL_L):
            _, rep = eng.dispatch(FakeBatch(wl, 1), t)
            t = rep.finish
    assert eng.evictions > 0
    assert dyn.dp_solves <= 3               # one solve per signature


def test_router_ignores_elastic_events_on_extra_pools():
    from repro.core import TPU_DENSE
    system = paper_system("pcie4").with_extra((TPU_DENSE, 2))
    dyn = DynamicScheduler(system, PerfModel(), mode="perf")
    r = Router(dyn)
    r.submit(Request(0, WL_A, 0.0), 0.0)
    r.step(1.0)
    epoch = dyn.epoch
    r.on_failure("TPU_DENSE", 1)            # no ValueError, no resize
    assert dyn.epoch == epoch
    assert any("unmanaged" in line for line in r.log)
    r.on_join("TPU_DENSE", 1)
    assert dyn.epoch == epoch


def test_pool_state_rejects_unmanaged_pool_names():
    from repro.core import TPU_DENSE
    from repro.runtime import PoolState
    system = paper_system("pcie4").with_extra((TPU_DENSE, 2))
    pool = PoolState(system.n_a, system.n_b)
    with pytest.raises(ValueError):
        pool.adjust(system, "TPU_DENSE", -1)
    assert pool.n_a == system.n_a and pool.n_b == system.n_b
    assert not PoolState.manages(system, "TPU_DENSE")
    assert PoolState.manages(system, "FPGA")


def test_observe_stage_time_targets_named_cell():
    """With two concurrent cells, measurements route to the cell that
    produced them (DispatchRecord.cell), not whichever dispatched last."""
    r = Router(fresh_dyn(),
               batcher=SignatureBatcher(max_batch=4, max_wait=0.0),
               policy=LoadWatermarkPolicy(window=10.0))
    for i in range(4):
        r.submit(Request(i, WL_A, 0.0), 0.0)
        r.submit(Request(10 + i, WL_L, 0.0), 0.0)
    r.step(0.0)
    first, last = r.dispatches[0], r.dispatches[-1]
    assert first.cell != last.cell
    target = r.engine.cell_by_id(first.cell)
    n0 = target.monitor.stats[0].strikes
    # a normal-time observation for the FIRST cell must not touch the last
    baseline = target.schedule.pipeline.stages[0].total
    r.observe_stage_time(0, baseline, cell=first.cell)
    assert r.engine.last_cell is not target
    assert target.monitor.stats[0].strikes == n0  # observed, no strike
