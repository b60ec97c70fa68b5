"""repro.serving: queue admission, signature batching, watermark policy,
metrics, traffic-sim determinism, and the end-to-end serving story
(acceptance: multi-schedule stream + mode switch + mid-stream failure)."""
import pytest

from repro.core import (DATASETS, DynamicScheduler, PerfModel, gcn_workload,
                        paper_system, signature, swa_transformer_workload)
from repro.serving import (Burst, LoadWatermarkPolicy, PoolEvent, Request,
                           RequestQueue, Router, ServingMetrics,
                           SignatureBatcher, TrafficSim, percentile)


def fresh_router(**policy_kw):
    dyn = DynamicScheduler(paper_system("pcie4"), PerfModel(), mode="perf")
    kw = dict(low=0.3, high=0.7, window=10.0)
    kw.update(policy_kw)
    return Router(dyn, batcher=SignatureBatcher(max_batch=8, max_wait=0.25),
                  policy=LoadWatermarkPolicy(**kw))


def req(rid, wl, t, deadline=None):
    return Request(rid, wl, t, deadline=deadline)


WL_A = gcn_workload(DATASETS["OA"])
WL_B = gcn_workload(DATASETS["OP"])


# ---------------------------------------------------------------------------
# RequestQueue admission control
# ---------------------------------------------------------------------------
def test_queue_rejects_when_full():
    q = RequestQueue(max_depth=2)
    assert q.admit(req(0, WL_A, 0.0), 0.0)
    assert q.admit(req(1, WL_A, 0.0), 0.0)
    assert not q.admit(req(2, WL_A, 0.0), 0.0)
    assert q.stats.rejected_full == 1
    assert len(q) == 2


def test_queue_rejects_hopeless_deadline():
    q = RequestQueue()
    # deadline already unreachable given the estimated wait
    assert not q.admit(req(0, WL_A, 0.0, deadline=1.0), 0.0, est_wait=2.0)
    assert q.stats.rejected_deadline == 1
    assert q.admit(req(1, WL_A, 0.0, deadline=1.0), 0.0, est_wait=0.5)


def test_queue_expires_aged_requests():
    q = RequestQueue()
    q.admit(req(0, WL_A, 0.0, deadline=1.0), 0.0)
    q.admit(req(1, WL_A, 0.0, deadline=5.0), 0.0)
    dead = q.expire(2.0)
    assert [r.rid for r in dead] == [0]
    assert [r.rid for r in q] == [1]
    assert q.stats.expired == 1


# ---------------------------------------------------------------------------
# SignatureBatcher grouping
# ---------------------------------------------------------------------------
def test_batches_are_signature_homogeneous():
    q = RequestQueue()
    b = SignatureBatcher(max_batch=8, max_wait=0.0)
    for i in range(6):                       # interleave two signatures
        q.admit(req(i, WL_A if i % 2 == 0 else WL_B, i * 0.01), i * 0.01)
    batches = b.drain(q, 1.0)
    assert len(batches) == 2
    for batch in batches:
        sigs = {signature(r.wl) for r in batch.requests}
        assert len(sigs) == 1
        assert sigs == {batch.sig}
    assert len(q) == 0


def test_batcher_oldest_first_and_max_batch():
    q = RequestQueue()
    b = SignatureBatcher(max_batch=2, max_wait=0.0)
    q.admit(req(0, WL_B, 0.5), 0.5)          # younger, different signature
    for i in range(1, 4):
        q.admit(req(i, WL_A, 0.0 + i * 1e-3), 0.0)   # older group
    first = b.next_batch(q, 1.0)
    assert [r.rid for r in first.requests] == [1, 2]  # oldest group, capped
    second = b.next_batch(q, 1.0)
    assert [r.rid for r in second.requests] == [3]


def test_batcher_waits_for_fill_or_age():
    q = RequestQueue()
    b = SignatureBatcher(max_batch=4, max_wait=1.0)
    q.admit(req(0, WL_A, 0.0), 0.0)
    assert b.next_batch(q, 0.5) is None      # underfull and young: hold
    assert len(q) == 1
    got = b.next_batch(q, 1.5)               # aged out: dispatch underfull
    assert got is not None and len(got) == 1


# ---------------------------------------------------------------------------
# watermark policy + metrics helpers
# ---------------------------------------------------------------------------
def test_watermark_hysteresis():
    p = LoadWatermarkPolicy(low=0.3, high=0.7, window=1.0,
                            initial_mode="perf")
    cap = 10.0
    # high load -> perf (unchanged)
    for t in [1.0 + i * 0.1 for i in range(10)]:
        p.observe_arrival(t)
    assert p.update(2.0, cap) == "perf"
    # mid load (util 0.6, between watermarks) keeps the current mode
    for t in (2.5, 2.6, 2.7, 2.8, 2.9):
        p.observe_arrival(t)
    assert p.update(2.9, cap) == "perf"
    # idle window -> energy
    assert p.update(10.0, cap) == "energy"
    # mid load again (util 0.5): hysteresis keeps energy
    for t in [10.2 + i * 0.2 for i in range(5)]:
        p.observe_arrival(t)
    assert p.update(11.0, cap) == "energy"
    assert [m for _, m in p.switches] == ["energy"]


def test_watermark_warmup_guard():
    p = LoadWatermarkPolicy(low=0.3, high=0.7, window=10.0,
                            initial_mode="perf")
    assert p.update(0.1, 10.0) == "perf"     # no history yet: don't flip
    assert p.switches == []


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile([], 99) == 0.0
    assert percentile([7.0], 50) == 7.0


def test_metrics_deadline_misses():
    m = ServingMetrics()
    r1 = req(0, WL_A, 0.0, deadline=1.0)
    r1.finish = 2.0
    r2 = req(1, WL_A, 0.0, deadline=5.0)
    r2.finish = 2.0
    m.record_completion(r1)
    m.record_completion(r2)
    snap = m.snapshot()
    assert snap.completed == 2
    assert snap.deadline_miss_rate == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Router elastic/straggler integration
# ---------------------------------------------------------------------------
def test_router_straggler_demotes_device():
    r = fresh_router()
    r.submit(req(0, WL_B, 0.0), 0.0)
    r.step(1.0)                              # dispatch -> active schedule
    assert r.dyn.active is not None
    stage0 = r.dyn.active.pipeline.stages[0]
    pool0 = r.pool.n_a if stage0.dev.name == "FPGA" else r.pool.n_b
    for _ in range(10):
        if r.observe_stage_time(0, 3.0 * max(stage0.total, 1e-9)):
            break
    pool1 = r.pool.n_a if stage0.dev.name == "FPGA" else r.pool.n_b
    assert pool1 == pool0 - 1
    assert any("straggler" in line for line in r.log)
    # serving continues on the shrunken pool; step(3.0) first reaps the
    # batch deferred from step(1.0) (deferred reaping delivers ready
    # completions at the start of the next cycle), then dispatches rid 1,
    # whose own completion surfaces at drain
    r.submit(req(1, WL_B, 2.0), 2.0)
    done = r.step(3.0)
    assert [x.rid for x in done] == [0]
    done = r.drain(3.0)
    assert [x.rid for x in done] == [1]


def test_router_monitor_follows_schedule_identity():
    """Two workloads can share a mnemonic with very different stage times;
    the straggler monitor must re-baseline per schedule, not per mnemonic."""
    r = fresh_router()
    r.submit(req(0, WL_A, 0.0), 0.0)
    r.step(1.0)
    m1 = r.monitor
    llm = swa_transformer_workload(1024, 512, layers=2)
    r.submit(req(1, llm, 1.0), 1.0)
    r.step(2.0)
    assert r.monitor is not m1
    assert [s.baseline for s in r.monitor.stats] == pytest.approx(
        [s.total for s in r.dyn.active.pipeline.stages])


def test_router_expires_and_drops_queued_request():
    r = fresh_router()
    r.submit(req(0, WL_A, 0.0, deadline=1.0), 0.0)
    r.step(0.1)                 # underfull + young: held
    assert len(r.queue) == 1
    r.step(2.0)                 # deadline passed while queued
    assert len(r.queue) == 0
    assert r.metrics.dropped == 1


# ---------------------------------------------------------------------------
# TrafficSim determinism
# ---------------------------------------------------------------------------
def sim_config(seed, events=()):
    return TrafficSim(seed=seed, duration=30.0, day=30.0, peak_rate=6.0,
                      trough_rate=0.5, events=events,
                      bursts=(Burst(5.0, 7.0, 2.0),))


def test_trafficsim_deterministic_under_fixed_seed():
    snaps, timelines = [], []
    for _ in range(2):
        r = fresh_router()
        sim = sim_config(seed=123)
        snaps.append(sim.run(r))
        timelines.append(sim.timeline)
    assert snaps[0] == snaps[1]
    assert timelines[0] == timelines[1]


def test_trafficsim_seed_changes_stream():
    a = sim_config(seed=1)
    b = sim_config(seed=2)
    sa = a.run(fresh_router())
    sb = b.run(fresh_router())
    assert sa != sb


# ---------------------------------------------------------------------------
# acceptance: the end-to-end serving story
# ---------------------------------------------------------------------------
def test_streaming_end_to_end():
    """Mixed GNN/LLM stream with a diurnal trough and a mid-stream device
    failure: (a) >=2 distinct schedules, (b) automatic perf->energy switch
    when load drops below the watermark, (c) recovery after resize —
    deterministic under the fixed seed."""
    fail_t, rejoin_t = 20.0, 40.0
    r = fresh_router()
    sim = TrafficSim(seed=7, duration=60.0, day=60.0, peak_rate=8.0,
                     trough_rate=0.4,
                     events=(PoolEvent(fail_t, "fail", "FPGA", 2),
                             PoolEvent(rejoin_t, "join", "FPGA", 2)))
    snap = sim.run(r)

    # (a) data-aware serving: distinct signatures -> distinct schedules
    mnems = {d.mnemonic for d in r.dispatches}
    assert len(mnems) >= 2, mnems

    # (b) the trough crosses the low watermark: perf -> energy, and the
    # objective flip is visible both in the policy and the event log
    modes = [m for _, m in r.policy.switches]
    assert "energy" in modes
    assert snap.mode_switches >= 1
    assert any(e.reason == "objective" for e in r.dyn.events)
    # ... and the ramp back to peak restores perf mode
    assert r.dyn.mode == "perf"

    # (c) failure -> resize -> reschedule -> continued serving
    assert any(e.reason == "resize" for e in r.dyn.events)
    during = [d for d in r.dispatches if fail_t <= d.t0 < rejoin_t]
    after = [d for d in r.dispatches if d.t0 >= rejoin_t]
    assert during, "no batches served between failure and rejoin"
    assert after, "no batches served after rejoin"
    # with 2 of 3 FPGAs down, no schedule may use more than 1 FPGA
    for d in during:
        n_f = sum(int(c[0]) for c in _stage_counts(d.mnemonic, "F"))
        assert n_f <= 1, (d.mnemonic, n_f)

    # the stream completes: nothing stuck in the queue, sane telemetry
    assert len(r.queue) == 0
    assert snap.completed > 100
    assert snap.p99_latency >= snap.p50_latency > 0
    assert snap.energy_per_req > 0

    # determinism of the whole story
    r2 = fresh_router()
    sim2 = TrafficSim(seed=7, duration=60.0, day=60.0, peak_rate=8.0,
                      trough_rate=0.4,
                      events=(PoolEvent(fail_t, "fail", "FPGA", 2),
                              PoolEvent(rejoin_t, "join", "FPGA", 2)))
    assert sim2.run(r2) == snap


def _stage_counts(mnemonic, dev_letter):
    """Parse '2F1G'-style mnemonics into per-stage (count, letter) pairs
    for ``dev_letter`` stages."""
    out, i = [], 0
    while i < len(mnemonic):
        j = i
        while mnemonic[j].isdigit():
            j += 1
        if mnemonic[j] == dev_letter:
            out.append((mnemonic[i:j], mnemonic[j]))
        i = j + 1
    return out


# ---------------------------------------------------------------------------
# drain: horizon flush (no admitted request silently dropped)
# ---------------------------------------------------------------------------
def test_drain_flushes_partial_batches_at_horizon():
    """An underfull group whose batch never fills must still be served by
    horizon end — previously it was stranded in the queue (neither
    completed nor counted dropped) when the horizon cut off the aging loop."""
    r = fresh_router()
    r.batcher.max_wait = 10.0               # ages out far beyond the horizon
    r.submit(req(0, WL_A, 0.0, deadline=50.0), 0.0)
    r.submit(req(1, WL_B, 0.0), 0.0)        # second partial group
    done = r.drain(0.0, horizon=1.0)
    assert {x.rid for x in done} == {0, 1}
    assert len(r.queue) == 0
    assert r.metrics.completed == 2
    # flushed at the horizon, not before (they were waiting to fill)
    assert all(d.t0 >= 1.0 for d in r.dispatches)


def test_drain_still_ages_out_groups_inside_horizon():
    r = fresh_router()
    r.submit(req(0, WL_A, 0.0), 0.0)
    done = r.drain(0.0)                     # default huge horizon
    assert [x.rid for x in done] == [0]
    # served via normal max_wait aging, long before any horizon flush
    assert r.dispatches[0].t0 <= r.batcher.max_wait + 1e-9


# ---------------------------------------------------------------------------
# arrival-trace record/replay (TrafficSim.to_jsonl / from_jsonl)
# ---------------------------------------------------------------------------
def test_trafficsim_jsonl_roundtrip(tmp_path):
    sim = sim_config(seed=9)
    snap = sim.run(fresh_router())
    path = tmp_path / "trace.jsonl"
    sim.to_jsonl(path)
    replay = TrafficSim.from_jsonl(path, peak_rate=sim.peak_rate)
    assert len(replay.trace) == len(sim.last_trace) > 0
    for a, b in zip(replay.trace, sim.last_trace):
        assert a.t == pytest.approx(b.t)
        assert a.kind == b.kind
        assert signature(a.wl) == signature(b.wl)
        assert a.deadline == pytest.approx(b.deadline)
    # replaying yields the same number of completions as were admitted
    snap2 = replay.run(fresh_router())
    assert snap2.completed + snap2.dropped == len(replay.trace)
    # second serialization is byte-identical (true round trip)
    path2 = tmp_path / "trace2.jsonl"
    replay.to_jsonl(path2)
    assert path.read_text() == path2.read_text()


def test_checked_in_sample_trace_replays():
    from pathlib import Path
    sample = (Path(__file__).resolve().parent.parent
              / "examples" / "traces" / "sample_mixed.jsonl")
    sim = TrafficSim.from_jsonl(sample, peak_rate=5.0)
    assert len(sim.trace) > 0
    snap = sim.run(fresh_router())
    assert snap.completed == len(sim.trace)


def test_llm_only_stream_uses_transformer_schedules():
    """A pure-LLM burst stream still batches by signature (seq-length
    regimes) and serves under cached schedules."""
    from repro.serving import MixItem
    mix = (MixItem("llm-1k", "llm", 0.5,
                   swa_transformer_workload(1024, 512, layers=2)),
           MixItem("llm-4k", "llm", 0.5,
                   swa_transformer_workload(4096, 512, layers=2)))
    r = fresh_router()
    sim = TrafficSim(seed=3, duration=20.0, day=20.0, peak_rate=6.0,
                     trough_rate=1.0, mix=mix)
    snap = sim.run(r)
    assert snap.completed > 20
    sigs = {d.sig for d in r.dispatches}
    assert len(sigs) == 2                    # both seq regimes served
    # far fewer DP solves than requests (continuous batching win)
    assert r.dyn.dp_solves <= 6
