"""repro.cluster (ISSUE 4 tentpole): transports, controller/worker peers,
heartbeat failure detection, ClusterBackend parity with local execution,
the kill-worker-mid-stream acceptance scenario (zero lost requests), and
deterministic replay from a recorded cluster-event JSONL."""
import pytest

from repro.cluster import (ClusterEvent, ClusterEventLog, Controller,
                           LocalCluster, WorkerCore, inproc_pair, mp_worker,
                           split_pool)
from repro.core import (DATASETS, DynamicScheduler, PerfModel, gcn_workload,
                        paper_system, swa_transformer_workload)
from repro.runtime import AnalyticBackend, ClusterBackend, WorkerLost
from repro.serving import (LoadWatermarkPolicy, Router, SignatureBatcher,
                           TrafficSim)
from replay_harness import Scenario, check_replay_identity

WL_A = gcn_workload(DATASETS["OA"])
WL_L = swa_transformer_workload(1024, 512, layers=2)


def fresh_dyn(mode="perf"):
    return DynamicScheduler(paper_system("pcie4"), PerfModel(), mode=mode)


def cluster_router(*, n_workers=2, script=(), backend="analytic",
                   hb_interval=0.5, hb_timeout=1.5, max_wait=0.25,
                   policy_window=10.0, async_mode=True):
    cluster = LocalCluster(paper_system("pcie4"), n_workers,
                           backend=backend, hb_interval=hb_interval,
                           hb_timeout=hb_timeout, script=script)
    router = Router(fresh_dyn(),
                    batcher=SignatureBatcher(max_batch=16,
                                             max_wait=max_wait),
                    policy=LoadWatermarkPolicy(window=policy_window),
                    backend=cluster.backend(), async_mode=async_mode)
    cluster.attach(router)
    return cluster, router


def diurnal_sim(seed=3, duration=20.0, deadline_slack=None):
    """The diurnal mixed GNN/LLM trace used across the cluster tests."""
    return TrafficSim(seed=seed, duration=duration, day=duration,
                      peak_rate=8.0, trough_rate=0.5,
                      deadline_slack=deadline_slack)


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------
def test_inproc_channel_fifo_roundtrip():
    a, b = inproc_pair()
    for i in range(3):
        a.send({"op": "ping", "echo": i})
    assert b.poll()
    assert [b.recv()["echo"] for _ in range(3)] == [0, 1, 2]
    assert b.recv() is None and not b.poll()
    b.send({"op": "pong"})
    assert a.recv()["op"] == "pong"


def test_mp_transport_smoke_roundtrip():
    """Satellite: the multiprocessing transport carries the same protocol
    through a real child process — ping, prepare, and a submit whose
    report round-trips by pickling."""
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    chan, proc = mp_worker("mp0", {"FPGA": 3, "GPU": 2})
    try:
        chan.send({"op": "ping", "echo": 42})
        pong = chan.recv_wait(timeout=30.0)
        assert pong is not None and pong["op"] == "pong"
        assert pong["echo"] == 42 and pong["wid"] == "mp0"
        chan.send({"op": "prepare", "hid": 0, "schedule": res,
                   "workload": WL_A, "epoch": dyn.epoch})
        assert chan.recv_wait(timeout=30.0)["op"] == "prepared"
        chan.send({"op": "submit", "hid": 0, "sid": 7, "n": 2, "t0": 1.0})
        acc = chan.recv_wait(timeout=30.0)
        assert acc["op"] == "accepted" and len(acc["finishes"]) == 2
        rep = chan.recv_wait(timeout=30.0)
        assert rep["op"] == "report" and rep["sid"] == 7
        # the report crossed a process boundary and still matches the
        # analytic model the controller-side schedule predicts
        local = AnalyticBackend()
        want = local.execute(local.prepare(res, WL_A), 2, 1.0)
        assert rep["report"].finishes == want.finishes
        assert rep["report"].measured == want.measured
        chan.send({"op": "stop"})
    finally:
        proc.join(timeout=30.0)
        if proc.is_alive():            # pragma: no cover - hang guard
            proc.terminate()
    assert proc.exitcode == 0


def test_mp_worker_refuses_device_backend():
    """The chip belongs to one process: a spawned worker must not try to
    build the device backend while its parent may hold the chip."""
    with pytest.raises(ValueError, match="in-process"):
        mp_worker("mp0", {"FPGA": 3, "GPU": 2}, backend="pallas")


# ---------------------------------------------------------------------------
# worker core + controller basics
# ---------------------------------------------------------------------------
def test_split_pool_round_robins_devices():
    assert split_pool(paper_system("pcie4"), 2) == [
        {"FPGA": 2, "GPU": 1}, {"FPGA": 1, "GPU": 1}]
    assert split_pool(paper_system("pcie4"), 1) == [{"FPGA": 3, "GPU": 2}]


def test_worker_latency_injection_scales_measured_only():
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    core = WorkerCore("w", {"FPGA": 3, "GPU": 2})
    core.handle({"op": "prepare", "hid": 0, "schedule": res,
                 "workload": WL_A, "epoch": 0})
    base = core.handle({"op": "submit", "hid": 0, "sid": 0, "n": 2,
                        "t0": 0.0})[1]["report"]
    core.handle({"op": "latency", "factor": 4.0})
    slow = core.handle({"op": "submit", "hid": 0, "sid": 1, "n": 2,
                        "t0": 0.0})[1]["report"]
    assert slow.finishes == base.finishes          # simulated clock intact
    assert slow.measured == pytest.approx(
        tuple(4.0 * t for t in base.measured))     # measurements scaled


def test_heartbeat_miss_detection_and_failure_cascade():
    """kill -> silence -> heartbeat-miss at hb_timeout -> per-pool
    on_failure on the listener, in deterministic order."""
    calls = []

    class Listener:
        def on_failure(self, dev, count):
            calls.append(("fail", dev, count))

        def on_join(self, dev, count):
            calls.append(("join", dev, count))

    ctrl = Controller(hb_interval=0.5, hb_timeout=1.5,
                      script=(ClusterEvent(2.0, "kill", "w1"),))
    ctrl.listeners.append(Listener())
    ctrl.add_worker("w0", {"FPGA": 2, "GPU": 1}, AnalyticBackend())
    ctrl.add_worker("w1", {"FPGA": 1, "GPU": 1}, AnalyticBackend())
    t = 0.0
    while t < 5.0:
        ctrl.tick(t)
        t += 0.25
    assert calls == [("fail", "FPGA", 1), ("fail", "GPU", 1)]
    kinds = ctrl.events.kinds()
    assert kinds == ["register", "register", "kill", "heartbeat-miss",
                     "failure", "failure"]
    miss = next(e for e in ctrl.events if e.kind == "heartbeat-miss")
    assert miss.worker == "w1" and miss.detail["via"] == "heartbeat"
    # detection happened one timeout after the last heartbeat, not sooner
    assert miss.t >= 2.0 + 1.5 - 0.5    # kill + timeout - hb granularity
    assert not ctrl.links["w1"].alive and ctrl.links["w0"].alive


def test_scripted_join_announces_new_capacity():
    joins = []

    class Listener:
        def on_join(self, dev, count):
            joins.append((dev, count))

        def on_failure(self, dev, count):   # pragma: no cover - unused
            raise AssertionError

    ctrl = Controller(script=(ClusterEvent(
        1.0, "join", "w9", {"pool": {"FPGA": 1}}),),
        backend_factory=AnalyticBackend)
    ctrl.listeners.append(Listener())
    ctrl.add_worker("w0", {"FPGA": 2, "GPU": 2}, AnalyticBackend())
    ctrl.tick(0.0)
    assert joins == []
    ctrl.tick(1.0)
    assert joins == [("FPGA", 1)]
    assert "w9" in ctrl.links and ctrl.links["w9"].alive
    assert "join" in ctrl.events.kinds()


def test_event_log_jsonl_roundtrip(tmp_path):
    log = ClusterEventLog([
        ClusterEvent(0.0, "register", "w0", {"pool": {"FPGA": 2}}),
        ClusterEvent(6.0, "kill", "w0"),
        ClusterEvent(7.5, "heartbeat-miss", "w0",
                     {"via": "heartbeat", "last_hb": 6.0}),
        ClusterEvent(8.0, "latency", "w1", {"factor": 4.0}),
    ])
    path = tmp_path / "events.jsonl"
    log.to_jsonl(path)
    back = ClusterEventLog.from_jsonl(path)
    assert list(back) == list(log)
    assert back.script() == (log.events[1], log.events[3])


# ---------------------------------------------------------------------------
# ClusterBackend parity with local execution (satellite)
# ---------------------------------------------------------------------------
def _local_run(seed=3):
    router = Router(fresh_dyn(),
                    batcher=SignatureBatcher(max_batch=16, max_wait=0.25),
                    policy=LoadWatermarkPolicy(window=10.0))
    snap = diurnal_sim(seed=seed).run(router)
    return router, snap


def test_cluster_parity_with_analytic_backend():
    """ClusterBackend over the in-process transport: identical completion
    ordering AND identical telemetry snapshot to plain AnalyticBackend on
    the diurnal mixed trace — distributing execution must not perturb the
    simulated clock, the dispatch decisions, or the measured feed."""
    local_r, local_snap = _local_run()
    cluster, cr = cluster_router()
    snap = diurnal_sim().run(cr)
    assert snap == local_snap
    assert sorted(cr.metrics.latencies) == sorted(local_r.metrics.latencies)
    recs = [(d.t0, d.sig, d.cell, d.n, d.finish) for d in cr.dispatches]
    recs_local = [(d.t0, d.sig, d.cell, d.n, d.finish)
                  for d in local_r.dispatches]
    assert recs == recs_local
    # and the work really crossed hosts: both workers served cells
    assert all(link.assignments > 0
               for link in cluster.controller.links.values())


def test_cluster_cross_worker_overlap():
    cluster, cr = cluster_router()
    snap = diurnal_sim().run(cr)
    assert snap.completed > 0
    assert cluster.cross_worker_overlap() > 1.0    # concurrent hosts


def test_cluster_latency_injection_demotes_through_monitors():
    """A scripted per-worker slowdown rides the measured-stage-time path:
    the affected cells' monitors flag, a device demotes, and serving
    reschedules — the straggler loop works across the cluster boundary."""
    cluster, cr = cluster_router(
        script=(ClusterEvent(0.0, "latency", "w0", {"factor": 4.0}),
                ClusterEvent(0.0, "latency", "w1", {"factor": 4.0})))
    snap = diurnal_sim().run(cr)
    assert any("straggler flagged" in line for line in cr.log)
    assert any(e.reason == "resize" for e in cr.dyn.events)
    assert snap.completed > 0 and len(cr.queue) == 0


# ---------------------------------------------------------------------------
# acceptance: kill a worker mid-diurnal-stream, replay it deterministically
# ---------------------------------------------------------------------------
KILL_T = 6.0


def test_kill_worker_mid_stream_zero_lost_requests(tmp_path):
    # the record -> replay dance (zero-lost accounting, telemetry/event
    # equality, byte-identical JSONL) lives in the shared harness now
    sc = Scenario(script=(ClusterEvent(KILL_T, "kill", "w1"),))
    rec, _ = check_replay_identity(sc, tmp_path)
    cluster, cr, snap = rec.cluster, rec.router, rec.snap

    # before the kill both workers served concurrently
    assert cluster.cross_worker_overlap() > 1.0

    # heartbeat-miss -> on_failure -> resize -> reschedule on survivors
    kinds = cluster.events.kinds()
    assert "heartbeat-miss" in kinds and "failure" in kinds
    assert any(e.reason == "resize" for e in cr.dyn.events)
    lost_pool = cluster.controller.links["w1"].pool
    assert cr.pool.n_a == 3 - lost_pool.get("FPGA", 0)
    assert cr.pool.n_b == 2 - lost_pool.get("GPU", 0)
    # serving continued after the failure cascade
    detect_t = next(e.t for e in cluster.events
                    if e.kind == "heartbeat-miss")
    assert any(d.t0 > detect_t for d in cr.dispatches)

    # batches in flight on the dead worker were re-queued, not dropped
    assert snap.requeued > 0
    # only the scripted kill survives into the extracted input script
    assert cluster.events.script() == sc.script


def test_kill_worker_same_tick_admissions_requeued():
    """Satellite (drain/queue fix): requests admitted in the same tick as
    the failure — and batches submitted into the detection window — are
    re-queued and served, never silently dropped, even when the stream
    ends before detection (the drain's event-driven clock must reach the
    heartbeat deadline)."""
    # kill just before stream end: detection + re-queue happen in drain
    cluster, cr = cluster_router(script=(ClusterEvent(19.8, "kill", "w1"),))
    snap = diurnal_sim().run(cr)
    assert cr.queue.stats.admitted == snap.completed
    assert snap.dropped == 0
    assert len(cr.queue) == 0 and cr.engine.inflight == []


def test_sync_mode_lost_batch_requeues_not_crashes():
    """Blocking dispatch onto a crashed-but-undetected worker: the RPC
    failure detector declares it lost mid-dispatch, the batch comes back
    as report=None, and the Router re-queues it — no crash, no loss."""
    from repro.serving import Request
    cluster, cr = cluster_router(script=(ClusterEvent(5.0, "kill", "w1"),),
                                 async_mode=False, max_wait=0.0)
    for i in range(2):
        cr.submit(Request(i, WL_A, 0.0), 0.0)       # cell -> w0
        cr.submit(Request(10 + i, WL_L, 0.0), 0.0)  # cell -> w1
    cr.step(0.0)
    assert cr.metrics.completed == 4
    t = 0.0
    while t < 5.5:                  # steady ticks keep heartbeats fresh;
        t += 0.25                   # the kill lands at t=5.0, detection
        cr.step(t)                  # not due before 5.0 + hb_timeout
    for i in range(2):              # w1's cell gets a batch while it is
        cr.submit(Request(20 + i, WL_L, 5.5), 5.5)  # dead but undetected
    cr.step(5.6)
    assert any("lost batch" in line for line in cr.log)
    assert cr.metrics.requeued == 2
    cr.drain(6.0)
    assert cr.queue.stats.admitted == cr.metrics.completed == 6
    miss = next(e for e in cluster.events if e.kind == "heartbeat-miss")
    assert miss.detail["via"] == "rpc"


def test_cluster_survives_with_single_worker():
    cluster, cr = cluster_router(n_workers=1)
    snap = diurnal_sim().run(cr)
    assert snap.completed > 0
    assert cr.queue.stats.admitted == snap.completed


def test_submit_to_lost_worker_fails_future_immediately():
    """A stale handle routed to an already-declared-lost worker must not
    strand its batch: the future is ready at once and raises WorkerLost
    (-> re-queue), instead of waiting on a detector that already fired."""
    ctrl = Controller()
    w0 = ctrl.add_worker("w0", {"FPGA": 2, "GPU": 1}, AnalyticBackend())
    ctrl.add_worker("w1", {"FPGA": 1, "GPU": 1}, AnalyticBackend())
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    backend = ClusterBackend(ctrl)
    handle = backend.prepare(res, WL_A, epoch=dyn.epoch)
    assert handle.payload[0] == "w0"
    w0.peer.fail()
    ctrl.declare_lost("w0", 1.0, via="heartbeat")
    fut = backend.submit(handle, 2, 2.0)
    assert fut.ready()
    with pytest.raises(WorkerLost):
        fut.result()


def test_place_raises_when_all_workers_lost():
    ctrl = Controller()
    link = ctrl.add_worker("w0", {"FPGA": 3, "GPU": 2}, AnalyticBackend())
    dyn = fresh_dyn()
    res = dyn.submit(WL_A)
    backend = ClusterBackend(ctrl)
    handle = backend.prepare(res, WL_A, epoch=dyn.epoch)   # places fine
    assert handle.payload[0] == "w0"
    link.peer.fail()
    ctrl.declare_lost("w0", 1.0, via="heartbeat")
    with pytest.raises(WorkerLost):
        backend.prepare(res, WL_A, epoch=dyn.epoch)
