"""Streaming-serving benchmark: the signature-aware router under traffic.

Three questions a production deployment asks of the serving stack:
  1. router overhead — how many simulated requests/sec the host-side control
     loop (queue + batcher + cached DP dispatch) pushes per wall-second,
  2. batching leverage — DP solves per 1k requests (cache hit rate) as the
     traffic mix gets more irregular,
  3. tail behavior — p50/p99 latency and deadline misses across load levels
     from trough to saturation, with and without a mid-stream failure.

All dispatch goes through the ExecutionBackend protocol; ``--backend
pallas`` runs every batch on the real shard_map pipeline (a one-device
chain of per-stage jits where fewer devices are visible) instead of the
analytic model. Rows report the **overlap ratio** (pipeline busy-time /
wall-time over the union of execution intervals, on the simulated
clock): > 1.0 means the Engine had signature cells executing
concurrently on disjoint device subsets. The
``diurnal-sync`` row replays the diurnal stream with blocking per-batch
dispatch — by design its simulated-clock columns (latency, energy,
overlap) are identical to the async row (the ordering-parity invariant);
what can differ is ``sim_req_per_wall_s``, the host-side cost of the
dispatch path, and with ``--backend pallas`` the async row overlaps
device work with the control loop.

The ``cluster-2worker`` row serves the same diurnal stream through the
``repro.cluster`` control plane (two in-process workers splitting the
device pool) and additionally reports the **cross-worker overlap** (sum of
per-worker busy coverage over cluster-wide coverage; > 1.0 = hosts
executing concurrently); ``cluster-kill-worker`` kills one worker
mid-stream and shows the heartbeat-miss -> reschedule -> re-queue path in
the ``requeued`` column.

The ``slow-host-*`` rows run a heterogeneous fleet (worker w1 is a
60x-slow host, ``HostProfile``; docs/heterogeneity.md) under saturating
load: ``slow-host-oblivious`` plans as if the fleet were uniform (legacy
placement; the tail explodes), ``slow-host-steal-only`` adds controller
work stealing on top of oblivious placement (the ``steals`` column goes
hot), and ``slow-host-aware+steal`` adds effective-throughput placement +
per-host DP re-solves — throughput should recover to the uniform
cluster's level.

The ``learned-slow-host`` row reruns the 60x-slow host with **no**
declared profile: the ``OnlineHostEstimator`` (docs/fleet.md) must
discover it from measured-vs-expected stage times — the
``learned_scale_err`` column is the published scale's relative error vs
ground truth, and the row is held to >= 90% of the declared
aware+steal throughput. ``autoscale-diurnal`` serves the diurnal curve
with the Holt arrival forecaster and ``PredictiveAutoscaler``;
``mode_flip_lead_s`` is how much earlier the look-ahead policy flipped
mode than the reactive twin.

The ``replicated-hot-cell`` row skews 90% of a saturating stream onto
one signature so a single cell is the bottleneck, then lets the
controller promote it to replicas on both workers (``--replicate-hot``;
docs/cluster.md) — acceptance holds the replicated run to >= 1.3x the
unreplicated twin's throughput.

The ``governor-diurnal`` row serves an energy-rich mix under the
``ParetoGovernor`` (continuous frontier walk; docs/energy.md) and is
held to >= 15% lower ``joules_per_req`` than the pinned always-perf
twin at the same deadline-miss rate; ``energy-capped`` clamps the fleet
to 70% of the perf-endpoint draw and is held to ``watts_p95`` <= cap at
the pinned always-energy twin's service level. Both report the new
``watts_mean``/``watts_p95``/``joules_per_req``/``opoint_switches``
columns (zero on ungoverned rows).

``--smoke`` runs one short diurnal scenario (plus cluster-2worker,
slow-host, learned-slow-host, replicated-hot-cell, autoscale-diurnal,
governor-diurnal, and energy-capped rows) and writes
``BENCH_serving.json`` (throughput, p99, energy/req, cross-worker
overlap, steal recovery, learned-profile accuracy, watts/J-per-req) at
the repo root — the artifact CI uploads so the serving-perf trajectory
accumulates across commits.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core import DynamicScheduler, PerfModel, paper_system
from repro.runtime import make_backend
from repro.serving import (LoadWatermarkPolicy, PoolEvent, Router,
                           SignatureBatcher, TrafficSim)

from .common import Timer, write_json

REPO = Path(__file__).resolve().parent.parent

# load level for the slow-host scenarios: high enough that pipeline busy
# time (not batching wait) dominates, so host heterogeneity is visible
SLOW_PEAK = 24.0


# load level + deadline for the replication scenario: hot enough that
# one cell's single-batch-at-a-time service is the bottleneck, and tight
# enough deadlines that the unreplicated twin's queue wait turns into
# drops (the capacity the replica recovers)
REP_PEAK = 320.0
REP_SLACK = 2.0


def _hot_mix() -> tuple:
    """Skewed traffic for the replication scenario: one signature takes
    90% of arrivals, so a single cell (one worker) is the bottleneck —
    exactly the shape hot-cell replication exists for."""
    from repro.core.workload import DATASETS, gcn_workload, \
        swa_transformer_workload
    from repro.serving.traffic import MixItem
    return (
        MixItem("gcn-arxiv", "gnn", 0.90, gcn_workload(DATASETS["OA"])),
        MixItem("llm-swa-1k", "llm", 0.10,
                swa_transformer_workload(1024, 512, layers=2)),
    )


def _energy_mix() -> tuple:
    """Traffic for the governor scenarios: weighted toward swa-4k, whose
    Pareto frontier on the engine's fair-share pool has several real
    rungs between the perf and energy endpoints — the room the
    ``ParetoGovernor``'s frontier walk actually exploits."""
    from repro.core.workload import DATASETS, gcn_workload, \
        swa_transformer_workload
    from repro.serving.traffic import MixItem
    return (
        MixItem("llm-swa-4k", "llm", 0.75,
                swa_transformer_workload(4096, 256)),
        MixItem("gcn-arxiv", "gnn", 0.25, gcn_workload(DATASETS["OA"])),
    )


def _swa_mix() -> tuple:
    """Single-signature swa-4k traffic for the power-cap scenario: the
    whole fleet draw rides one multi-rung frontier, so the 70%-of-peak
    cap binds exactly when demand would upshift to the perf endpoint."""
    from repro.core.workload import swa_transformer_workload
    from repro.serving.traffic import MixItem
    return (MixItem("llm-swa-4k", "llm", 1.0,
                    swa_transformer_workload(4096, 256)),)


def _cap_watts(frac: float = 0.7) -> float:
    """``frac`` x the perf-endpoint draw of the swa-4k frontier on the
    engine's fair-share pool (max_cells=2) — the observed perf-mode peak
    watts of the ``_swa_mix`` scenario, derived analytically so the cap
    tracks model changes instead of hard-coding 351.4."""
    import math

    from repro.core.workload import swa_transformer_workload
    from repro.energy import FrontierCache
    sysm = paper_system("pcie4")
    share = tuple(math.ceil(c / 2) for _, c in sysm.pools)
    dyn = DynamicScheduler(sysm, PerfModel(), mode="perf")
    front = FrontierCache(dyn).frontier(swa_transformer_workload(4096, 256),
                                        pool=share)
    return round(frac * front[0].watts, 6)


def _learned_err(est, truth_profiles) -> float | None:
    """Max relative error of the published compute scales against the
    injected ground truth; an unpublished truth-profiled host counts at
    its belief (scale 1.0), so a silent estimator scores badly instead
    of not at all."""
    if est is None or not truth_profiles:
        return None
    errs = []
    for wid, truth in truth_profiles.items():
        ts = truth if isinstance(truth, (int, float)) else truth.compute_scale
        prof = est.published.get(wid)
        learned = prof.compute_scale if prof is not None else 1.0
        errs.append(abs(learned / ts - 1.0))
    return round(max(errs), 4)


def _run(duration, peak, trough, *, seed=0, events=(), mix=None,
         backend="analytic", max_cells=2, async_mode=True, cluster=0,
         cluster_script=(), profiles=None, steal=False, host_aware=True,
         truth_profiles=None, learn=False, autoscale=False,
         forecast_horizon=0.0, mode_cooldown=0.0, replicate_hot=0,
         migrate=False, deadline_slack=30.0, tracer=None,
         snapshot_every=None, governor=False, power_cap=None,
         energy_slo=None, mode="perf", pin_mode=False):
    """One scenario. ``cluster=N`` routes execution through the
    repro.cluster control plane (N in-process workers splitting the pool,
    each running a local ``backend``); ``cluster_script`` injects cluster
    events (e.g. a scripted worker kill). ``profiles`` declares per-worker
    ``HostProfile``s (heterogeneous fleet); ``steal``/``host_aware``
    select the controller's placement intelligence
    (docs/heterogeneity.md). ``truth_profiles`` injects ground-truth host
    physics the control plane cannot see and ``learn`` turns on the
    ``OnlineHostEstimator`` that discovers them (docs/fleet.md);
    ``forecast_horizon`` swaps the reactive watermark policy for the
    Holt look-ahead one, and ``autoscale`` adds the
    ``PredictiveAutoscaler`` on top of that forecast. ``tracer`` wires a
    ``repro.obs.Tracer`` through the stack (the tracing-overhead row);
    ``snapshot_every`` appends periodic ``MetricsSnapshot`` rows (JSON
    round-tripped) under the ``snapshots`` key. ``governor`` attaches the
    ``ParetoGovernor`` (continuous frontier walk; implies the forecaster),
    ``power_cap`` adds a fleet ``PowerBudget`` in watts, and
    ``energy_slo`` a J/request ceiling (docs/energy.md)."""
    perf = PerfModel()
    dyn = DynamicScheduler(paper_system("pcie4"), perf, mode=mode)
    cl = None
    if cluster:
        from repro.cluster import LocalCluster
        cl = LocalCluster(paper_system("pcie4"), cluster, backend=backend,
                          script=cluster_script, profiles=profiles,
                          truth_profiles=truth_profiles,
                          steal=steal, host_aware=host_aware,
                          replicate_hot=replicate_hot, migrate=migrate,
                          perf=perf)
        exec_backend = cl.backend()
    else:
        exec_backend = make_backend(backend)
    forecaster = None
    if forecast_horizon or autoscale or governor:
        from repro.fleet import ArrivalForecaster
        forecaster = ArrivalForecaster(horizon=forecast_horizon or 5.0)
    # pin_mode holds the watermark policy at ``mode`` for the whole run
    # (watermarks no util can cross) — the governor rows' fixed
    # always-perf / always-energy comparison baselines
    policy = (LoadWatermarkPolicy(low=-1.0, high=float("inf"),
                                  initial_mode=mode, window=10.0,
                                  forecaster=forecaster)
              if pin_mode else
              LoadWatermarkPolicy(window=10.0, forecaster=forecaster,
                                  cooldown=mode_cooldown))
    router = Router(dyn, batcher=SignatureBatcher(max_batch=16,
                                                  max_wait=0.25),
                    policy=policy,
                    backend=exec_backend, max_cells=max_cells,
                    async_mode=async_mode, tracer=tracer)
    est = scaler = None
    if cl is not None:
        cl.attach(router)
        if learn:
            from repro.fleet import OnlineHostEstimator
            est = OnlineHostEstimator().attach(router, cl.controller)
        if autoscale:
            from repro.fleet import PredictiveAutoscaler
            scaler = PredictiveAutoscaler(forecaster)
            scaler.attach(router, cl.controller)
    gov = None
    if governor:
        from repro.energy import ParetoGovernor, PowerBudget
        budget = PowerBudget(power_cap) if power_cap is not None else None
        gov = ParetoGovernor(budget=budget, energy_slo_j=energy_slo)
        gov.attach(router, cl.controller if cl is not None else None)
    sim = TrafficSim(seed=seed, duration=duration, peak_rate=peak,
                     trough_rate=trough, day=duration, events=events,
                     mix=mix, deadline_slack=deadline_slack,
                     snapshot_every=snapshot_every)
    t0 = time.time()
    snap = sim.run(router)
    wall = time.time() - t0
    if tracer is not None:
        router.tracer.flush(router.metrics.t_last)
    n_solves = dyn.dp_solves            # actual DP runs, not event count
    total = snap.completed + snap.dropped
    row = {
        "backend": f"cluster({backend})x{cluster}" if cluster else backend,
        "requests": total,
        "completed": snap.completed,
        "dropped": snap.dropped,
        "sim_req_per_wall_s": round(total / wall, 1) if wall > 0 else 0.0,
        "wall_s": round(wall, 4),
        "throughput_req_s": round(snap.throughput, 3),
        "p50_ms": round(snap.p50_latency * 1e3, 2),
        "p99_ms": round(snap.p99_latency * 1e3, 2),
        "energy_per_req_J": round(snap.energy_per_req, 3),
        "deadline_miss": round(snap.deadline_miss_rate, 4),
        "dp_reschedules": n_solves,
        "dp_per_1k_req": round(1e3 * n_solves / max(total, 1), 2),
        # wall-clock cost of one placement decision (DP lookup/solve +
        # cell acquire + backend dispatch) — the scheduler self-metric
        "place_ms_p50": snap.place_ms_p50,
        "place_ms_p99": snap.place_ms_p99,
        "mode_switches": snap.mode_switches,
        "evictions": router.engine.evictions,
        # busy-time / wall-time over the union of execution intervals:
        # > 1.0 means signature cells executed concurrently (async engine)
        "overlap_ratio": round(snap.overlap_ratio, 3),
        # per-worker busy coverage / cluster-wide coverage: > 1.0 means
        # workers (hosts) executed concurrently — 0.0 for non-cluster rows
        "cross_worker_overlap": (round(cl.cross_worker_overlap(), 3)
                                 if cl is not None else 0.0),
        "requeued": snap.requeued,
        "steals": snap.steals,
        "measured_stage_s": round(snap.measured_stage_s, 3),
        "schedules": sorted(set(d.mnemonic for d in router.dispatches)),
        # max relative error of the published learned compute scale vs
        # the injected ground truth (None when not learning)
        "learned_scale_err": _learned_err(est, truth_profiles),
        # first perf/energy flip (sim s); the smoke derives the
        # forecaster's mode_flip_lead_s from the reactive twin's value
        "first_mode_switch_s": (round(router.policy.switches[0][0], 3)
                                if router.policy.switches else None),
        "autoscale_actions": (len([a for a in scaler.actions
                                   if a[1] in ("park", "unpark")])
                              if scaler is not None else 0),
        "prewarms": (len([a for a in scaler.actions if a[1] == "prewarm"])
                     if scaler is not None else 0),
        # hot-cell replication + live migration (derived cluster events)
        "replicas": (sum(1 for e in cl.events if e.kind == "replicate")
                     if cl is not None else 0),
        "migrations": (sum(1 for e in cl.events if e.kind == "migrate")
                       if cl is not None else 0),
        # energy governance (repro.energy): modeled fleet draw over the
        # governor's post-enforcement power samples, J per completed
        # request, and the number of operating-point moves it made
        "watts_mean": snap.watts_mean,
        "watts_p95": snap.watts_p95,
        "joules_per_req": snap.joules_per_req,
        "opoint_switches": snap.opoint_switches,
    }
    if snapshot_every is not None:
        # one cumulative MetricsSnapshot per window, round-tripped
        # through to_json/from_json so the artifact rows are exactly
        # what a consumer reloading them would see
        from repro.serving.metrics import MetricsSnapshot
        row["snapshots"] = [
            MetricsSnapshot.from_json(s.to_json()).as_dict()
            for s in sim.snapshots]
    return row


def smoke(*, backend: str = "analytic",
          out: Path | None = None) -> dict:
    """Short diurnal run -> BENCH_serving.json for the CI perf artifact.
    Includes a ``cluster-2worker`` row so the perf trajectory tracks the
    cross-worker overlap ratio across commits."""
    r = _run(30.0, 8.0, 0.5, backend=backend, snapshot_every=10.0)
    bench = {
        "bench": "serving_stream_smoke",
        "backend": backend,
        "throughput_req_s": r["throughput_req_s"],
        "p99_ms": r["p99_ms"],
        "p50_ms": r["p50_ms"],
        "energy_per_req_J": r["energy_per_req_J"],
        "completed": r["completed"],
        "deadline_miss": r["deadline_miss"],
        "dp_per_1k_req": r["dp_per_1k_req"],
        "place_ms_p50": r["place_ms_p50"],
        "place_ms_p99": r["place_ms_p99"],
        "sim_req_per_wall_s": r["sim_req_per_wall_s"],
        "overlap_ratio": r["overlap_ratio"],
        "measured_stage_s": r["measured_stage_s"],
        # one cumulative MetricsSnapshot per 10s drain window (round-
        # tripped through MetricsSnapshot.to_json/from_json)
        "snapshots": r["snapshots"],
    }
    # tracing overhead: the same diurnal scenario with a full span bus
    # attached (MemorySink keeps disk noise out). Recorded, not asserted
    # here — wall time on shared CI runners is noisy; the acceptance
    # check lives in the test suite with generous headroom.
    from repro.obs import MemorySink, Tracer
    sink = MemorySink()
    tr = _run(30.0, 8.0, 0.5, backend=backend, tracer=Tracer(sink))
    bench["tracing"] = {
        "disabled_wall_s": r["wall_s"],
        "enabled_wall_s": tr["wall_s"],
        "overhead_frac": (round(tr["wall_s"] / r["wall_s"] - 1.0, 4)
                          if r["wall_s"] > 0 else 0.0),
        "spans": len(sink.records),
        "throughput_req_s": tr["throughput_req_s"],
    }
    c = _run(30.0, 8.0, 0.5, backend=backend, cluster=2)
    bench["cluster-2worker"] = {
        "throughput_req_s": c["throughput_req_s"],
        "p99_ms": c["p99_ms"],
        "completed": c["completed"],
        "overlap_ratio": c["overlap_ratio"],
        "cross_worker_overlap": c["cross_worker_overlap"],
        "sim_req_per_wall_s": c["sim_req_per_wall_s"],
    }
    # heterogeneity trajectory: slow host planned around (aware + steal)
    # vs planned into (oblivious) — the artifact tracks the recovered
    # throughput and the steal volume across commits
    slow = {"w1": 60.0}
    obl = _run(30.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
               profiles=slow, host_aware=False)
    rec = _run(30.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
               profiles=slow, steal=True)
    bench["slow-host"] = {
        "oblivious_throughput_req_s": obl["throughput_req_s"],
        "oblivious_p99_ms": obl["p99_ms"],
        "aware_steal_throughput_req_s": rec["throughput_req_s"],
        "aware_steal_p99_ms": rec["p99_ms"],
        "steals": rec["steals"],
    }
    # learned slow host: the SAME 60x host, but NO declared profiles —
    # the OnlineHostEstimator must discover it from measured-vs-expected
    # stage times; the artifact tracks how close the learned run gets to
    # the declared aware+steal row (acceptance: >= 90%) and the learned
    # scale's relative error (acceptance: <= 15%)
    lrn = _run(30.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
               truth_profiles=slow, learn=True, steal=True)
    declared = rec["throughput_req_s"]
    bench["learned-slow-host"] = {
        "throughput_req_s": lrn["throughput_req_s"],
        "p99_ms": lrn["p99_ms"],
        "learned_scale_err": lrn["learned_scale_err"],
        "vs_declared": (round(lrn["throughput_req_s"] / declared, 3)
                        if declared else 0.0),
        "steals": lrn["steals"],
    }
    assert lrn["throughput_req_s"] >= 0.9 * declared, bench["learned-slow-host"]
    assert (lrn["learned_scale_err"] is not None
            and lrn["learned_scale_err"] <= 0.15), bench["learned-slow-host"]
    # predictive autoscaling on the diurnal curve: forecast-driven mode
    # flips (lead vs the reactive cluster-2worker twin above — positive =
    # the forecaster flipped earlier) plus park/unpark + prewarm volume
    fcast = _run(30.0, 8.0, 0.5, backend=backend, cluster=2,
                 autoscale=True, forecast_horizon=5.0, mode_cooldown=5.0)
    lead = None
    if (fcast["first_mode_switch_s"] is not None
            and c["first_mode_switch_s"] is not None):
        lead = round(c["first_mode_switch_s"]
                     - fcast["first_mode_switch_s"], 3)
    bench["autoscale-diurnal"] = {
        "throughput_req_s": fcast["throughput_req_s"],
        "p99_ms": fcast["p99_ms"],
        "mode_flip_lead_s": lead,
        "autoscale_actions": fcast["autoscale_actions"],
        "prewarms": fcast["prewarms"],
    }
    # hot-cell replication: one signature takes 90% of a saturating
    # stream, so one worker's cell is the bottleneck; --replicate-hot 2
    # promotes it to both workers and dispatch routes each batch to the
    # replica with the lowest estimated wait. Acceptance: the replicated
    # run clears >= 1.3x the unreplicated twin's throughput.
    base = _run(30.0, REP_PEAK, 8.0, backend=backend, cluster=2,
                mix=_hot_mix(), forecast_horizon=5.0,
                deadline_slack=REP_SLACK)
    rep = _run(30.0, REP_PEAK, 8.0, backend=backend, cluster=2,
               mix=_hot_mix(), forecast_horizon=5.0,
               deadline_slack=REP_SLACK, replicate_hot=2)
    bench["replicated-hot-cell"] = {
        "baseline_throughput_req_s": base["throughput_req_s"],
        "baseline_p99_ms": base["p99_ms"],
        "baseline_dropped": base["dropped"],
        "throughput_req_s": rep["throughput_req_s"],
        "p99_ms": rep["p99_ms"],
        "dropped": rep["dropped"],
        "speedup": (round(rep["throughput_req_s"]
                          / base["throughput_req_s"], 3)
                    if base["throughput_req_s"] else 0.0),
        "replicas": rep["replicas"],
        "migrations": rep["migrations"],
    }
    assert rep["throughput_req_s"] >= 1.3 * base["throughput_req_s"], \
        bench["replicated-hot-cell"]
    # continuous Pareto governor on the diurnal curve: vs a pinned
    # always-perf twin, the frontier walk must cut J/req by >= 15% while
    # matching the deadline SLO (docs/energy.md). Acceptance per ISSUE 9.
    gbase = _run(30.0, 8.0, 0.5, seed=3, mix=_energy_mix(),
                 backend=backend, pin_mode=True)
    gov = _run(30.0, 8.0, 0.5, seed=3, mix=_energy_mix(),
               backend=backend, governor=True)
    bench["governor-diurnal"] = {
        "always_perf_joules_per_req": gbase["joules_per_req"],
        "always_perf_deadline_miss": gbase["deadline_miss"],
        "joules_per_req": gov["joules_per_req"],
        "deadline_miss": gov["deadline_miss"],
        "throughput_req_s": gov["throughput_req_s"],
        "watts_mean": gov["watts_mean"],
        "watts_p95": gov["watts_p95"],
        "opoint_switches": gov["opoint_switches"],
        "joules_reduction": (round(1.0 - gov["joules_per_req"]
                                   / gbase["joules_per_req"], 4)
                             if gbase["joules_per_req"] else 0.0),
    }
    assert gov["joules_per_req"] <= 0.85 * gbase["joules_per_req"], \
        bench["governor-diurnal"]
    assert gov["deadline_miss"] <= gbase["deadline_miss"], \
        bench["governor-diurnal"]
    # fleet power cap at 70% of the perf-endpoint draw: watts_p95 must
    # never exceed the cap, and the clamped run must still serve every
    # request the pinned always-energy twin serves (the cap pins the
    # governor to the same energy-endpoint schedule; only the drain tail
    # of the final batch shifts, hence the 1% throughput band)
    cap = _cap_watts(0.7)
    ebase = _run(30.0, 16.0, 16.0, seed=3, mix=_swa_mix(),
                 backend=backend, mode="energy", pin_mode=True)
    capped = _run(30.0, 16.0, 16.0, seed=3, mix=_swa_mix(),
                  backend=backend, governor=True, power_cap=cap)
    bench["energy-capped"] = {
        "power_cap_w": cap,
        "watts_p95": capped["watts_p95"],
        "watts_mean": capped["watts_mean"],
        "throughput_req_s": capped["throughput_req_s"],
        "completed": capped["completed"],
        "energy_mode_throughput_req_s": ebase["throughput_req_s"],
        "energy_mode_completed": ebase["completed"],
        "joules_per_req": capped["joules_per_req"],
        "opoint_switches": capped["opoint_switches"],
    }
    assert capped["watts_p95"] <= cap + 1e-6, bench["energy-capped"]
    assert capped["completed"] >= ebase["completed"], bench["energy-capped"]
    assert (capped["throughput_req_s"]
            >= 0.99 * ebase["throughput_req_s"]), bench["energy-capped"]
    path = out or (REPO / "BENCH_serving.json")
    path.write_text(json.dumps(bench, indent=1))
    print(f"[smoke] {path}: thp={bench['throughput_req_s']} req/s "
          f"p99={bench['p99_ms']}ms E/req={bench['energy_per_req_J']}J "
          f"overlap={bench['overlap_ratio']}x")
    print(f"[smoke] cluster-2worker: "
          f"thp={bench['cluster-2worker']['throughput_req_s']} req/s "
          f"cross-worker overlap="
          f"{bench['cluster-2worker']['cross_worker_overlap']}x")
    print(f"[smoke] slow-host: oblivious "
          f"thp={bench['slow-host']['oblivious_throughput_req_s']} req/s "
          f"-> aware+steal "
          f"thp={bench['slow-host']['aware_steal_throughput_req_s']} req/s "
          f"({bench['slow-host']['steals']} steals)")
    print(f"[smoke] learned-slow-host: "
          f"thp={bench['learned-slow-host']['throughput_req_s']} req/s "
          f"({bench['learned-slow-host']['vs_declared']:.0%} of declared) "
          f"scale_err={bench['learned-slow-host']['learned_scale_err']}")
    print(f"[smoke] replicated-hot-cell: "
          f"thp={bench['replicated-hot-cell']['throughput_req_s']} req/s "
          f"({bench['replicated-hot-cell']['speedup']}x of baseline "
          f"{bench['replicated-hot-cell']['baseline_throughput_req_s']}) "
          f"replicas={bench['replicated-hot-cell']['replicas']}")
    print(f"[smoke] autoscale-diurnal: "
          f"thp={bench['autoscale-diurnal']['throughput_req_s']} req/s "
          f"flip_lead={bench['autoscale-diurnal']['mode_flip_lead_s']}s "
          f"actions={bench['autoscale-diurnal']['autoscale_actions']} "
          f"prewarms={bench['autoscale-diurnal']['prewarms']}")
    print(f"[smoke] governor-diurnal: "
          f"J/req={bench['governor-diurnal']['joules_per_req']} "
          f"(-{bench['governor-diurnal']['joules_reduction']:.1%} vs "
          f"always-perf {bench['governor-diurnal']['always_perf_joules_per_req']}) "
          f"miss={bench['governor-diurnal']['deadline_miss']} "
          f"switches={bench['governor-diurnal']['opoint_switches']}")
    print(f"[smoke] energy-capped: "
          f"watts_p95={bench['energy-capped']['watts_p95']} "
          f"<= cap={bench['energy-capped']['power_cap_w']}W "
          f"thp={bench['energy-capped']['throughput_req_s']} req/s "
          f"(energy-mode twin "
          f"{bench['energy-capped']['energy_mode_throughput_req_s']})")
    print(f"[smoke] scheduler: dp/1k={bench['dp_per_1k_req']} "
          f"place p50={bench['place_ms_p50']}ms "
          f"p99={bench['place_ms_p99']}ms; "
          f"{len(bench['snapshots'])} snapshot rows")
    print(f"[smoke] tracing: {bench['tracing']['spans']} spans, "
          f"overhead={bench['tracing']['overhead_frac']:+.1%} wall "
          f"({bench['tracing']['disabled_wall_s']}s -> "
          f"{bench['tracing']['enabled_wall_s']}s)")
    return bench


def main(quiet: bool = False, backend: str = "analytic"):
    t = Timer()
    rows = []
    for label, peak, trough in (("trough-only", 1.0, 0.25),
                                ("diurnal", 8.0, 0.5),
                                ("saturating", 24.0, 2.0)):
        r = _run(60.0, peak, trough, backend=backend)
        r["scenario"] = label
        rows.append(r)
    r = _run(60.0, 8.0, 0.5, backend=backend,
             events=(PoolEvent(20.0, "fail", "FPGA", 2),
                     PoolEvent(40.0, "join", "FPGA", 2)))
    r["scenario"] = "diurnal+failure"
    rows.append(r)
    r = _run(60.0, 8.0, 0.5, backend=backend, async_mode=False)
    r["scenario"] = "diurnal-sync"
    rows.append(r)
    r = _run(60.0, 8.0, 0.5, backend=backend, cluster=2)
    r["scenario"] = "cluster-2worker"
    rows.append(r)
    from repro.cluster import ClusterEvent
    r = _run(60.0, 8.0, 0.5, backend=backend, cluster=2,
             cluster_script=(ClusterEvent(20.0, "kill", "w1"),))
    r["scenario"] = "cluster-kill-worker"
    rows.append(r)
    # heterogeneous fleet: w1 is a 60x-slow host. 'slow-host-oblivious'
    # plans as if it were healthy (legacy placement, no steal) — the tail
    # explodes; 'slow-host-aware+steal' places by effective throughput,
    # re-solves per host, and steals pending batches to the dry fast
    # worker — throughput should recover to the uniform cluster's level
    slow = {"w1": 60.0}
    r = _run(60.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
             profiles=slow, host_aware=False)
    r["scenario"] = "slow-host-oblivious"
    rows.append(r)
    r = _run(60.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
             profiles=slow, host_aware=False, steal=True)
    r["scenario"] = "slow-host-steal-only"
    rows.append(r)
    r = _run(60.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
             profiles=slow, steal=True)
    r["scenario"] = "slow-host-aware+steal"
    rows.append(r)
    # the same 60x host with NO declared profile: the estimator discovers
    # it online; compare against slow-host-aware+steal directly above
    r = _run(60.0, SLOW_PEAK, 2.0, backend=backend, cluster=2,
             truth_profiles=slow, learn=True, steal=True)
    r["scenario"] = "learned-slow-host"
    rows.append(r)
    r = _run(60.0, 8.0, 0.5, backend=backend, cluster=2,
             autoscale=True, forecast_horizon=5.0, mode_cooldown=5.0)
    r["scenario"] = "autoscale-diurnal"
    rows.append(r)
    # one hot signature saturating the fleet: unreplicated twin vs the
    # controller promoting the hot cell onto both workers
    r = _run(60.0, REP_PEAK, 8.0, backend=backend, cluster=2,
             mix=_hot_mix(), forecast_horizon=5.0,
             deadline_slack=REP_SLACK)
    r["scenario"] = "hot-cell-baseline"
    rows.append(r)
    r = _run(60.0, REP_PEAK, 8.0, backend=backend, cluster=2,
             mix=_hot_mix(), forecast_horizon=5.0,
             deadline_slack=REP_SLACK, replicate_hot=2)
    r["scenario"] = "replicated-hot-cell"
    rows.append(r)
    # continuous Pareto governor: diurnal frontier walk vs the pinned
    # always-perf twin, and the 70%-of-peak power cap (docs/energy.md)
    r = _run(60.0, 8.0, 0.5, seed=3, backend=backend, mix=_energy_mix(),
             pin_mode=True)
    r["scenario"] = "governor-baseline-perf"
    rows.append(r)
    r = _run(60.0, 8.0, 0.5, seed=3, backend=backend, mix=_energy_mix(),
             governor=True)
    r["scenario"] = "governor-diurnal"
    rows.append(r)
    r = _run(60.0, 16.0, 16.0, seed=3, backend=backend, mix=_swa_mix(),
             governor=True, power_cap=_cap_watts(0.7))
    r["scenario"] = "energy-capped"
    rows.append(r)
    write_json("serving_stream", rows)
    if not quiet:
        for r in rows:
            print(f"{r['scenario']:22s} req={r['requests']:5d} "
                  f"thp={r['throughput_req_s']:6.2f}/s "
                  f"p50={r['p50_ms']:7.1f}ms p99={r['p99_ms']:8.1f}ms "
                  f"DP/1k={r['dp_per_1k_req']:5.1f} "
                  f"place={r['place_ms_p50']:6.3f}ms "
                  f"overlap={r['overlap_ratio']:5.2f}x "
                  f"xworker={r['cross_worker_overlap']:5.2f}x "
                  f"steals={r['steals']:3d} "
                  f"sim-req/wall-s={r['sim_req_per_wall_s']:8.1f}")
    return rows, t.us


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short run; writes BENCH_serving.json at repo root")
    ap.add_argument("--backend", default="analytic",
                    choices=("analytic", "pallas"))
    args = ap.parse_args()
    from repro.launch.compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        smoke(backend=args.backend)
    else:
        main(backend=args.backend)
