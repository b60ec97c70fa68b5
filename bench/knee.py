#!/usr/bin/env python3
"""The highest rate a cell's stack sustains on the simulated clock.

    python3 bench/knee.py --workload <cell> --rates 10,20,40 [--sim-s 300]
    python3 bench/knee.py --workload <cell> [--sim-s 300]

For each rate: the cell's stack, with the analytic backend in place of the
pallas one (completions come from the same schedule model on the simulated
clock, so what is sustained does not depend on the device or the host), fed
the cell's traffic mix with its swings and bursts taken out, Poisson at that
constant rate, for ``--sim-s`` simulated seconds. One JSON line per rate:
requests offered, completed and dropped, the backlog left at the end, and
the modelled latency's 95th percentile, and the objective flips. A rate is
sustained when under 1% of what was offered is dropped or left behind. The
traffic files set ``provisioned_rate`` to the highest rate sustained and
the rate to about four fifths of it. Without ``--rates``, the cell's
traffic as it stands, swings and bursts included, for a check that it
drops nothing. Needs no accelerator.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def sweep_one(workload: str, rate: float | None, sim_s: float,
              seed: int) -> dict:
    from repro.runtime import AnalyticBackend
    from repro.serving import Request

    from bench import arrivals, harness, stack

    cell = next(c for c in harness.load_benchmark()["workloads"]
                if c["name"] == workload)
    cfg = stack.load_config(cell["config"])
    spec = copy.deepcopy(arrivals.load_traffic(cell["traffic"]))
    if rate is not None:
        spec["arrivals"] = {"mean_rate": rate, "swing": None,
                            "bursts": None}
        spec["provisioned_rate"] = rate
    router, _ = stack.build(cfg, spec["provisioned_rate"],
                            backend=AnalyticBackend())
    stream = arrivals.Stream(spec, seed)
    wls, rid, arrived = {}, 0, {}
    lat = []
    while stream.t < sim_s:
        for a in stream.next_tick():
            wl = wls.get(a.name) or wls.setdefault(
                a.name, stack.workload(a.name, cfg))
            arrived[rid] = a.t
            router.submit(Request(rid, wl, a.t, deadline=a.deadline,
                                  kind=a.kind, tenant=a.tenant), a.t)
            rid += 1
        for r in router.step(stream.t):
            lat.append(stream.t - arrived[r.rid])
    left = len(router.queue) + len(router.engine.inflight)
    lat.sort()
    p95 = lat[min(len(lat) - 1, int(0.95 * len(lat)))] if lat else None
    dropped = router.metrics.dropped
    return {"rate": rate, "offered": rid, "completed": len(lat),
            "dropped": dropped, "left": left, "modelled_p95_s": p95,
            "objective_flips": sum(m.startswith("mode ->")
                                   for m in router.log),
            "preemptions": router.metrics.preemptions,
            "sustained": (dropped + left) < 0.01 * rid}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None,
                    help="comma-separated requests per simulated second")
    ap.add_argument("--sim-s", type=float, default=300.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    rates = [None] if args.rates is None else \
        [float(x) for x in args.rates.split(",")]
    for r in rates:
        print(json.dumps(sweep_one(args.workload, r, args.sim_s, args.seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
