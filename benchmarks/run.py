"""Benchmark orchestrator: one function per paper table/figure + roofline.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only NAME]
Prints ``name,us_per_call,derived`` CSV at the end.
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    from . import (fig6_p2p, fig7_gnn_datasets, fig8_transformer_sweep,
                   fig9_pareto, roofline, sched_latency, serving_stream,
                   table3_accuracy, table4_improvement, table5_schedules)

    suite = [
        ("fig6_p2p", fig6_p2p.main),
        ("sched_latency", sched_latency.main),
        ("serving_stream", serving_stream.main),
        ("table5_schedules", table5_schedules.main),
        ("fig9_pareto", fig9_pareto.main),
        ("fig7_gnn_datasets", fig7_gnn_datasets.main),
        ("fig8_transformer_sweep", fig8_transformer_sweep.main),
        ("table4_improvement", table4_improvement.main),
        ("table3_accuracy", table3_accuracy.main),
        ("roofline", roofline.main),
    ]
    rows = []
    for name, fn in suite:
        if args.only and args.only != name:
            continue
        payload, us = fn()
        derived = _derived(name, payload)
        rows.append((name, us, derived))

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")


def _derived(name: str, payload) -> str:
    try:
        if name == "fig6_p2p":
            return f"max_speedup={max(r['speedup'] for r in payload):.2f}x"
        if name == "sched_latency":
            cold = max(r["seconds"] for r in payload if "cold" in r["what"])
            return f"max_cold_solve={cold:.2f}s"
        if name == "serving_stream":
            diurnal = next(r for r in payload if r["scenario"] == "diurnal")
            return (f"dp_per_1k={diurnal['dp_per_1k_req']};"
                    f"sim_req_per_wall_s={diurnal['sim_req_per_wall_s']}")
        if name == "table5_schedules":
            return (f"static_opt={payload['static_matches_optimal']};"
                    f"fleetrec_opt={payload['fleetrec_matches_optimal']}")
        if name == "fig9_pareto":
            return f"fronts={sum(len(v) for v in payload.values())}"
        if name == "fig7_gnn_datasets":
            ok = all(r["dype"][0] >= r["fleetrec"][0] - 1e-9
                     >= r["static"][0] - 2e-9 for r in payload)
            return f"ordering_dype_ge_fleetrec_ge_static={ok}"
        if name == "fig8_transformer_sweep":
            import statistics
            return (f"avg_thp_gain={statistics.mean(r['thp_gain'] for r in payload):.2f}x")
        if name == "table4_improvement":
            a = payload["Average"]["perf"]
            return (f"perf_vs_fleetrec={a['FleetRec*'][0]:.2f}x;"
                    f"perf_vs_gpu={a['GPU-only'][0]:.2f}x")
        if name == "table3_accuracy":
            s = sum(r["sub_optimal"] for r in payload)
            t = sum(r["total"] for r in payload)
            return f"suboptimal={s}/{t}"
        if name == "roofline":
            n = len(payload)
            dom = {}
            for c in payload:
                dom[c["dominant"]] = dom.get(c["dominant"], 0) + 1
            return f"cells={n};dominant={dom}"
    except Exception as e:  # pragma: no cover
        return f"derived_error={e!r}"
    return "-"


if __name__ == "__main__":
    main()
