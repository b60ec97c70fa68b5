#!/usr/bin/env python3
"""Readings that the proxy plug-in's limit is set from, in one process.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \\
        --seconds 10 --first-seed <n>

For each seed: the cell's stack, its warm-up and a window of ``--seconds``
at the cell's own load, exactly as a run makes them; then the program's
numbers after the drain. For the first ``--control-seeds`` seeds also the
control's: the reference chain in bfloat16 on the device, the nearest
precision below the configuration's, put in the program's place on the same
batches (a hook of the proxy plug-in, ``plugins/proxy.py``, which both
cells use). One JSON line per seed, then the summary: the lower reading
(the largest the program gives) and the upper (the smallest the control
gives) of each gap. Benchmark runs never run the control. Exits with 2 unless JAX's
first device is a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def readings(workload: str, seed: int, seconds: float, *,
             control: bool) -> dict:
    import jax

    from bench import harness

    platform = jax.devices()[0].platform
    served = harness.serve_window(workload, seed, seconds,
                                  log=lambda msg: None)
    out = {"seed": seed, "batches": served.window.batches}
    nums = harness.numbers(served, platform)
    nums.pop("_by_shape")
    out.update(nums)
    proxy = served.recorder.plugin   # the control is the proxy's hook
    records = served.recorder.records
    recs = proxy.host_records(records)
    gaps = {}
    if records:
        _, gaps["act_batch"], gaps["act_dim"] = records[0].input.shape
    out["vs_float32"] = proxy.output_gaps(recs, operands="float32", **gaps)
    if control:
        out["control"] = proxy.output_gaps(
            recs, operands=proxy.operands(platform), control=True, **gaps)
        out["control_vs_float32"] = proxy.output_gaps(
            recs, operands="float32", control=True, **gaps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable
    enable()
    rows = []
    for i in range(args.seeds):
        r = readings(args.workload, args.first_seed + i, args.seconds,
                     control=i < args.control_seeds)
        rows.append(r)
        print(json.dumps(r), flush=True)
    lower = max(r["worst_answer_gap"] for r in rows)
    ctrl = [r["control"]["worst_answer_gap"] for r in rows if "control" in r]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower_worst_answer_gap": lower,
        "upper_worst_answer_gap": min(ctrl) if ctrl else None,
        "lower_max_abs_gap": max(r["_max_abs_gap"] for r in rows),
        "upper_max_abs_gap": min((r["control"]["max_abs_gap"]
                                  for r in rows if "control" in r),
                                 default=None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
