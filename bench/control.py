#!/usr/bin/env python3
"""Readings that a plug-in's limits are set from, in one process.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \\
        --seconds 10 --first-seed <n>

For each seed: the cell's stack, its warm-up and a window of ``--seconds``
at the cell's own load, exactly as a run makes them; then the program's
numbers after the drain. For the first ``--control-seeds`` seeds also the
control's: the plug-in's ``control(records, platform)`` hook, its numbers
with the device work redone one precision below the configuration's, put
in the program's place on the same batches. One JSON line per seed, then
the summary: for each number of the plug-in's ``LIMITS`` and each printed
one (``_`` names) that the control gives, the lower reading (the largest
the program gives) and the upper (the smallest the control gives).
Benchmark runs never run the control. Exits with 2 unless JAX's first
device is a TPU.
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
    if control:
        out["control"] = served.recorder.plugin.control(
            served.recorder.records, platform)
    return out


def summary(rows: list, limits: dict) -> dict:
    """{name: (lower, upper)} of each number of ``limits`` and each printed
    one (``_`` names) that the control gives: the largest reading of the
    program over ``rows`` and the smallest of the control (None without
    a control reading)."""
    ctrl = [r["control"] for r in rows if "control" in r]
    printed = [k for k in (ctrl[0] if ctrl else {}) if k.startswith("_")]
    return {k: (max(r[k] for r in rows), min((c[k] for c in ctrl),
                                             default=None))
            for k in [*limits, *printed]}


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
    from bench import harness, stack
    from repro.launch.compile_cache import enable
    enable()
    cell = next(c for c in harness.load_benchmark()["workloads"]
                if c["name"] == args.workload)
    limits = stack.plugin(stack.load_config(cell["config"])).LIMITS
    rows = []
    for i in range(args.seeds):
        r = readings(args.workload, args.first_seed + i, args.seconds,
                     control=i < args.control_seeds)
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        **{f"{end}_{k.lstrip('_')}": v
           for k, pair in summary(rows, limits).items()
           for end, v in zip(("lower", "upper"), pair)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
