#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``). The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks`` compared, each
with its limit; the same checks are the last lines of standard error. Exits
with 2, printing no result, unless JAX's devices are TPUs and as many as
the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cells = {c["name"]: c for c in harness.load_benchmark()["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: {sorted(cells)}",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    want = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"needs {want} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s) ({devices[0].device_kind})",
              file=sys.stderr)
        return 2
    log = lambda msg: print(f"[bench] {msg}", file=sys.stderr, flush=True)
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; jax {jax.__version__}")
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), log=log)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
