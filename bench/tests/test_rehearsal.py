"""CPU rehearsals of each cell through the harness at a tiny window."""
import json
import shutil
import subprocess
import sys

import pytest

from bench import harness

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
SEED = 2**31 + 99


def _log(msg):
    print(msg, file=sys.stderr)


@pytest.mark.parametrize("cell", CELLS)
def test_result_line(cell, no_compile_cache):
    out = json.loads(json.dumps(harness.run_cell(cell, SEED, 0.5, False,
                                                 log=_log)))
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = [m for m in harness.load_benchmark()["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    for m in e2e:
        assert out["metrics"][m["name"]]["unit"] == m["unit"]
        assert out["metrics"][m["name"]]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_traced_result_line(no_compile_cache):
    cell = CELLS[0]
    out = harness.run_cell(cell, SEED, 0.5, True, log=_log)
    assert out["correct"] is True
    # the CPU has no device plane: the counters' metrics only
    assert {"place_ms", "batch_occupancy", "reschedules_per_kreq"} <= \
        set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_no_result(tmp_path):
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=harness.ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and not p.stdout.strip()


def test_bare_checkout_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and not p.stdout.strip()
