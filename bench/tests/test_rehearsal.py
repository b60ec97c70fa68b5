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


#: the per-layer metrics read from the program's spans and counters
PROGRAM = {"batch_form_ms", "admit_ms", "micro_build_ms", "launch_us",
           "launches_per_batch", "queue_wait_wall_p95_ms"}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_result_line(cell, no_compile_cache, monkeypatch):
    seen = []
    metrics_for = harness.metrics_for

    def keep_window(bench, cell, trace, w):
        seen.append(w)
        return metrics_for(bench, cell, trace, w)
    monkeypatch.setattr(harness, "metrics_for", keep_window)
    out = harness.run_cell(cell, SEED, 0.5, True, log=_log)
    assert out["correct"] is True
    # the CPU has no device plane: the counters' and spans' metrics only
    tenancy = cell == "llm-tenants-bursty"
    want = {"place_ms", "batch_occupancy", "reschedules_per_kreq"} | \
        PROGRAM | ({"preempt_pass_ms"} if tenancy else set())
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    w, = seen
    assert {"router.step", "backend.dispatch", "engine.admit"} <= \
        set(w.span_totals)
    for sec, n, own in w.span_totals.values():
        assert n > 0 and 0 <= own <= sec + 1e-12
    assert w.counters["launches"] >= w.batches > 0
    assert w.queue_wait_s and len(w.dispatched) == w.batches
    assert w.device_programs == {} and w.device_kind == "cpu"
    assert w.work is None          # the proxy has no work hook


def test_untraced_window_carries_no_trace_fields(no_compile_cache):
    served = harness.serve_window(CELLS[0], SEED, 0.3, log=_log)
    w = served.window
    assert served.router.tracer.timing is False
    assert (w.span_totals, w.counters, w.queue_wait_s, w.device_programs,
            w.device_kind, w.dispatched, w.work) == (None,) * 7
    assert served.recorder.records and w.batches > 0


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
