"""CPU rehearsals of each cell through the harness at a tiny window."""
import json
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import cells

FIRST = harness.load_benchmark()["workloads"][0]["name"]
SEED = 2**31 + 99


def _log(msg):
    print(msg, file=sys.stderr)


@pytest.mark.parametrize("cell", cells.CELLS, indirect=True)
def test_result_line(cell):
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


#: the program span that each per-layer metric of ``program_spans`` reads
SPAN_OF = {"batch_form_ms": "batcher.next_batch", "admit_ms": "engine.admit",
           "preempt_pass_ms": "router.preempt_pass",
           "micro_build_ms": "backend.microbatches",
           "launch_us": "backend.dispatch"}


@pytest.mark.parametrize("cell", cells.CELLS, indirect=True)
def test_traced_result_line(cell, monkeypatch):
    seen = []
    metrics_for = harness.metrics_for

    def keep_window(bench, cell, trace, w):
        seen.append(w)
        return metrics_for(bench, cell, trace, w)
    monkeypatch.setattr(harness, "metrics_for", keep_window)
    out = harness.run_cell(cell, SEED, 0.5, True, log=_log)
    assert out["correct"] is True
    # the cell's per-layer metrics; the CPU has no device plane, so those
    # read from the device trace find nothing
    want = {m["name"] for m in harness.load_benchmark()["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"}
    assert want and set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    w, = seen
    # every step; any other span where a metric of the cell reads it (a
    # steady cell admits all its cells in warm-up: no engine.admit in the
    # window)
    assert {"router.step"} | {SPAN_OF[m] for m in want & set(SPAN_OF)} <= \
        set(w.span_totals)
    for sec, n, own in w.span_totals.values():
        assert n > 0 and 0 <= own <= sec + 1e-12
    assert w.batches > 0
    assert w.counters.get("launches", w.batches) >= w.batches
    assert w.queue_wait_s and len(w.dispatched) == w.batches
    assert w.device_programs == {} and w.device_kind == "cpu"
    assert w.work is getattr(cells.plugin(cell), "work", None)


def test_untraced_window_carries_no_trace_fields(no_compile_cache):
    served = harness.serve_window(FIRST, SEED, 0.3, log=_log)
    w = served.window
    assert served.router.tracer.timing is False
    assert (w.span_totals, w.counters, w.queue_wait_s, w.device_programs,
            w.device_kind, w.dispatched, w.work) == (None,) * 7
    assert served.recorder.records and w.batches > 0


def test_no_accelerator_no_result(tmp_path):
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         FIRST, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=harness.ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and not p.stdout.strip()


def test_bare_checkout_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", FIRST, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and not p.stdout.strip()
