"""Configurations as plug-ins: the proxy plug-in compares as the check did
before it moved there, and a toy configuration made only of new files
under ``tests/data/toy`` is served, checked and read by the harness."""
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import files, harness, peaks, reference, stack

TOY = Path(__file__).parent / "data" / "toy"
SEED = 2**31 + 41


def _log(msg):
    print(msg, file=sys.stderr)


# ---------------------------------------------------------------------------
# the proxy plug-in
# ---------------------------------------------------------------------------
def _proxy_records():
    """Fixed (kinds, m) batches with outputs a little off the reference."""
    shapes = [((("spmm", "gemm"), ("gemm",)), 3),
              ((("win_attn", "gemm", "gemm"),), 8),
              ((("gemm",), ("win_attn",), ("gemm", "gemm")), 1),
              ((("spmm", "gemm"), ("gemm",)), 3)]
    out = []
    for i, (kinds, m) in enumerate(shapes):
        micro = reference.microbatch(m)
        got = reference.stage_chain(kinds, micro, operands="bfloat16")
        got = got + np.float32(1e-5) * (i + 1) * np.sin(
            np.arange(got.size, dtype=np.float32)).reshape(got.shape)
        out.append(stack.Record("w", kinds, m, micro, got.astype(np.float32)))
    return out


# what check.output_gaps gave on these records before it moved into the
# proxy plug-in: worst_answer_gap, max_abs_gap
BEFORE = {"cpu": (0.0009061070159077644, 0.002594292163848877),
          "tpu": (2.5424407795071602e-05, 3.999471664428711e-05)}


@pytest.mark.parametrize("platform", sorted(BEFORE))
def test_proxy_numbers_are_the_checks_before(platform):
    proxy = stack.plugin({})
    got = proxy.numbers(_proxy_records(), platform)
    assert (got["worst_answer_gap"], got["_max_abs_gap"]) == BEFORE[platform]
    assert proxy.LIMITS == {"worst_answer_gap": 3.0e-4}


def test_every_cell_has_its_plugins_limits():
    for w in harness.load_benchmark()["workloads"]:
        plug = stack.plugin(stack.load_config(w["config"]))
        assert plug.LIMITS and not set(plug.LIMITS) & set(harness.check.LIMITS)
        for hook in ("workload", "backend", "warm", "keep", "numbers"):
            assert callable(getattr(plug, hook)), hook


# ---------------------------------------------------------------------------
# a configuration made of new files alone
# ---------------------------------------------------------------------------
@pytest.fixture
def toy(monkeypatch, no_compile_cache):
    """The harness pointed at ``tests/data/toy`` first; the toy plug-in."""
    for kind, dirs in files.DIRS.items():
        monkeypatch.setitem(files.DIRS, kind, [TOY / kind, *dirs])
    monkeypatch.setattr(files, "BENCHMARK", TOY / "BENCHMARK.json")
    return files.load("plugins", "toy_doubler")


def test_toy_is_served_checked_and_read(toy, monkeypatch):
    seen = []
    metrics_for = harness.metrics_for

    def keep_window(bench, cell, trace, w):
        seen.append(w)
        return metrics_for(bench, cell, trace, w)
    monkeypatch.setattr(harness, "metrics_for", keep_window)
    out = harness.run_cell("toy-steady", SEED, 0.5, True, log=_log)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == set(harness.check.LIMITS) | {"toy_gap"}
    assert out["checks"]["toy_gap"]["value"] == 0.0
    assert out["attempted"] > 0 and out["failed"] == 0
    # the span reader reads the toy's own span; the CPU has no device
    # plane, so the roofline reader finds nothing and is left out
    assert out["metrics"]["toy_dispatch_us"]["value"] > 0
    assert out["metrics"]["batch_occupancy"]["value"] > 0
    assert "toy_stage_roofline" not in out["metrics"]
    w, = seen
    assert w.counters["doublings"] >= w.counters["launches"] > 0
    assert w.dispatched and all(wl in ("toy-gcn", "toy-swa")
                                for wl, _, _ in w.dispatched)
    assert w.work is toy.work and w.device_kind == "cpu"

    # the roofline reader on the same dispatches, with device time as a
    # v5e trace would give it
    roofline = files.load("metrics", "toy_stage_roofline").read
    stages = sum(len(kinds) for _, kinds, _ in w.dispatched)
    rows = sum(len(kinds) * m for _, kinds, m in w.dispatched)
    w.device_kind = "TPU v5 lite"
    w.device_programs = {"jit_toy_stage(123)": (1e-3, stages),
                         "jit_other(4)": (5.0, 1)}
    p = peaks.PEAKS["TPU v5 lite"]
    want = 100.0 * (8.0 * 4 * rows / p["hbm_bytes_per_s"]) / 1e-3
    assert roofline(w) == pytest.approx(want)
    w.device_kind = "unknown chip"
    assert roofline(w) is None


def test_toy_broken_stage_reads_incorrect(toy, monkeypatch):
    def broken(x):
        return x + x + 1.0
    monkeypatch.setattr(toy, "toy_stage", broken)
    out = harness.run_cell("toy-steady", SEED, 0.5, False, log=_log)
    assert out["correct"] is False
    assert out["checks"]["toy_gap"]["value"] > 0
    assert all(out["checks"][k]["value"] == 0 for k in harness.check.LIMITS)

