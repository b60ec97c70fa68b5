"""Configurations as plug-ins: the proxy plug-in compares as the check did
before it moved there, and a toy configuration made only of new files
under ``tests/data/toy`` is served, checked and read by the harness."""
import json
import sys

import numpy as np
import pytest

from bench import files, harness, peaks, reference, stack
from bench.tests import cells

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


# what bench/control.py's call of output_gaps(control=True) gave on these
# records before the control became the plug-in's hook
BEFORE_CONTROL = {"cpu": (0.0012022799346596003, 0.004126518964767456),
                  "tpu": (0.0011323501821607351, 0.004058748483657837)}


@pytest.mark.parametrize("platform", sorted(BEFORE_CONTROL))
def test_proxy_control_is_the_control_before(platform):
    proxy = stack.plugin({})
    records = _proxy_records()
    got = proxy.control(records, platform)
    old = proxy.output_gaps(proxy.host_records(records),
                            operands=proxy.operands(platform), control=True)
    assert (got["worst_answer_gap"], got["_max_abs_gap"]) == \
        (old["worst_answer_gap"], old["max_abs_gap"]) == \
        BEFORE_CONTROL[platform]


@pytest.mark.parametrize("c", cells.ALL, ids=lambda c: c.id)
def test_every_cell_has_its_plugins_limits(c):
    plug = cells.plugin_of(c)
    assert plug.LIMITS and not set(plug.LIMITS) & set(harness.check.LIMITS)
    for hook in ("workload", "backend", "warm", "keep", "numbers",
                 "control"):
        assert callable(getattr(plug, hook)), hook
    faults = getattr(plug, "FAULTS", {})
    assert all(isinstance(k, str) and callable(f) for k, f in faults.items())


# ---------------------------------------------------------------------------
# a configuration made of new files alone
# ---------------------------------------------------------------------------
@pytest.fixture
def toy(monkeypatch, no_compile_cache):
    """The harness pointed at ``tests/data/toy`` first, with the toy's
    ``"cpu"`` preset, as the per-cell tests run it; the toy plug-in."""
    cells.point(monkeypatch, cells.TOY_CELLS[0], cpu=True)
    return files.load("plugins", "toy_doubler")


def test_harness_takes_the_configuration_as_written(monkeypatch,
                                                    no_compile_cache):
    """``bench/run.py``, ``knee.py`` and ``control.py`` load configurations
    through ``stack.load_config``: the ``"cpu"`` preset is not applied
    there, only by the tests' ``cells.point(..., cpu=True)``."""
    toy_cell = cells.TOY_CELLS[0]
    cells.point(monkeypatch, toy_cell)
    written = json.loads(files.find("configs", "toy-doubler",
                                    ".json").read_text())
    want = written["backend"]["max_inputs"]
    assert want != written["cpu"]["backend"]["max_inputs"]
    served = harness.serve_window(toy_cell.name, SEED, 0.2, log=_log)
    assert served.cfg == written
    assert served.recorder.backend.max_inputs == want
    cells.point(monkeypatch, toy_cell, cpu=True)
    served = harness.serve_window(toy_cell.name, SEED, 0.2, log=_log)
    assert served.recorder.backend.max_inputs == \
        written["cpu"]["backend"]["max_inputs"]


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

