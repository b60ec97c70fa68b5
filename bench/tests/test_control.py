"""The control, at a size a test run holds: the reference chain in bfloat16
put in the program's place fails the output check that the program passes.
On the chip the same readings come from ``bench/control.py``."""
import pytest

from bench import control, harness, stack

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_program_passes(cell, no_compile_cache):
    r = control.readings(cell, 2**31 + 17, 0.3, control=True)
    cfg = stack.load_config(next(w["config"] for w in
                                 harness.load_benchmark()["workloads"]
                                 if w["name"] == cell))
    limit = stack.plugin(cfg).LIMITS["worst_answer_gap"]
    assert r["batches"] > 0
    assert r["worst_answer_gap"] <= limit
    assert r["control"]["worst_answer_gap"] > limit
