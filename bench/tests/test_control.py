"""The control, at a size a test run holds: the plug-in's ``control`` hook,
its numbers with the device work one precision below the configuration's,
fails a limit that the program passes. On the chip the same readings come
from ``bench/control.py``."""
import pytest

from bench import control
from bench.tests import cells


@pytest.mark.parametrize("cell", cells.CELLS, indirect=True)
def test_control_fails_program_passes(cell):
    r = control.readings(cell, 2**31 + 17, 0.3, control=True)
    limits = cells.plugin(cell).LIMITS
    assert r["batches"] > 0
    assert all(r[k] <= limit for k, limit in limits.items())
    assert any(r["control"][k] > limit for k, limit in limits.items())
    s = control.summary([r], limits)
    for k in limits:
        assert s[k] == (r[k], r["control"][k])
