"""The rates in the traffic files: each cell's traffic, swings and bursts
included, is sustained on the simulated clock and drops nothing, and its
``provisioned_rate`` is sustained at a constant rate. The analytic backend
serves, so the configuration is taken as written, as ``bench/knee.py``
takes it on the chip: its ``"cpu"`` preset does not apply."""
import pytest

from bench import arrivals, harness, knee
from bench.tests import cells


@pytest.mark.parametrize("cell_files", cells.CELLS, indirect=True)
def test_cell_traffic_drops_nothing(cell_files):
    r = knee.sweep_one(cell_files, None, 120.0, 2**31 + 3)
    assert r["offered"] > 0 and r["dropped"] == 0 and r["sustained"]


@pytest.mark.parametrize("cell_files", cells.CELLS, indirect=True)
def test_provisioned_rate_is_sustained(cell_files):
    cfg = next(c for c in harness.load_benchmark()["workloads"]
               if c["name"] == cell_files)
    rate = arrivals.load_traffic(cfg["traffic"])["provisioned_rate"]
    assert knee.sweep_one(cell_files, rate, 120.0, 2**31 + 4)["sustained"]
