"""The rates in the traffic files: each cell's traffic, swings and bursts
included, is sustained on the simulated clock and drops nothing, and its
``provisioned_rate`` is sustained at a constant rate."""
import pytest

from bench import harness, knee

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traffic_drops_nothing(cell):
    r = knee.sweep_one(cell, None, 120.0, 2**31 + 3)
    assert r["offered"] > 0 and r["dropped"] == 0 and r["sustained"]


@pytest.mark.parametrize("cell", CELLS)
def test_provisioned_rate_is_sustained(cell):
    from bench import arrivals
    cfg = next(c for c in harness.load_benchmark()["workloads"]
               if c["name"] == cell)
    rate = arrivals.load_traffic(cfg["traffic"])["provisioned_rate"]
    assert knee.sweep_one(cell, rate, 120.0, 2**31 + 4)["sustained"]
