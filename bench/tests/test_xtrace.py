"""The reduction from trace to metrics, on a trace recorded on a TPU v5e:
six ticks of paper-mix-diurnal with the harness's spans."""
from pathlib import Path

import pytest

from bench import xtrace

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xtrace.read(str(SMALL))


def test_planes_and_spans(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    names = {n for n, _, _ in trace.spans}
    assert {"bench:submit", "bench:step", "bench:engine.reap"} <= names
    t0, t1 = trace.window
    assert 0 < t1 - t0 < 1e9                   # under a second


def test_busy_is_the_union_inside_the_window(trace):
    t0, t1 = trace.window
    ops = trace.ops["/device:TPU:0"]
    inside = [(max(s, t0), min(e, t1)) for _, s, e in ops]
    summed = sum(e - s for s, e in inside if e > s)
    busy = xtrace.busy_ns(trace)
    assert 0 < busy <= summed
    assert busy == pytest.approx(7554.0)      # read off this trace


def test_idle_gaps_cover_the_rest(trace):
    t0, t1 = trace.window
    gaps = xtrace.idle_gaps(trace, k=100)
    idle = sum(v for _, v in gaps)
    assert idle * 1e9 + xtrace.busy_ns(trace) == pytest.approx(t1 - t0)
    assert all(n.split(" (")[0] in {"submit", "step", "engine.reap",
                                    "engine.submit", "backend.dispatch",
                                    "backend.resolve", "none"}
               for n, _ in gaps)


def test_top_programs(trace):
    top = xtrace.top_programs(trace)
    assert top and all(v > 0 for _, v in top)
    assert any(n.startswith("jit_apply") for n, _ in top)


def test_merged():
    assert xtrace.merged([(0, 2), (1, 3), (5, 6), (7, 9)], 1, 8) == \
        [(1, 3), (5, 6), (7, 8)]
