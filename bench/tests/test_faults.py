"""A run with the timed path broken underneath has to read ``correct``
false: once for each fault that a served cell can have. The look for a chip
is skipped; the rest of the run is the harness's own."""
import sys

import pytest

from bench import harness

SEED = 2**31 + 5


def _state_unchanged(monkeypatch):
    """Every stage returns its activation unchanged."""
    from repro.runtime import PallasPipelineBackend
    monkeypatch.setattr(PallasPipelineBackend, "_stage_fn",
                        lambda self, kinds: (lambda p, x: x))


def _half_batch(monkeypatch):
    """Half of each batch is left out of its completion report."""
    from repro.serving.router import Router
    apply = Router._apply_report

    def half(self, cell, batch, report, at=None):
        batch.requests = batch.requests[:(len(batch.requests) + 1) // 2]
        return apply(self, cell, batch, report, at=at)
    monkeypatch.setattr(Router, "_apply_report", half)


def _answer_altered(monkeypatch):
    """One answer of one batch in a hundred is altered where it is made:
    its first row of features is negated."""
    from repro.runtime import PallasPipelineBackend
    dispatch = PallasPipelineBackend.dispatch
    count = [0]

    def altered(self, handle, micro):
        outs = dispatch(self, handle, micro)
        count[0] += 1
        if count[0] % 100:
            return outs
        last = outs[-1].at[0, 0].multiply(-1.0)
        return outs[:-1] + (last,)
    monkeypatch.setattr(PallasPipelineBackend, "dispatch", altered)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_incorrect(cell, fault, monkeypatch, no_compile_cache):
    FAULTS[fault](monkeypatch)
    out = harness.run_cell(cell, SEED, 0.5, False,
                           log=lambda m: print(m, file=sys.stderr))
    assert out["correct"] is False
    failed = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    print(fault, failed, file=sys.stderr)
    assert failed


