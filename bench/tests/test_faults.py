"""A run with the timed path broken underneath has to read ``correct``
false: once for each fault that a served cell can have. The look for a chip
is skipped; the rest of the run is the harness's own.

The value faults act at the backend instance's ``dispatch``, the seam that
``stack.Recorder`` wraps and that every plug-in's ``submit`` goes through,
so they reach any plug-in. A plug-in's ``FAULTS`` adds faults of its own:
name -> a function of pytest's ``monkeypatch`` that plants the fault."""
import sys

import pytest

from bench import harness, stack
from bench.tests import cells

SEED = 2**31 + 5


def _at_dispatch(monkeypatch, alter):
    """Every served backend's ``dispatch`` returns ``alter(outs)`` in place
    of its arrays ``outs`` (output last)."""
    init = stack.Recorder.__init__

    def faulty(self, backend, plug):
        dispatch = backend.dispatch
        backend.dispatch = lambda handle, x: alter(dispatch(handle, x))
        init(self, backend, plug)
    monkeypatch.setattr(stack.Recorder, "__init__", faulty)


def _answer_altered(monkeypatch):
    """One answer of one batch in a hundred is altered where it is made:
    the value of the output largest in magnitude is negated."""
    import jax.numpy as jnp
    count = [0]

    def alter(outs):
        count[0] += 1
        if count[0] % 100:
            return outs
        last = outs[-1]
        at = jnp.unravel_index(jnp.argmax(jnp.abs(last)), last.shape)
        return outs[:-1] + (last.at[at].multiply(-1),)
    _at_dispatch(monkeypatch, alter)


def _answer_wrong(monkeypatch):
    """Every batch answers with the stage before the last's output, or with
    zeros where there is none of the output's shape."""
    import jax.numpy as jnp

    def alter(outs):
        last = outs[-1]
        prev = outs[-2] if len(outs) > 1 and outs[-2].shape == last.shape \
            else jnp.zeros_like(last)
        return outs[:-1] + (prev,)
    _at_dispatch(monkeypatch, alter)


def _half_batch(monkeypatch):
    """Half of each batch is left out of its completion report."""
    from repro.serving.router import Router
    apply = Router._apply_report

    def half(self, cell, batch, report, at=None):
        batch.requests = batch.requests[:(len(batch.requests) + 1) // 2]
        return apply(self, cell, batch, report, at=at)
    monkeypatch.setattr(Router, "_apply_report", half)


FAULTS = {"answer_altered": _answer_altered, "answer_wrong": _answer_wrong,
          "half_batch": _half_batch}


def _cases():
    """(cell, fault name) for every cell: the faults above, then those of
    the cell's plug-in."""
    out = []
    for c in cells.ALL:
        own = getattr(cells.plugin_of(c), "FAULTS", {})
        for name in [*sorted(FAULTS), *sorted(own)]:
            out.append(pytest.param(c, name, id=f"{name}-{c.id}"))
    return out


@pytest.mark.parametrize("cell,fault", _cases(), indirect=["cell"])
def test_fault_reads_incorrect(cell, fault, monkeypatch):
    plant = FAULTS.get(fault) or cells.plugin(cell).FAULTS[fault]
    plant(monkeypatch)
    out = harness.run_cell(cell, SEED, 0.5, False,
                           log=lambda m: print(m, file=sys.stderr))
    assert out["correct"] is False
    failed = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    print(fault, failed, file=sys.stderr)
    assert failed
