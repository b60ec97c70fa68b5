"""Plug-in of the configurations that serve the pallas backend's proxy stage
chain: GCN and SWA-T signatures for the DP, an (m <= 8, 8, 16) float32
activation through one jit a stage on the chip.

The hooks every plug-in provides (``PERF.md`` section 4 lists them):
``workload``, ``backend``, ``warm``, ``keep``, ``numbers``, ``control`` and
``LIMITS``; this one has no ``work``, since its stages do no model's
arithmetic, and one fault of its own in ``FAULTS``.
"""
from __future__ import annotations

import numpy as np

from bench import reference

# number -> limit, beside the serving limits of check.py; the readings it
# was set from are in PERF.md
LIMITS = {"worst_answer_gap": 3.0e-4}


def workload(name: str, cfg: dict):
    """The configuration's workload ``name`` as a ``repro`` Workload."""
    from repro.core.workload import (GraphDataset, gcn_workload,
                                     swa_transformer_workload)

    w = cfg["workloads"][name]
    if w["model"] == "gcn":
        ds = GraphDataset(w["graph"], w["vertices"], w["edges"],
                          w["features"])
        return gcn_workload(ds, hidden=w["hidden"], layers=w["layers"])
    if w["model"] == "swa_t":
        return swa_transformer_workload(
            w["seq_len"], w["window"], layers=cfg["swa_layers"], d=w["d"],
            heads=w["heads"], ffn_mult=w["ffn_mult"])
    raise ValueError(f"{name}: unknown model {w['model']!r}")


def backend(cfg: dict):
    from repro.runtime import PallasPipelineBackend

    return PallasPipelineBackend(**cfg["backend"])


def warm(backend, handle) -> None:
    """Runs a new stage structure once for each microbatch count a batch
    can bring, so that nothing compiles in the measured window."""
    import jax

    for m in range(1, backend.max_micro + 1):
        jax.block_until_ready(backend.dispatch(handle,
                                               backend.microbatches(m)))


def keep(output):
    """The whole (m, 8, 16) output stays on the device for the check."""
    return output


def output_gaps(records, *, operands: str, act_batch: int = 8,
                act_dim: int = 16, control: bool = False) -> dict:
    """``records``: (stage kinds, microbatch count, host array) per batch.
    A batch of n requests carries min(n, 8) microbatches, one answer each;
    an answer's gap is the mean absolute difference from the reference over
    its (8, 16) values, and ``worst_answer_gap`` is the largest over every
    answer of every batch. With ``control`` the bfloat16 chain stands in
    the program's place."""
    refs, ctrl = {}, {}
    worst = max_abs = 0.0
    for kinds, m, got in records:
        key = (kinds, m)
        if key not in refs:
            micro = reference.microbatch(m, act_batch, act_dim)
            refs[key] = reference.stage_chain(kinds, micro,
                                              operands=operands)
            if control:
                ctrl[key] = reference.control_chain(kinds, micro)
        out = ctrl[key] if control else np.asarray(got, np.float32)
        if out.shape != refs[key].shape or not np.isfinite(out).all():
            return {"worst_answer_gap": float("inf"),
                    "max_abs_gap": float("inf")}
        d = np.abs(out - refs[key])
        worst = max(worst, float(d.mean(axis=(1, 2)).max()))
        max_abs = max(max_abs, float(d.max()))
    return {"worst_answer_gap": worst, "max_abs_gap": max_abs}


def operands(platform: str) -> str:
    """How the platform's float32 matmul sees its operands by default."""
    return "bfloat16" if platform == "tpu" else "float32"


def host_records(records) -> list:
    """(stage kinds, m, host array) of the recorded batches, copied from
    the device in one call."""
    import jax

    host = jax.device_get([r.kept for r in records])
    return [(r.kinds, r.m, h) for r, h in zip(records, host)]


def _gaps(records, platform: str, control: bool) -> dict:
    if not records:
        return {"worst_answer_gap": 0.0, "_max_abs_gap": 0.0}
    _, act_batch, act_dim = records[0].input.shape
    gaps = output_gaps(host_records(records), operands=operands(platform),
                       act_batch=act_batch, act_dim=act_dim, control=control)
    return {"worst_answer_gap": gaps["worst_answer_gap"],
            "_max_abs_gap": gaps["max_abs_gap"]}


def numbers(records, platform: str) -> dict:
    """``worst_answer_gap`` over the recorded batches (``stack.Record``),
    against the reference chain at the platform's matmul precision; the
    (8, 16) activation shape is the recorded input's."""
    return _gaps(records, platform, control=False)


def control(records, platform: str) -> dict:
    """``numbers`` with the chain redone in bfloat16 on the device, the
    nearest precision below the configuration's, in the program's place on
    the same batches. Benchmark runs never call it (``bench/control.py``)."""
    return _gaps(records, platform, control=True)


def _state_unchanged(monkeypatch) -> None:
    """Every stage returns its activation unchanged."""
    from repro.runtime import PallasPipelineBackend
    monkeypatch.setattr(PallasPipelineBackend, "_stage_fn",
                        lambda self, kinds: (lambda p, x: x))


# fault name -> a function of pytest's monkeypatch that plants it, for
# bench/tests/test_faults.py beside the faults every plug-in can have
FAULTS = {"state_unchanged": _state_unchanged}
