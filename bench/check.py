"""The comparison that decides ``correct``.

Serving layers (``serving.router``, ``serving.engine``, ``tenancy``): after
the drain, every request the harness submitted has completed once or been
counted as dropped, and none is queued or in flight.

``runtime.backend`` on the chip: the output of every batch dispatched in the
window, kept on the device until the window closed, is on the accelerator
and matches the plain reference of its stage chain (``reference.py``) at the
precision the configuration states.

Each number is compared with its limit; ``correct`` holds when every number
is at or under its limit. The readings each limit was set from are in
``PERF.md``.
"""
from __future__ import annotations

import collections

import numpy as np

from . import reference

# number -> limit
LIMITS = {
    "lost": 0,             # requests neither completed nor dropped
    "left_over": 0,        # requests still queued or in flight after drain
    "duplicated": 0,       # completions beyond one per request
    "off_device": 0,       # checked outputs not on the accelerator
    "unchecked": 0,        # batches dispatched in the window with no output
    "worst_answer_gap": 3.0e-4,
}


def accounting(submitted: int, completed_rids, dropped: int, left: int):
    uniq = len(set(completed_rids))
    return {"lost": submitted - uniq - dropped,
            "left_over": left,
            "duplicated": len(completed_rids) - uniq}


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


def outputs(records, platform: str, dispatched: int, *, operands: str,
            act_batch: int = 8, act_dim: int = 16) -> dict:
    import jax

    off = sum(1 for _, _, arr in records
              if {d.platform for d in arr.devices()} != {platform})
    host = jax.device_get([arr for _, _, arr in records])
    gaps = output_gaps([(k, m, h) for (k, m, _), h in zip(records, host)],
                       operands=operands, act_batch=act_batch,
                       act_dim=act_dim)
    return {"off_device": off, "unchecked": dispatched - len(records),
            "worst_answer_gap": gaps["worst_answer_gap"],
            "_max_abs_gap": gaps["max_abs_gap"],
            "_by_shape": dict(collections.Counter((len(k), m)
                                                  for k, m, _ in records))}


def verdict(numbers: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit; names starting with ``_`` are printed but not compared."""
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in numbers.items() if k in LIMITS}
    missing = set(LIMITS) - set(checks)
    ok = not missing and all(c["value"] <= c["limit"]
                             for c in checks.values())
    return ok, checks
