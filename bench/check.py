"""The comparison that decides ``correct``.

Serving layers (``serving.router``, ``serving.engine``, ``tenancy``): after
the drain, every request the harness submitted has completed once or been
counted as dropped, and none is queued or in flight; every batch
dispatched in the window was recorded, and what was kept of its output is
on the accelerator.

The device work: the configuration's plug-in (``plugins/<name>.py``)
compares what was kept of each batch with its plain reference and gives
its own numbers and ``LIMITS``.

Each number is compared with its limit; ``correct`` holds when every number
is at or under its limit. The readings each limit was set from are in
``PERF.md``.
"""
from __future__ import annotations

import collections

# number -> limit
LIMITS = {
    "lost": 0,             # requests neither completed nor dropped
    "left_over": 0,        # requests still queued or in flight after drain
    "duplicated": 0,       # completions beyond one per request
    "off_device": 0,       # checked outputs not on the accelerator
    "unchecked": 0,        # batches dispatched in the window with no output
}


def accounting(submitted: int, completed_rids, dropped: int, left: int):
    uniq = len(set(completed_rids))
    return {"lost": submitted - uniq - dropped,
            "left_over": left,
            "duplicated": len(completed_rids) - uniq}


def recorded(records, platform: str, dispatched: int) -> dict:
    """``off_device`` and ``unchecked`` of the window's records
    (``stack.Record``), and ``_by_shape``, the count of records by (stages,
    inputs), printed only."""
    def platforms(arr):
        devices = getattr(arr, "devices", None)
        return {d.platform for d in devices()} if devices else set()

    return {"off_device": sum(1 for r in records
                              if platforms(r.kept) != {platform}),
            "unchecked": dispatched - len(records),
            "_by_shape": dict(collections.Counter((len(r.kinds), r.m)
                                                  for r in records))}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit in ``LIMITS`` or in the plug-in's ``limits``, every one of which
    has to be there; names starting with ``_`` are printed but not
    compared."""
    limits = {**LIMITS, **limits}
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in numbers.items() if k in limits}
    missing = set(limits) - set(checks)
    ok = not missing and all(c["value"] <= c["limit"]
                             for c in checks.values())
    return ok, checks
