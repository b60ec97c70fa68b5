"""The one arrival generator: reads a traffic file and yields, tick by tick,
the requests that arrive in each tick, from ``--seed`` alone.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

``tick_s``
    length of one control tick in simulated seconds.
``arrivals``
    Poisson(rate(t) * tick) arrivals per tick, placed uniformly inside it,
    with ``rate(t) = base * swing(t) * burst(t)``:

    ``mean_rate``
        requests per simulated second, averaged over swings and bursts.
    ``swing``: ``{"trough_share", "period_s"}`` or null
        a cosine load curve, 1 at t = 0 and ``trough_share`` at half a
        period (the arithmetic of ``repro.serving.traffic.TrafficSim.rate``,
        copied so that no change to the program moves the yardstick).
    ``bursts``: ``{"mult", "len_s", "period_s"}`` or null
        one burst of ``len_s`` at ``mult`` times the rate in every
        ``period_s``, starting at a point of the period that the seed draws.
        Every period holds the same burst, so every seed offers the same
        load; only where the bursts fall changes.

    ``base`` is ``mean_rate`` over the mean of ``swing * burst``.
``requests``
    ``{"mix": [{"name", "kind", "weight"}, ...],
    "tenants": [{"name", "weight"}, ...]}``: each arrival draws a workload
    and, where ``tenants`` is given, apart from it a tenant, by weight.
``deadline_slack_s``
    seconds from arrival to deadline, or null for none.
``provisioned_rate``, ``warmup_sim_s``
    read by the harness, not here.

The stream has no end: the harness reads as many ticks as its wall-clock
window lasts.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float                       # simulated seconds
    name: str                      # workload name in the configuration
    kind: str
    tenant: str = ""
    deadline: float | None = None


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _cum(weights) -> np.ndarray:
    w = np.asarray(weights, dtype=float)
    return np.cumsum(w / w.sum())


class Stream:
    """Arrivals of one traffic mix under one seed, one tick at a time."""

    def __init__(self, spec: dict, seed: int):
        self.tick = float(spec["tick_s"])
        self.slack = spec.get("deadline_slack_s")
        bursts_ss, arrivals_ss = np.random.SeedSequence(seed).spawn(2)
        self.rng = np.random.default_rng(arrivals_ss)
        self.burst_rng = np.random.default_rng(bursts_ss)
        arr = spec["arrivals"]
        self.swing = arr.get("swing")
        self.bursts = arr.get("bursts")
        mean = 1.0
        if self.swing:
            mean *= 0.5 * (1.0 + float(self.swing["trough_share"]))
        if self.bursts:
            b = self.bursts
            mean *= 1.0 + (b["mult"] - 1.0) * b["len_s"] / b["period_s"]
        self.base = float(arr["mean_rate"]) / mean
        self._burst_period = -1
        self._burst_start = 0.0
        req = spec["requests"]
        self.items = [(m["name"], m["kind"]) for m in req["mix"]]
        self.cum = _cum([m["weight"] for m in req["mix"]])
        tenants = req.get("tenants") or []
        self.tenants = [t["name"] for t in tenants]
        self.tenant_cum = _cum([t["weight"] for t in tenants]) \
            if tenants else None
        self.t = 0.0

    def _burst_mult(self, t: float) -> float:
        b = self.bursts
        period = float(b["period_s"])
        k = int(t // period)
        while self._burst_period < k:      # one draw per period, in order
            self._burst_period += 1
            self._burst_start = self.burst_rng.uniform(
                0.0, period - float(b["len_s"]))
        off = t - k * period
        inside = self._burst_start <= off < self._burst_start + b["len_s"]
        return float(b["mult"]) if inside else 1.0

    def rate(self, t: float) -> float:
        """Offered requests per simulated second at time ``t``."""
        r = self.base
        if self.swing:
            s = float(self.swing["trough_share"])
            phase = 0.5 * (1.0 + math.cos(
                2.0 * math.pi * t / float(self.swing["period_s"])))
            r *= s + (1.0 - s) * phase
        if self.bursts:
            r *= self._burst_mult(t)
        return r

    def next_tick(self) -> list[Arrival]:
        """Arrivals in [t, t + tick); advances the stream by one tick."""
        t = self.t
        self.t = t + self.tick
        rng = self.rng
        n = int(rng.poisson(self.rate(t) * self.tick))
        if not n:
            return []
        offs = np.sort(rng.uniform(0.0, self.tick, n))
        picks = np.searchsorted(self.cum, rng.random(n), side="right")
        if self.tenant_cum is None:
            tenants = [""] * n
        else:
            idx = np.searchsorted(self.tenant_cum, rng.random(n),
                                  side="right")
            tenants = [self.tenants[i] for i in idx]
        out = []
        for o, p, ten in zip(offs, picks, tenants):
            at = t + float(o)
            ddl = None if self.slack is None else at + float(self.slack)
            name, kind = self.items[int(p)]
            out.append(Arrival(at, name, kind, ten, ddl))
        return out
